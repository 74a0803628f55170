"""Readers' view of the verify spine's table cache
(`services/verifier.py` `TableBatchVerifier._tables_for`): how far
`tendermint_verify_table_cache_total{event}` rose between the `/metrics`
pulls at the window's start and end. `hit` and `miss` count lookups (a
launch's, and a `prebuild`'s of a set the cache does not hold);
`incremental` the misses answered by concatenating new keys' columns to a
cached set's and gathering; `host_build` the builds on the host behind an
open breaker. A program without the series gives None."""

from __future__ import annotations

from benchmark.lib import rpc

EVENTS = "tendermint_verify_table_cache_total"


def _rise(obs: dict, event: str) -> float:
    return rpc.rise(obs["metrics_start"], obs["metrics_end"], EVENTS, event=event)


def miss_share(obs: dict) -> float | None:
    lookups = _rise(obs, "hit") + _rise(obs, "miss")
    if EVENTS not in obs["metrics_end"] or lookups <= 0:
        return None
    return 100.0 * _rise(obs, "miss") / lookups


def incremental_share(obs: dict) -> float | None:
    misses = _rise(obs, "miss")
    if EVENTS not in obs["metrics_end"] or misses <= 0:
        return None
    return 100.0 * _rise(obs, "incremental") / misses
