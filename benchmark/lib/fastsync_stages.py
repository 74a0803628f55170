"""Readers' view of fast-sync's stage clock (`blockchain/reactor.py`): how
far `tendermint_fastsync_stage_seconds{stage}` and its two counters rose
between the `/metrics` pulls at the window's start and end. A program
without these series (one older than PR 24) reads as no block applied,
and every reader then gives None."""

from __future__ import annotations

from benchmark.lib import rpc

SECONDS = "tendermint_fastsync_stage_seconds_sum"
BLOCKS = "tendermint_fastsync_blocks_applied_total"
WINDOWS = "tendermint_fastsync_windows_total"
# `decode` is left out: it runs on the p2p receive thread, beside these
SYNC_THREAD = (
    "part_set", "verify_submit", "verify_wait", "store",
    "validate", "exec", "state_save", "starved",
)


def _rise(obs: dict, name: str, **labels) -> float:
    return rpc.rise(obs["metrics_start"], obs["metrics_end"], name, **labels)


def ms_per_block(obs: dict, stage: str) -> float | None:
    blocks = _rise(obs, BLOCKS)
    if blocks <= 0:
        return None
    return 1e3 * _rise(obs, SECONDS, stage=stage) / blocks


def share_of_window(obs: dict, stages: tuple[str, ...]) -> float | None:
    """The stages' seconds as a share of the window's, in percent."""
    if _rise(obs, BLOCKS) <= 0:
        return None
    start, end = obs["window"]
    return 100.0 * sum(_rise(obs, SECONDS, stage=s) for s in stages) / (end - start)


def window_cut_share(obs: dict, cut: str) -> float | None:
    """Windows that ended for the reason `cut` (`full`, `pool_gap`,
    `boundary`) over all windows joined, in percent."""
    windows = _rise(obs, WINDOWS)
    if _rise(obs, BLOCKS) <= 0 or windows <= 0:
        return None
    return 100.0 * _rise(obs, WINDOWS, cut=cut) / windows


def full_window_share(obs: dict) -> float | None:
    return window_cut_share(obs, "full")
