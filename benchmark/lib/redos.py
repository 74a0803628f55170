"""Readers' view of fast-sync's redos (`blockchain/reactor.py` `_redo`,
PR 47): how far the redo counters rose between the `/metrics` pulls at the
window's start and end. A redo forgets a block that cannot be what the
chain committed, with whatever else its server delivered, and debits that
server; `whose="others"` counts forgotten blocks that another peer served,
and the histogram runs from a redo to the apply of the height it was
called at. A program without the series gives None."""

from __future__ import annotations

from benchmark.lib import rpc

REDOS = "tendermint_fastsync_redos_total"
DROPPED = "tendermint_fastsync_redo_blocks_dropped_total"
RECOVER = "tendermint_fastsync_redo_recover_seconds"


def _rise(obs: dict, name: str, **labels) -> float | None:
    if name not in obs["metrics_end"]:
        return None
    return rpc.rise(obs["metrics_start"], obs["metrics_end"], name, **labels)


def in_window(obs: dict) -> float | None:
    """Redos inside the window, every cause."""
    return _rise(obs, REDOS)


def sound_blocks_dropped(obs: dict) -> float | None:
    """Blocks a redo forgot that the debited peer did not serve."""
    return _rise(obs, DROPPED, whose="others")


def recover_ms(obs: dict) -> float | None:
    """Mean time from a redo to the apply of its height, over the
    recoveries that ended in the window."""
    ended = _rise(obs, RECOVER + "_count")
    if not ended or ended <= 0:
        return None
    return 1e3 * _rise(obs, RECOVER + "_sum") / ended
