"""Readers' view of a program counter over the blocks fast-sync applied:
how far it rose between the `/metrics` pulls at the window's start and
end, over the rise of `tendermint_fastsync_blocks_applied_total`. A
program without the series, or a window with no block applied, gives
None (as the stage readers do: `fastsync_stages.py`)."""

from __future__ import annotations

from benchmark.lib import rpc
from benchmark.lib.fastsync_stages import BLOCKS


def per_block(obs: dict, name: str) -> float | None:
    start, end = obs["metrics_start"], obs["metrics_end"]
    blocks = rpc.rise(start, end, BLOCKS)
    if name not in end or blocks <= 0:
        return None
    return rpc.rise(start, end, name) / blocks
