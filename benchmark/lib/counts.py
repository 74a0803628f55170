"""Bytes a device launch must move, from its shapes alone.

A table-verify launch:

The algorithm reads the validator set's comb table once (64 windows x 16
entries x 60 limbs, int16, per validator: `ops/ed25519_tables.py`
`_to_fused_layout`), reads three 32-byte rows per lane (S, h and R) and
writes one verdict byte per lane. What an implementation moves beyond that
(the materialized path writes every selected entry through HBM) is its own
cost, not the algorithm's, and lowers its share.

A Merkle tree (`ops/merkle_kernel.py`): every leaf's padded SHA-256 message
comes in (64 B a block of `0x00 || leaf`, padded as SHA-256 pads it), its
32-byte digest goes out, and at every level each pair of nodes is read
(2 x 32 B) and its parent written (32 B); a node without a partner moves
up as it is and costs nothing. Rows that pad the leaves to a power of two
are the implementation's.
"""

from __future__ import annotations

import json
import os

TABLE_BYTES_PER_VALIDATOR = 64 * 16 * 60 * 2  # 122,880
LANE_BYTES_IN = 3 * 32
LANE_BYTES_OUT = 1


def verify_launch_bytes(n_validators: int, commits: int) -> int:
    """Least bytes one launch of `commits` stacked commits over an
    `n_validators` set moves between HBM and the cores."""
    lanes = n_validators * commits
    return (
        TABLE_BYTES_PER_VALIDATOR * n_validators
        + lanes * (LANE_BYTES_IN + LANE_BYTES_OUT)
    )


NODE_BYTES = 32
SHA256_BLOCK_BYTES = 64


def merkle_tree_bytes(leaf_sizes: list[int]) -> int:
    """Least bytes one SimpleMerkle tree over leaves of these sizes (in
    bytes, without the one-byte leaf prefix) moves between HBM and the
    cores."""
    # prefix + leaf + 0x80 + the 8-byte bit length, in whole blocks
    blocks = sum((1 + n + 9 + SHA256_BLOCK_BYTES - 1) // SHA256_BLOCK_BYTES for n in leaf_sizes)
    moved = blocks * SHA256_BLOCK_BYTES + len(leaf_sizes) * NODE_BYTES
    nodes = len(leaf_sizes)
    while nodes > 1:
        moved += (nodes // 2) * 3 * NODE_BYTES
        nodes = (nodes + 1) // 2
    return moved


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; a device that is not in the
    table is an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in peaks.json"
        )
    return table[device_kind]
