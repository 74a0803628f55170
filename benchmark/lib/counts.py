"""Bytes a table-verify launch must move, from its shapes alone.

The algorithm reads the validator set's comb table once (64 windows x 16
entries x 60 limbs, int16, per validator: `ops/ed25519_tables.py`
`_to_fused_layout`), reads three 32-byte rows per lane (S, h and R) and
writes one verdict byte per lane. What an implementation moves beyond that
(the materialized path writes every selected entry through HBM) is its own
cost, not the algorithm's, and lowers its share.
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
