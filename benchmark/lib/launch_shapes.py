"""Readers' view of the shape a commit window was launched at
(`services/verifier.py` `commit_launch_shape`): the launch record's
`k_launch`, `n_launch` and `rows_padded`, on the window's tagged verify
launches that a device backend answered."""

from __future__ import annotations

from benchmark.lib import ledger


def device_launches(obs: dict) -> list[dict] | None:
    """The window's tagged launches a device backend answered, or None
    where there is none or the program's records carry no launch shape
    (one older than PR 27)."""
    recs = [r for r in ledger.tagged(obs) if r.get("backend") in ledger.DEVICE_BACKENDS]
    if not recs or not all(r.get("k_launch") and r.get("n_launch") for r in recs):
        return None
    return recs
