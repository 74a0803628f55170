"""Readers' view of the launch ledger (`dump_telemetry?launches=N`): the
verify launches the fast-sync reactor tagged with the heights they cover,
and those of them inside a traced stretch."""

from __future__ import annotations

KERNEL = "verify_tables_kernel"
VERIFY_KINDS = ("verify", "tables")
DEVICE_BACKENDS = ("tables", "mesh")


def tagged(obs: dict, lo: float | None = None, hi: float | None = None) -> list[dict]:
    out = []
    for r in obs["launches"]:
        # a table-verify launch closes under kind "tables", a flat one under
        # "verify": what marks a fast-sync window is the reactor's tag
        if r.get("kind") not in VERIFY_KINDS or r.get("error") or r.get("height_lo") is None:
            continue
        if lo is not None and not lo <= float(r["t"]) <= hi:
            continue
        out.append(r)
    return out


def commits(rec: dict) -> int:
    return int(rec["height_hi"]) - int(rec["height_lo"]) + 1


def traced_kernel(obs: dict):
    """(device seconds per chip of the table-verify executables, the
    launches inside the traced stretch that the device answered), or None
    without a device trace. A window of fewer than 512 lanes goes to the
    host library: its signatures and bytes are no work of the kernel's."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds = sum(v for k, v in tr["modules"].items() if KERNEL in k)
    recs = [
        r
        for r in tagged(obs, tr["wall0"], tr["wall0"] + tr["window_s"])
        if r.get("backend") in DEVICE_BACKENDS
    ]
    if seconds <= 0 or not recs:
        return None
    return seconds, recs
