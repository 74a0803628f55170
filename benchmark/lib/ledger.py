"""Readers' view of the launch ledger (`dump_telemetry?launches=N`): the
verify launches the fast-sync reactor tagged with the heights they cover,
and those of them inside a traced stretch."""

from __future__ import annotations

KERNEL = "verify_tables_kernel"
# `ops/merkle_kernel.py` `_leafhash_and_reduce`: a tree's leaf hashes and
# levels, one executable a padded shape
TREE_KERNEL = "leafhash_and_reduce"
HASH_DEVICE_BACKENDS = ("device", "mesh")
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


def device_trees(obs: dict) -> list[dict]:
    """The window's launch records of Merkle roots a device backend built
    (`services/hasher.py` closes one a tree, kind `hash`, `rows` its
    leaves)."""
    return [
        r for r in obs["launches"]
        if r.get("kind") == "hash" and not r.get("error") and r.get("backend") in HASH_DEVICE_BACKENDS
    ]


def traced_trees(obs: dict):
    """(device seconds per chip of the tree executables in the traced
    stretch, how many times they ran, the real leaves of a tree), or None
    without a device trace or a device tree. The runs are counted in the
    trace itself; what a tree holds is read off the window's records,
    which all carry the mix's one leaf count."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds = sum(v for k, v in tr["modules"].items() if TREE_KERNEL in k)
    runs = sum(v for k, v in tr.get("module_runs", {}).items() if TREE_KERNEL in k)
    leaves = {int(r.get("rows", 0)) for r in device_trees(obs)}
    if seconds <= 0 or runs <= 0 or len(leaves) != 1:
        return None
    return seconds, runs, leaves.pop()
