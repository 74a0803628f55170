"""Crash-point injection (role of ebuchman/fail-test in the reference).

`fail_point()` calls are numbered in program order per process; when the
`FAIL_TEST_INDEX` env var equals the current index the process exits
immediately with status 1 — exactly the reference's semantics
(`consensus/state.go:1172-1233`, `state/execution.go:224-243`,
`test/persist/test_failure_indices.sh:39-41`). Used by the
kill-at-every-persistence-step recovery test matrix.
"""

from __future__ import annotations

import os
import sys

_counter = 0


class SimulatedCrash(BaseException):
    """In-process stand-in for the os._exit crash: derives from
    BaseException so the consensus receive loop's fault isolation
    (`except Exception`) cannot swallow it — the loop's thread dies
    mid-step exactly where the process would have. Raised instead of
    exiting when FAIL_TEST_SOFT is set (multi-node in-process chaos
    harnesses kill ONE node, not the whole test process)."""


def fail_point() -> None:
    global _counter
    target = os.environ.get("FAIL_TEST_INDEX")
    if target is None:
        return
    if _counter == int(target):
        if os.environ.get("FAIL_TEST_SOFT"):
            _counter += 1  # don't re-trip on the next call after restart
            raise SimulatedCrash(f"FAIL_TEST_INDEX={target}")
        sys.stderr.write(f"FAIL_TEST_INDEX={target}: exiting at fail point\n")
        sys.stderr.flush()
        os._exit(1)
    _counter += 1


def reset_for_testing() -> None:
    global _counter
    _counter = 0


# -- device fault injection ---------------------------------------------------
#
# The accelerator-dispatch analog of fail_point(): force device backend
# calls (batch verify, device merkle) to raise deterministically so the
# resilient-dispatch layer (`services/resilient.py`) can be driven
# through its degrade→probe→recover cycle in tests. Selected by the
# TENDERMINT_TPU_DEVICE_FAIL env var — "verify", "hash", "tables"
# (valset comb-table construction), "all", with an
# optional per-kind budget: "verify:3" fails the first 3 verify
# dispatches then clears; comma-separate for multiple kinds — or at
# runtime via set_device_fault()/clear_device_faults().


class InjectedDeviceFault(RuntimeError):
    """A test-injected device failure (stands in for compile errors,
    runtime errors, and dispatch timeouts)."""


_device_faults: dict[str, int] | None = None  # kind -> remaining (-1 = forever)


def _load_device_faults() -> dict[str, int]:
    global _device_faults
    if _device_faults is None:
        faults: dict[str, int] = {}
        spec = os.environ.get("TENDERMINT_TPU_DEVICE_FAIL", "")
        for part in filter(None, (p.strip() for p in spec.split(","))):
            kind, _, count = part.partition(":")
            faults[kind] = int(count) if count else -1
        _device_faults = faults
    return _device_faults


def set_device_fault(kind: str, count: int = -1) -> None:
    """Arm fault injection for `kind` ("verify"/"hash"/"tables"/"all");
    `count` dispatches fail (-1 = until cleared)."""
    _load_device_faults()[kind] = count


def clear_device_faults() -> None:
    global _device_faults
    _device_faults = {}


def device_faults_armed() -> bool:
    """Any fault injection configured (env or runtime)? The service
    layer uses this to wrap resilient dispatch even on host-only runs."""
    return bool(_load_device_faults())


def device_fail_point(kind: str) -> None:
    """Raise InjectedDeviceFault when a fault is armed for `kind` (or
    "all"), consuming one unit of a bounded budget."""
    faults = _load_device_faults()
    for k in (kind, "all"):
        remaining = faults.get(k)
        if remaining is None or remaining == 0:
            continue
        if remaining > 0:
            faults[k] = remaining - 1
        raise InjectedDeviceFault(f"injected {kind} device fault")


class ShardDeviceFault(InjectedDeviceFault):
    """A device fault attributable to ONE shard of a mesh (the
    per-chip analog of InjectedDeviceFault): the mesh layer catches it
    and re-meshes onto the survivors instead of tripping the whole
    verify breaker."""

    def __init__(self, shard: int):
        super().__init__(f"injected device fault on mesh shard {shard}")
        self.shard = shard


def shard_fail_point(indices) -> None:
    """Per-shard analog of `device_fail_point`: spec entries of the form
    "shard<i>" (optionally "shard<i>:<count>") fail launches that include
    device index `i` in their active mesh. Raises `ShardDeviceFault(i)`
    for the lowest armed index in `indices`, consuming budget."""
    faults = _load_device_faults()
    if not faults:
        return
    for i in indices:
        remaining = faults.get(f"shard{i}")
        if remaining is None or remaining == 0:
            continue
        if remaining > 0:
            faults[f"shard{i}"] = remaining - 1
        raise ShardDeviceFault(i)


def shard_fault_armed(index: int) -> bool:
    """True while a fault is still armed for mesh shard `index` (the
    re-probe path peeks without consuming budget)."""
    return bool(_load_device_faults().get(f"shard{index}"))
