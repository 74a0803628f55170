"""Device-mesh sharding for the verification data plane.

One logical commit (N validator signatures + voting powers) is sharded
along the batch axis across every chip in the mesh; each chip runs the
ed25519 ladder on its shard and the >2/3 power tally is reduced with a
single `psum` over ICI — the collective replaces the reference's
sequential accumulate in `types/validator_set.go:236-261`.

`MeshManager` is the production lifecycle around those kernels: device
discovery (capped by TENDERMINT_TPU_MESH_DEVICES), per-device-set
compiled-step caching, and the survivor re-mesh cycle — a per-shard
device fault (`utils.fail.ShardDeviceFault`, injected via
`TENDERMINT_TPU_DEVICE_FAIL=shard<i>`) drops that chip from the mesh
and recompiles over the survivors, so the verify spine keeps serving on
N-1 chips instead of falling all the way back to host crypto; a
re-probe window later the full mesh is restored. Only when NO devices
survive does the launch raise out to the `CircuitBreaker` in
`services/resilient.py` (host fallback, the PR 1 degradation ladder).
"""

from __future__ import annotations

import os
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tendermint_tpu.ops.ed25519_kernel import verify_kernel
from tendermint_tpu.ops.ed25519_tables import verify_tables_kernel

BATCH_AXIS = "batch"


def batch_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices, batch-sharded."""
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


def sharded_verify_kernel(mesh: Mesh):
    """Compile a batch-sharded verify: (B,32)x4 uint8 -> (B,) bool.

    B must be divisible by the mesh size; callers pad with zero rows
    and slice the output back to the real count (zero rows verify
    False — see pad_to_multiple).
    """
    spec = P(BATCH_AXIS)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
    )
    def _verify(pub, r, s, h):
        return verify_kernel(pub, r, s, h)

    return _verify


def sharded_verify_and_tally(mesh: Mesh):
    """Compile the full commit-verification step over the mesh.

    Inputs: (B,32)x4 uint8 sig batch + (B,) int32 voting powers.
    Returns ((B,) bool verdicts, () int32 verified-power total) — the
    total is psum-reduced across chips so every shard holds the global
    tally (the 2/3-quorum decision needs no host gather).
    """
    spec = P(BATCH_AXIS)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, P()),
    )
    def _step(pub, r, s, h, power):
        ok = verify_kernel(pub, r, s, h)
        # int32 tally: JAX x64 is globally disabled; callers with >2^31
        # total power must scale powers down before shipping them.
        local = jnp.sum(jnp.where(ok, power, 0).astype(jnp.int32))
        total = jax.lax.psum(local, BATCH_AXIS)
        return ok, total

    return _step


def sharded_tables_verify_and_tally(mesh: Mesh):
    """Compile the TABLE fast path — the production steady-state kernel
    (`ops.ed25519_tables`) — over the mesh.

    Sharding is along the VALIDATOR axis: each device holds 1/ndev of the
    comb-table columns (tables (64, 16, 60, N) int16 sharded on the last
    axis — 1.25 GB at N=10k splits to ~160 MB/chip) plus the lanes of its
    own validators for all K stacked commits. Lane arrays must be in
    shard-major order (see shard_lanes_validator_major); the >2/3 power
    tally is psum-reduced so every shard holds the global total.

    Inputs: tables (64, 16, 60, N) int16; s/h/r (K*N, 32) uint8; lane_ok
    (K*N,) bool — the host precheck AND the table build's key_ok tiled
    over commits (an invalid-key table column degrades to a forgeable
    check, so it MUST be masked in-device before the tally); powers
    (K*N,) int32. ALL lane arrays — s, h, r, lane_ok, powers — must be
    in the same shard-major order (shard_lanes_validator_major).
    Returns ((K*N,) bool shard-major verdicts, () int32 global tally).
    """
    lane_spec = P(BATCH_AXIS)
    tbl_spec = P(None, None, None, BATCH_AXIS)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(tbl_spec,) + (lane_spec,) * 5,
        out_specs=(lane_spec, P()),
    )
    def _step(tables, s, h, r, lane_ok, power):
        ok = verify_tables_kernel(tables, s, h, r) & lane_ok
        local = jnp.sum(jnp.where(ok, power, 0).astype(jnp.int32))
        total = jax.lax.psum(local, BATCH_AXIS)
        return ok, total

    return _step


def shard_lanes_validator_major(arrays, n_vals: int, n_shards: int):
    """Reorder commit-major lanes (lane = c*N + v, the
    prepare_commit_lanes layout) into shard-major order (shard, commit,
    local validator) so a P(batch) sharding of the lane axis hands every
    device exactly the lanes of its own table columns. N must divide
    evenly into n_shards blocks."""
    if n_vals % n_shards:
        raise ValueError(f"n_vals {n_vals} not divisible by {n_shards} shards")
    out = []
    for a in arrays:
        k = a.shape[0] // n_vals
        a2 = a.reshape((k, n_shards, n_vals // n_shards) + a.shape[1:])
        out.append(
            np.ascontiguousarray(np.moveaxis(a2, 1, 0)).reshape(a.shape)
        )
    return out


def unshard_lanes_validator_major(a, n_vals: int, n_shards: int):
    """Inverse of shard_lanes_validator_major (device order -> commit-major)."""
    k = a.shape[0] // n_vals
    a2 = a.reshape((n_shards, k, n_vals // n_shards) + a.shape[1:])
    return np.ascontiguousarray(np.moveaxis(a2, 0, 1)).reshape(a.shape)


def mesh_device_count() -> int:
    """Devices the verify mesh should span on this backend.

    TENDERMINT_TPU_MESH_DEVICES: unset/0 = every visible device,
    1 = force the single-device legacy path, N = cap at N. The knob is
    what lets CPU CI (8 virtual devices via
    --xla_force_host_platform_device_count) opt IN and a multi-chip TPU
    host opt OUT."""
    try:
        have = len(jax.devices())
    except Exception:
        return 1
    knob = int(os.environ.get("TENDERMINT_TPU_MESH_DEVICES", "0"))
    if knob <= 0:
        return have
    return min(knob, have)


class MeshExhaustedError(RuntimeError):
    """Every device of the mesh has faulted out; the caller's breaker
    owns the next step (host fallback)."""


def _host_verify_prepared_rows(pub, r, s, h) -> np.ndarray:
    """Bit-faithful host evaluation of the device verify equation
    ([S]B + [h](-A) == R, cofactorless) over prepared (B, 32) rows —
    the `executor="host"` stand-in that lets mesh *choreography* (pad
    geometry, shard faults, survivor re-mesh) run tier-1 on CPU without
    an XLA kernel compile. All-zero pad rows short-circuit to False,
    matching the kernel property documented on `pad_to_multiple`."""
    from tendermint_tpu.crypto import ed25519_ref as ref

    n = pub.shape[0]
    out = np.zeros(n, dtype=bool)
    for i in range(n):
        row_pub, row_r = bytes(pub[i]), bytes(r[i])
        row_s, row_h = bytes(s[i]), bytes(h[i])
        if row_pub == b"\x00" * 32 and row_r == b"\x00" * 32:
            continue  # zero pad row: verifies False by construction
        a_pt = ref._decode_point(row_pub)
        r_pt = ref._decode_point(row_r)
        if a_pt is None or r_pt is None:
            continue
        s_int = int.from_bytes(row_s, "little")
        h_int = int.from_bytes(row_h, "little")
        neg_a = (
            ref.P - a_pt[0],
            a_pt[1],
            a_pt[2],
            ref.P - a_pt[3],
        )
        check = ref._pt_add(ref._mult_base(s_int), ref._mult_var(h_int, neg_a))
        out[i] = ref._encode_point(check) == row_r
    return out


# Compiled sharded steps keyed by (executor, device tuple, program) so
# every MeshManager in the process (default verifier stack, tests,
# bench) shares one compile per device set — a survivor re-mesh costs
# ONE recompile process-wide, and restoring the full mesh is free.
_STEP_CACHE: dict = {}
_STEP_LOCK = threading.Lock()
# compiles currently building under _STEP_LOCK — the `/health` device
# section's "compile in progress" flag (read without the lock: a
# single-int read is atomic enough for a health probe)
_COMPILES_IN_PROGRESS = 0


def compiles_in_progress() -> int:
    """Compiled-step builds running right now (0 or 1 — builds serialize
    on the step-cache lock). Health reports it so a load balancer can
    tell a compile stall from a dead device."""
    return _COMPILES_IN_PROGRESS


class MeshManager:
    """Mesh lifecycle: discovery, step compilation, survivor re-mesh.

    One manager is shared by the verifier and hasher mesh lanes of a
    process (they degrade together — a sick chip is sick for every
    kernel). Thread-safe: launches from the dispatch worker, re-probes,
    and telemetry snapshots may interleave.

    `executor="host"` swaps the compiled shard_map steps for host
    evaluations with identical verdict semantics and the SAME fault /
    re-mesh choreography — the CPU-CI seam (tests, nemesis chaos) where
    an XLA:CPU kernel compile would cost minutes.
    """

    def __init__(
        self,
        devices=None,
        executor: str = "device",
        reprobe_s: float | None = None,
    ) -> None:
        if executor not in ("device", "host"):
            raise ValueError(f"unknown mesh executor {executor!r}")
        self.executor = executor
        if devices is None:
            devices = list(jax.devices())[: mesh_device_count()]
        self._all = list(devices)
        if not self._all:
            raise ValueError("mesh needs at least one device")
        self._excluded: set[int] = set()
        self._last_fault = 0.0
        self._reprobe_s = (
            float(os.environ.get("TENDERMINT_TPU_MESH_REPROBE_S", "5.0"))
            if reprobe_s is None
            else reprobe_s
        )
        self._lock = threading.RLock()
        self._bind_gauge()

    def _bind_gauge(self) -> None:
        from tendermint_tpu.telemetry import metrics as _metrics

        _metrics.MESH_DEVICES.set(self.n_active)

    # -- topology ----------------------------------------------------------

    @property
    def n_total(self) -> int:
        return len(self._all)

    @property
    def n_active(self) -> int:
        return len(self._all) - len(self._excluded)

    @property
    def degraded(self) -> bool:
        return bool(self._excluded)

    def active_indices(self) -> tuple[int, ...]:
        with self._lock:
            return tuple(
                i for i in range(len(self._all)) if i not in self._excluded
            )

    def mesh(self) -> Mesh:
        with self._lock:
            return batch_mesh([self._all[i] for i in self.active_indices()])

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "executor": self.executor,
                "devices_total": self.n_total,
                "devices_active": self.n_active,
                "excluded": sorted(self._excluded),
            }

    # -- fault / re-mesh cycle ---------------------------------------------

    def check_shard_faults(self) -> None:
        """Injected per-shard fault gate — called at the top of every
        mesh launch with the ACTIVE device indices, so an armed
        `shard<i>` spec only fires while chip i is in the mesh."""
        from tendermint_tpu.utils.fail import shard_fail_point

        shard_fail_point(self.active_indices())

    def record_shard_fault(self, shard: int) -> bool:
        """Drop `shard` from the mesh; True while survivors remain.
        False means the mesh is exhausted — the caller raises to its
        breaker and host crypto takes over."""
        import logging

        from tendermint_tpu.telemetry import metrics as _metrics
        from tendermint_tpu.utils.log import kv, logger

        from tendermint_tpu.telemetry import tracectx as _tracectx
        from tendermint_tpu.telemetry.flightrec import FLIGHT

        with self._lock:
            _metrics.MESH_SHARD_FAULTS.inc()
            self._last_fault = time.monotonic()
            if shard in self._excluded:
                return self.n_active > 0
            self._excluded.add(shard)
            survivors = self.n_active
            if survivors > 0:
                _metrics.MESH_REMESH.labels(direction="shrink").inc()
            self._bind_gauge()
        # a mesh transition is a forensic moment: black-box it and
        # sample everything for a window (same policy as breaker trips)
        FLIGHT.record("mesh", event="shard_fault", shard=shard, survivors=survivors)
        _tracectx.boost()
        kv(
            logger("mesh"),
            logging.WARNING,
            "mesh shard fault",
            shard=shard,
            survivors=survivors,
            total=self.n_total,
        )
        return survivors > 0

    def maybe_reprobe(self) -> None:
        """Restore the full mesh once the re-probe window has passed
        since the last shard fault. Shards whose injected fault is
        still armed stay excluded (the peek costs no budget); a REAL
        recovered chip simply starts serving again — if it is still
        sick the next launch's fault re-excludes it, which is the
        probe."""
        from tendermint_tpu.telemetry import metrics as _metrics
        from tendermint_tpu.utils.fail import shard_fault_armed

        with self._lock:
            if not self._excluded:
                return
            if time.monotonic() - self._last_fault < self._reprobe_s:
                return
            recovered = {
                i for i in self._excluded if not shard_fault_armed(i)
            }
            if not recovered:
                self._last_fault = time.monotonic()  # re-arm the window
                return
            self._excluded -= recovered
            _metrics.MESH_REMESH.labels(direction="restore").inc()
            self._bind_gauge()
        from tendermint_tpu.telemetry.flightrec import FLIGHT

        FLIGHT.record(
            "mesh", event="restore", recovered=sorted(recovered), active=self.n_active
        )

    def reset(self) -> None:
        """Forget all exclusions (tests)."""
        with self._lock:
            self._excluded.clear()
            self._bind_gauge()

    # -- compiled steps ----------------------------------------------------

    def _cached_step(self, program: str, build):
        global _COMPILES_IN_PROGRESS

        from tendermint_tpu.telemetry import launchlog as _launchlog
        from tendermint_tpu.telemetry import metrics as _metrics

        key = (self.executor, tuple(self._all[i] for i in self.active_indices()), program)
        compile_s = None
        with _STEP_LOCK:
            step = _STEP_CACHE.get(key)
            if step is None:
                _COMPILES_IN_PROGRESS += 1
                t0 = time.perf_counter()
                try:
                    step = build()
                finally:
                    _COMPILES_IN_PROGRESS -= 1
                compile_s = time.perf_counter() - t0
                _STEP_CACHE[key] = step
        # compile-cache telemetry outside the lock: the miss stalls the
        # launch for the whole build, and its record carries the cost
        if compile_s is None:
            _metrics.MESH_COMPILE.labels(result="hit").inc()
            _launchlog.annotate(compile="hit")
        else:
            _metrics.MESH_COMPILE.labels(result="miss").inc()
            _metrics.MESH_COMPILE_SECONDS.observe(compile_s)
            _launchlog.annotate(compile="miss")
            _launchlog.annotate(_additive=True, compile_s=compile_s)
        return step

    def verify_step(self):
        """(pub, r, s, h, powers) -> (verdicts, psum power tally) over
        the ACTIVE mesh. Row counts must already be padded to a
        multiple of `n_active` (`ops.padding.pad_rows_to`)."""
        if self.executor == "host":
            def _host_step(pub, r, s, h, power):
                ok = _host_verify_prepared_rows(pub, r, s, h)
                return ok, int(np.where(ok, power, 0).sum())

            return _host_step
        return self._cached_step(
            "verify_tally", lambda: sharded_verify_and_tally(self.mesh())
        )

    def tables_step(self):
        """Sharded TABLE fast path over the active mesh (validator-axis
        sharding; see `sharded_tables_verify_and_tally`)."""
        if self.executor == "host":
            raise NotImplementedError(
                "host executor has no table path — use the generic verify_step"
            )
        return self._cached_step(
            "tables_tally", lambda: sharded_tables_verify_and_tally(self.mesh())
        )

    def leaf_hash_step(self, algo: str, max_blocks: int):
        """Batch-sharded leaf hashing over the active mesh: (blocks
        (B, max_blocks, 16) u32, n_blocks (B,) i32) -> (B, W) u32
        digests, B a multiple of `n_active`."""
        if self.executor == "host":
            return None  # hasher mesh lane hashes host-side per shard
        return self._cached_step(
            f"leafhash_{algo}_{max_blocks}",
            lambda: sharded_leaf_hash_kernel(self.mesh(), algo, max_blocks),
        )


_DEFAULT_MANAGER: MeshManager | None = None
_DEFAULT_MANAGER_LOCK = threading.Lock()


def default_mesh_manager() -> MeshManager:
    """The process-wide mesh shared by the default verifier and hasher
    stacks — one health view per process: a chip that faults out of the
    verify lane is out of the hash lane too."""
    global _DEFAULT_MANAGER
    if _DEFAULT_MANAGER is None:
        with _DEFAULT_MANAGER_LOCK:
            if _DEFAULT_MANAGER is None:
                _DEFAULT_MANAGER = MeshManager()
    return _DEFAULT_MANAGER


def set_default_mesh_manager(manager: MeshManager | None) -> None:
    global _DEFAULT_MANAGER
    _DEFAULT_MANAGER = manager


def sharded_leaf_hash_kernel(mesh: Mesh, algo: str, max_blocks: int):
    """Compile the Merkle LEAF lane over the mesh: every chip hashes
    1/ndev of the padded leaf messages (one batched masked-SHA-256 /
    RIPEMD-160 pass, `ops.sha256_kernel._sha256_masked` semantics).
    Tree *reduction* stays single-device — inner levels halve too fast
    to amortize collectives; the leaf pass is the O(N) term."""
    spec = P(BATCH_AXIS)

    if algo == "ripemd160":
        from tendermint_tpu.ops.ripemd160_kernel import _ripemd160_masked as _masked
    else:
        from tendermint_tpu.ops.sha256_kernel import _sha256_masked as _masked

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=spec,
    )
    def _leaves(blocks, n_blocks):
        return _masked(blocks, n_blocks, max_blocks)

    return _leaves


def pad_to_multiple(arrays, powers, multiple: int):
    """Pad (B,32) byte arrays + (B,) powers up to a multiple of `multiple`.

    Padding rows are zeros. A zero row does decode (y=0 is a valid
    order-4 point) but still verifies False because S=h=0 makes the
    ladder produce the identity, which never equals the decoded R point
    (0, 1) != (±sqrt(-1), 0); powers are zero too, so the psum tally is
    unaffected either way. Don't replace zero padding with copied rows —
    those WOULD verify True and corrupt the tally if given power.
    """
    b = arrays[0].shape[0]
    size = ((b + multiple - 1) // multiple) * multiple
    if size == b:
        return arrays, powers, b
    pad = size - b
    arrays = [np.concatenate([a, np.zeros((pad, 32), dtype=np.uint8)]) for a in arrays]
    powers = np.concatenate([powers, np.zeros(pad, dtype=powers.dtype)])
    return arrays, powers, b
