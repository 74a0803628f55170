"""Multi-chip sharded verify mesh: the production mesh path on the
8-virtual-device CPU mesh conftest forces (ISSUE 6 tentpole).

Choreography — pad/slice geometry, shard faults, survivor re-mesh,
breaker interplay, coalescer drain order — runs tier-1 through the
`executor="host"` mesh stand-in (verdict-identical host evaluation of
the device equation, zero XLA compiles; the TestFusedPathShaping idiom).
One tier-1 test compiles the REAL sharded ladder once to pin verdict
parity through the default stack; heavier real-kernel variants are
double-marked kernel+slow per the conftest lint.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from tendermint_tpu.crypto.keys import gen_priv_key
from tendermint_tpu.parallel.mesh import (
    MeshExhaustedError,
    MeshManager,
    mesh_device_count,
    set_default_mesh_manager,
)
from tendermint_tpu.services.batcher import CoalescingVerifier
from tendermint_tpu.services.resilient import ResilientVerifier
from tendermint_tpu.services.verifier import (
    HostBatchVerifier,
    ShardedBatchVerifier,
    ShardedTableBatchVerifier,
    set_default_verifier,
)
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.utils import fail


@pytest.fixture(autouse=True)
def _clean_mesh_state():
    fail.clear_device_faults()
    set_default_mesh_manager(None)
    yield
    fail.clear_device_faults()
    set_default_mesh_manager(None)
    set_default_verifier(None)


def _triples(n, corrupt=(), salt=b""):
    privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
    out = []
    for i, p in enumerate(privs):
        m = b"mesh-msg-%s-%d" % (salt, i)
        sig = p.sign(m)
        if i in corrupt:
            sig = sig[:8] + bytes([sig[8] ^ 1]) + sig[9:]
        out.append((p.pub_key.data, m, sig))
    return out


def _counter(name, **labels) -> float:
    return REGISTRY.counter_value(name, **labels)


def _host_mesh_verifier(min_batch=1, reprobe_s=60.0, devices=None):
    mgr = MeshManager(executor="host", reprobe_s=reprobe_s, devices=devices)
    return ShardedBatchVerifier(mesh=mgr, min_device_batch=min_batch), mgr


class TestMeshManager:
    def test_discovers_all_eight_virtual_devices(self):
        assert mesh_device_count() == 8
        mgr = MeshManager(executor="host")
        assert mgr.n_total == 8
        assert mgr.active_indices() == tuple(range(8))
        assert not mgr.degraded

    def test_mesh_devices_knob(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "3")
        assert mesh_device_count() == 3
        assert MeshManager(executor="host").n_total == 3
        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "1")
        assert mesh_device_count() == 1  # force single-device legacy
        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "0")
        assert mesh_device_count() == 8  # 0/unset = all
        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "64")
        assert mesh_device_count() == 8  # capped at visible devices

    def test_shard_fault_excludes_then_reprobe_restores(self):
        mgr = MeshManager(executor="host", reprobe_s=0.05)
        shrink0 = _counter("tendermint_mesh_remesh_total", direction="shrink")
        restore0 = _counter("tendermint_mesh_remesh_total", direction="restore")
        assert mgr.record_shard_fault(5)  # survivors remain
        assert mgr.n_active == 7
        assert 5 not in mgr.active_indices()
        assert mgr.degraded
        assert (
            _counter("tendermint_mesh_remesh_total", direction="shrink")
            == shrink0 + 1
        )
        # inside the window: still degraded
        mgr.maybe_reprobe()
        assert mgr.n_active == 7
        time.sleep(0.06)
        mgr.maybe_reprobe()
        assert mgr.n_active == 8 and not mgr.degraded
        assert (
            _counter("tendermint_mesh_remesh_total", direction="restore")
            == restore0 + 1
        )

    def test_reprobe_keeps_excluding_while_fault_armed(self):
        mgr = MeshManager(executor="host", reprobe_s=0.05)
        fail.set_device_fault("shard2")
        assert mgr.record_shard_fault(2)
        time.sleep(0.06)
        mgr.maybe_reprobe()  # peeks the armed fault, stays degraded
        assert mgr.n_active == 7
        fail.clear_device_faults()
        time.sleep(0.06)
        mgr.maybe_reprobe()
        assert mgr.n_active == 8

    def test_exhaustion_reports_no_survivors(self):
        mgr = MeshManager(executor="host")
        for i in range(7):
            assert mgr.record_shard_fault(i)
        assert not mgr.record_shard_fault(7)
        assert mgr.n_active == 0
        snap = mgr.snapshot()
        assert snap["devices_active"] == 0
        assert snap["excluded"] == list(range(8))

    def test_devices_gauge_tracks_active(self):
        mgr = MeshManager(executor="host")
        fam = REGISTRY.get("tendermint_mesh_devices")
        assert fam.value == 8
        mgr.record_shard_fault(1)
        assert fam.value == 7
        mgr.reset()
        assert fam.value == 8


class TestShardedVerifierHostExecutor:
    @pytest.mark.parametrize("n", [1, 7, 8, 9, 16, 17])
    def test_pad_slice_round_trip_non_divisible(self, n):
        """Every batch size — divisible by the mesh or not — must come
        back bit-identical to the host library, at the true length."""
        v, _mgr = _host_mesh_verifier()
        triples = _triples(n, corrupt={n - 1} if n > 2 else ())
        want = HostBatchVerifier().verify_batch(triples)
        got = v.verify_batch(triples)
        assert got.shape == (n,)
        assert (got == want).all()

    def test_per_shard_bucket_geometry(self, monkeypatch):
        """Launch rows = per-chip power-of-two bucket x active chips —
        the chunk/stack fix: geometry derives from the per-chip shard
        size, and re-derives after a survivor re-mesh."""
        v, mgr = _host_mesh_verifier()
        shapes = []
        real_factory = mgr.verify_step

        def spying_step():
            real = real_factory()

            def _step(pub, r, s, h, pw):
                shapes.append(pub.shape[0])
                return real(pub, r, s, h, pw)

            return _step

        monkeypatch.setattr(mgr, "verify_step", spying_step)
        v.verify_batch(_triples(10))
        assert shapes[-1] == 8 * 8  # ceil(10/8)=2 -> bucket 8 -> x8 chips
        for i in (0, 1, 2):
            mgr.record_shard_fault(i)
        v.verify_batch(_triples(10))
        assert shapes[-1] == 8 * 5  # 5 survivors, per-chip bucket 8
        v.verify_batch(_triples(200))
        assert shapes[-1] == 64 * 5  # ceil(200/5)=40 -> bucket 64

    def test_zero_padding_rows_never_verify(self):
        """The pad-row safety property on the host-emulated step: an
        all-zero row reports False and zero power, so padding can never
        inflate a tally (mirrors `pad_to_multiple`'s kernel analysis)."""
        from tendermint_tpu.parallel.mesh import _host_verify_prepared_rows

        zeros = np.zeros((16, 32), dtype=np.uint8)
        ok = _host_verify_prepared_rows(zeros, zeros, zeros, zeros)
        assert not ok.any()

    def test_commit_tally_with_powers(self):
        v, _mgr = _host_mesh_verifier()
        triples = _triples(10, corrupt={3, 7})
        powers = np.arange(1, 11, dtype=np.int32)
        mask, tally = v.verify_batch_with_powers(triples, powers)
        want = HostBatchVerifier().verify_batch(triples)
        assert (mask == want).all()
        assert tally == int(powers[want].sum())

    def test_commit_grid_flat_lanes(self):
        v, _mgr = _host_mesh_verifier()
        triples = _triples(10, corrupt={2})
        pubs = [t[0] for t in triples]
        msgs = [t[1] for t in triples]
        sigs = [t[2] for t in triples]
        absent_msgs = list(msgs)
        absent_sigs = list(sigs)
        absent_msgs[5] = None
        absent_sigs[5] = None
        grid = v.verify_commits(pubs, [(msgs, sigs), (absent_msgs, absent_sigs)])
        assert grid.shape == (2, 10)
        want = HostBatchVerifier().verify_batch(triples)
        assert (grid[0] == want).all()
        want_absent = want.copy()
        want_absent[5] = False
        assert (grid[1] == want_absent).all()

    def test_small_batch_short_circuits_to_host(self, monkeypatch):
        v, mgr = _host_mesh_verifier(min_batch=512)

        def boom():  # the mesh must not be consulted below the threshold
            raise AssertionError("sub-threshold batch reached the mesh")

        monkeypatch.setattr(mgr, "verify_step", boom)
        triples = _triples(4)
        assert v.verify_batch(triples).all()

    def test_shard_fault_survivor_remesh_keeps_serving(self):
        """A single shard fault degrades through re-mesh, NOT through
        the breaker: verdicts stay correct, the resilient wrapper never
        sees a failure, telemetry shows the shrink."""
        v, mgr = _host_mesh_verifier()
        rv = ResilientVerifier(v)
        faults0 = _counter("tendermint_mesh_shard_faults_total")
        fallback0 = _counter(
            "tendermint_device_fallback_calls_total", kind="verify"
        )
        fail.set_device_fault("shard4")
        triples = _triples(12, corrupt={0})
        want = HostBatchVerifier().verify_batch(triples)
        got = rv.verify_batch(triples)
        assert (got == want).all()
        assert mgr.n_active == 7 and 4 not in mgr.active_indices()
        assert _counter("tendermint_mesh_shard_faults_total") == faults0 + 1
        # the breaker path was NEVER taken — re-mesh absorbed the fault
        assert (
            _counter("tendermint_device_fallback_calls_total", kind="verify")
            == fallback0
        )
        assert rv.breaker.state == "closed"

    def test_exhaustion_degrades_through_breaker_then_recovers(self):
        """All shards faulted -> MeshExhaustedError -> CircuitBreaker
        host fallback (the PR 1 ladder); clearing the faults and passing
        the re-probe window restores the FULL mesh."""
        v, mgr = _host_mesh_verifier(reprobe_s=0.05)
        rv = ResilientVerifier(v, max_retries=0)
        for i in range(8):
            fail.set_device_fault(f"shard{i}")
        fallback0 = _counter(
            "tendermint_device_fallback_calls_total", kind="verify"
        )
        triples = _triples(10, corrupt={1})
        want = HostBatchVerifier().verify_batch(triples)
        got = rv.verify_batch(triples)  # breaker fallback answers
        assert (got == want).all()
        assert mgr.n_active == 0
        assert (
            _counter("tendermint_device_fallback_calls_total", kind="verify")
            == fallback0 + 1
        )
        fail.clear_device_faults()
        time.sleep(0.06)
        restore0 = _counter("tendermint_mesh_remesh_total", direction="restore")
        got2 = rv.verify_batch(triples)
        assert (got2 == want).all()
        assert mgr.n_active == 8
        assert (
            _counter("tendermint_mesh_remesh_total", direction="restore")
            == restore0 + 1
        )

    def test_mesh_exhausted_raises_without_breaker(self):
        v, _mgr = _host_mesh_verifier()
        for i in range(8):
            fail.set_device_fault(f"shard{i}")
        with pytest.raises(MeshExhaustedError):
            v.verify_batch(_triples(9))


class TestTablesMeshGeometry:
    """Mesh-aware TableBatchVerifier SHAPING on the CPU mesh: the
    validator-axis table path's lane reordering, per-shard K padding,
    and fallbacks — kernel calls faked (the TestFusedPathShaping idiom),
    kernel correctness pinned by the kernel-marked suites and
    test_services' sharded tables test."""

    def _verifier(self, n, monkeypatch, executor="device"):
        import jax.numpy as jnp

        from tendermint_tpu.ops.ed25519_tables import host_build_key_tables

        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
        pubs = tuple(p.pub_key.data for p in privs)
        mgr = MeshManager(executor=executor, reprobe_s=60.0)
        v = ShardedTableBatchVerifier(mesh=mgr, min_device_batch=1)
        tables, ok = host_build_key_tables(list(pubs))
        v._tables[v._cache_key(pubs)] = (pubs, jnp.asarray(tables), ok)
        calls = []

        def fake_tables_step():
            def _step(tables, s, h, r, lane_ok, power):
                calls.append(
                    {"lanes": s.shape[0], "lane_ok": np.asarray(lane_ok).copy()}
                )
                return np.asarray(lane_ok).copy(), int(
                    np.where(np.asarray(lane_ok), power, 0).sum()
                )

            return _step

        monkeypatch.setattr(mgr, "tables_step", fake_tables_step)
        # the sharded-tables placement needs a real Mesh even with the
        # fake step skipped on CPU — avoid it entirely
        monkeypatch.setattr(
            v, "_tables_for_mesh", lambda pk, m: v._tables_for(pk)
        )
        return privs, pubs, v, mgr, calls

    def _commits(self, privs, k, absent=()):
        commits = []
        for c in range(k):
            msgs = [b"c%d-%d" % (c, i) for i in range(len(privs))]
            sigs = [p.sign(m) for p, m in zip(privs, msgs)]
            for (ci, i) in absent:
                if ci == c:
                    msgs[i] = None
                    sigs[i] = None
            commits.append((msgs, sigs))
        return commits

    def test_shard_major_order_and_absent_lanes(self, monkeypatch):
        """The grid a fake echo-lane_ok step produces must equal the
        presence mask — proving the shard-major reorder and its inverse
        round-trip lane identity exactly."""
        privs, pubs, v, mgr, calls = self._verifier(16, monkeypatch)
        commits = self._commits(privs, 3, absent=[(1, 5), (2, 0)])
        grid = v.verify_commits(list(pubs), commits)
        assert grid.shape == (3, 16)
        want = np.ones((3, 16), dtype=bool)
        want[1, 5] = False
        want[2, 0] = False
        assert (grid == want).all()
        assert calls[-1]["lanes"] == 3 * 16

    def test_k_padding_from_per_shard_geometry(self, monkeypatch):
        """force_fused pads the K stack to 16 with absent commits and
        each chip's 2 validators to the 128 tile with placeholder
        columns (both sliced off at finalize) — the one shape rule
        asked about per-chip lane counts, the single-device assumption
        removed."""
        privs, pubs, v, mgr, calls = self._verifier(16, monkeypatch)
        monkeypatch.setattr(
            v,
            "_tables_for_mesh",
            lambda pk, m: (None, np.ones(len(pk), dtype=bool)),
        )
        commits = self._commits(privs, 3)
        grid = v.verify_commits(list(pubs), commits, force_fused=True)
        assert grid.shape == (3, 16)
        assert grid.all()
        # K 3 -> 16, N 16 -> 8 chips x 128
        assert calls[-1]["lanes"] == 16 * 8 * 128
        assert int(calls[-1]["lane_ok"].sum()) == 3 * 16  # pads carry no lane

    def test_uneven_valset_falls_back_to_single_device(self, monkeypatch):
        """N=10 does not split over 8 chips: the call degrades to the
        legacy single-device table path, not an error."""
        privs, pubs, v, mgr, calls = self._verifier(10, monkeypatch)
        sentinel = []

        import tendermint_tpu.services.verifier as svc

        orig = svc.TableBatchVerifier.launch_verify_commits

        def spy(self, pubkeys, commits, force_fused=None):
            sentinel.append(len(pubkeys))
            return ("host", self._host_commit_loop(pubkeys, commits))

        monkeypatch.setattr(svc.TableBatchVerifier, "launch_verify_commits", spy)
        grid = v.verify_commits(list(pubs), self._commits(privs, 2))
        assert sentinel == [10]
        assert grid.shape == (2, 10) and grid.all()
        assert not calls  # mesh tables step never consulted
        assert orig is not None

    def test_shard_fault_mid_commit_grid_remeshes(self, monkeypatch):
        """A shard fault during a commit-grid launch re-meshes; with 16
        validators over 7 survivors the split is uneven, so the SAME
        call lands on the single-device path — degraded but serving."""
        privs, pubs, v, mgr, calls = self._verifier(16, monkeypatch)
        fail.set_device_fault("shard3")
        grid = v.verify_commits(list(pubs), self._commits(privs, 2))
        assert grid.shape == (2, 16) and grid.all()
        assert mgr.n_active == 7

    def test_host_executor_routes_flat_lanes(self, monkeypatch):
        privs, pubs, v, mgr, calls = self._verifier(
            16, monkeypatch, executor="host"
        )
        commits = self._commits(privs, 2, absent=[(0, 1)])
        grid = v.verify_commits(list(pubs), commits)
        want = np.ones((2, 16), dtype=bool)
        want[0, 1] = False
        assert (grid == want).all()
        assert not calls  # host executor has no tables program


class TestCoalescerMeshIntegration:
    def test_max_batch_scales_with_mesh_width(self):
        from tendermint_tpu.services.batcher import MAX_COALESCED_BATCH

        v, _mgr = _host_mesh_verifier()
        cv = CoalescingVerifier(ResilientVerifier(v))
        try:
            assert cv.coalescer._max_batch == MAX_COALESCED_BATCH * 8
        finally:
            cv.close()
        single = CoalescingVerifier(HostBatchVerifier())
        try:
            assert single.coalescer._max_batch == MAX_COALESCED_BATCH
        finally:
            single.close()

    def test_explicit_max_batch_stays_per_call(self):
        v, _mgr = _host_mesh_verifier()
        cv = CoalescingVerifier(ResilientVerifier(v), max_batch=64)
        try:
            assert cv.coalescer._max_batch == 64
        finally:
            cv.close()

    def test_drain_order_through_mid_coalesce_shard_fault(self):
        """Two consumers stream FIFO batches through one coalescer; a
        shard fault lands mid-stream. The re-mesh happens INSIDE the
        merged launch — every sub-handle still resolves, in per-consumer
        submission order, with correct verdicts (PR 4/5 discipline)."""
        v, mgr = _host_mesh_verifier()
        cv = CoalescingVerifier(
            ResilientVerifier(v), cache_size=0, window_s=0.002
        )
        try:
            batches = {
                tag: [
                    _triples(6, corrupt={r}, salt=b"%s%d" % (tag.encode(), r))
                    for r in range(3)
                ]
                for tag in ("consensus", "fastsync")
            }
            handles = {tag: [] for tag in batches}
            for r in range(3):
                for tag in batches:
                    handles[tag].append(
                        cv.verify_batch_async(batches[tag][r], consumer=tag)
                    )
                if r == 0:
                    fail.set_device_fault("shard6")
            for tag in batches:
                for r, h in enumerate(handles[tag]):
                    got = h.result(timeout=30)
                    want = np.ones(6, dtype=bool)
                    want[r] = False
                    assert (got == want).all(), (tag, r)
            assert mgr.n_active == 7
        finally:
            cv.close()


class TestDefaultStackComposition:
    def test_cpu_opt_in_builds_mesh_stack(self, monkeypatch):
        import tendermint_tpu.services.verifier as svc

        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "8")
        set_default_verifier(None)
        v = svc.default_verifier()
        try:
            assert isinstance(v, CoalescingVerifier)
            assert isinstance(v.inner, ResilientVerifier)
            assert isinstance(v.inner.primary, ShardedBatchVerifier)
            assert v.inner.primary.mesh.n_total == 8
            assert v.inner.mesh is v.inner.primary.mesh  # passthrough
        finally:
            v.close()
            set_default_verifier(None)

    def test_cpu_without_knob_stays_host(self, monkeypatch):
        import tendermint_tpu.services.verifier as svc

        monkeypatch.delenv("TENDERMINT_TPU_MESH_DEVICES", raising=False)
        set_default_verifier(None)
        v = svc.default_verifier()
        try:
            inner = getattr(v, "inner", v)
            assert not isinstance(inner, ResilientVerifier) or not isinstance(
                getattr(inner, "primary", None), ShardedBatchVerifier
            )
        finally:
            if hasattr(v, "close"):
                v.close()
            set_default_verifier(None)

    def test_force_single_device_knob(self, monkeypatch):
        import tendermint_tpu.services.verifier as svc

        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "1")
        set_default_verifier(None)
        v = svc.default_verifier()
        try:
            inner = getattr(v, "inner", v)
            assert not isinstance(
                getattr(inner, "primary", None), ShardedBatchVerifier
            )
        finally:
            if hasattr(v, "close"):
                v.close()
            set_default_verifier(None)

    def test_auto_hasher_cpu_opt_in_gets_mesh(self, monkeypatch):
        from tendermint_tpu.services.hasher import auto_hasher
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "8")
        h = auto_hasher()
        assert isinstance(h, ResilientTreeHasher)
        assert h.mesh is not None and h.mesh.n_total == 8
        assert h.primary.mesh is h.mesh

    def test_auto_hasher_without_knob_stays_host(self, monkeypatch):
        from tendermint_tpu.services.hasher import TreeHasher, auto_hasher

        monkeypatch.delenv("TENDERMINT_TPU_MESH_DEVICES", raising=False)
        h = auto_hasher()
        assert type(h) is TreeHasher and h.backend == "host"


class TestMeshHasherLane:
    def test_host_executor_leaf_hashes_match_and_remesh(self):
        from tendermint_tpu.merkle.simple import leaf_hash
        from tendermint_tpu.services.hasher import TreeHasher

        mgr = MeshManager(executor="host", reprobe_s=60.0)
        th = TreeHasher(backend="device", min_device_leaves=2, mesh=mgr)
        items = [b"leaf-%d" % i for i in range(37)]
        fail.set_device_fault("shard1")
        out = th.leaf_hashes(items)
        assert out == [leaf_hash(x) for x in items]
        assert mgr.n_active == 7  # the hash lane re-meshed too

    def test_hash_lane_exhaustion_hits_hash_breaker(self):
        from tendermint_tpu.merkle.simple import leaf_hash
        from tendermint_tpu.services.hasher import TreeHasher
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        mgr = MeshManager(executor="host")
        th = ResilientTreeHasher(
            TreeHasher(backend="device", min_device_leaves=2, mesh=mgr),
            TreeHasher(backend="host"),
            max_retries=0,
        )
        for i in range(8):
            fail.set_device_fault(f"shard{i}")
        fallback0 = _counter(
            "tendermint_device_fallback_calls_total", kind="hash"
        )
        items = [b"x%d" % i for i in range(9)]
        assert th.leaf_hashes(items) == [leaf_hash(x) for x in items]
        assert (
            _counter("tendermint_device_fallback_calls_total", kind="hash")
            == fallback0 + 1
        )


class TestMeshNemesis:
    def test_live_net_loses_shard_mid_height_keeps_committing(self, tmp_path):
        """The chaos acceptance: a running 4-validator net whose verify
        spine is the full production mesh stack (coalescer -> resilient
        -> sharded mesh, host-emulated executor) loses one shard
        mid-height. The mesh re-meshes onto 7 survivors and the chain
        keeps committing — no fork, NO breaker trip (re-mesh absorbs the
        fault below the breaker); clearing the fault restores the full
        mesh. The whole cycle is asserted through exported telemetry."""
        from tendermint_tpu.testing import Nemesis

        stacks = []

        def factory(_i):
            mgr = MeshManager(executor="host", reprobe_s=0.5)
            cv = CoalescingVerifier(
                ResilientVerifier(
                    ShardedBatchVerifier(mesh=mgr, min_device_batch=1),
                    max_retries=0,
                ),
                cache_size=4096,
            )
            stacks.append((cv, mgr))
            return cv

        try:
            with Nemesis(
                4, home=str(tmp_path), verifier_factory=factory
            ) as net:
                net.wait_height(2, timeout=60)
                base = net.mesh_baseline()
                trips0 = _counter(
                    "tendermint_breaker_transitions_total",
                    kind="verify",
                    to="open",
                )

                fail.set_device_fault("shard2")  # one chip dies mid-height
                net.wait_progress(delta=2, timeout=60)  # commits continue
                net.assert_mesh_degraded(base)
                # every node's mesh degraded to 7 survivors ...
                degraded = [m.n_active for _cv, m in stacks]
                assert all(a == 7 for a in degraded), degraded
                # ... WITHOUT tripping any verify breaker (re-mesh is a
                # layer below the PR 1 degradation ladder)
                assert (
                    _counter(
                        "tendermint_breaker_transitions_total",
                        kind="verify",
                        to="open",
                    )
                    == trips0
                )
                net.check_invariants()  # no fork while degraded

                fail.clear_device_faults()  # the chip comes back
                net.assert_mesh_restored(base)
                net.wait_progress(delta=2, timeout=60)
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    if all(m.n_active == 8 for _cv, m in stacks):
                        break
                    time.sleep(0.1)
                actives = [m.n_active for _cv, m in stacks]
                assert all(a == 8 for a in actives), actives
                net.check_invariants()
        finally:
            for cv, _m in stacks:
                cv.close()


class TestDefaultStackRealKernelParity:
    """The acceptance criterion, tier-1: with the 8-virtual-device CPU
    mesh, the DEFAULT verifier stack (coalescer -> resilient -> sharded
    device) verifies batches and tallies commit power sharded over all
    8 devices, bit-identical to the single-device kernel and the host
    library. ONE ladder compile (~70 s XLA:CPU): every call here reuses
    the same 64-row global shape, so the jit cache serves all of them.
    """

    def test_default_stack_sharded_verify_parity_and_tally(self, monkeypatch):
        import tendermint_tpu.services.verifier as svc

        monkeypatch.setenv("TENDERMINT_TPU_MESH_DEVICES", "8")
        monkeypatch.setattr(svc, "DEVICE_MIN_BATCH", 1)
        set_default_verifier(None)
        v = svc.default_verifier()
        try:
            assert isinstance(v, CoalescingVerifier)
            sharded = v.inner.primary
            assert isinstance(sharded, ShardedBatchVerifier)
            mgr = sharded.mesh
            assert mgr.n_total == 8 and mgr.executor == "device"

            triples = _triples(10, corrupt={3, 7})
            want_host = HostBatchVerifier().verify_batch(triples)
            want_dev = svc.DeviceBatchVerifier(min_device_batch=1).verify_batch(
                triples
            )
            assert (want_host == want_dev).all()  # single-device oracle

            got = v.verify_batch(triples)  # compiles the sharded step
            assert (got == want_host).all()

            # the coalesced async lane rides the SAME mesh executable
            fresh = _triples(10, corrupt={1}, salt=b"async")
            want2 = HostBatchVerifier().verify_batch(fresh)
            h = v.verify_batch_async(fresh, consumer="consensus")
            assert (h.result(timeout=120) == want2).all()

            # commit tally: psum-reduced on device across all 8 shards,
            # equal to the host-side power sum over valid lanes
            powers = np.arange(1, 11, dtype=np.int32)
            mask, tally = sharded.verify_batch_with_powers(triples, powers)
            assert (mask == want_host).all()
            assert tally == int(powers[want_host].sum())

            # zero pad rows verify False on the REAL kernel (the
            # property the padding rule depends on) — same 64-row shape
            zeros = np.zeros((64, 32), dtype=np.uint8)
            zero_pw = np.zeros(64, dtype=np.int32)
            ok, total = mgr.verify_step()(zeros, zeros, zeros, zeros, zero_pw)
            assert not np.asarray(ok).any()
            assert int(total) == 0

            # commit grids flatten onto the same sharded lane
            pubs = [t[0] for t in triples]
            msgs = [t[1] for t in triples]
            sigs = [t[2] for t in triples]
            grid = sharded.verify_commits(pubs, [(msgs, sigs), (msgs, sigs)])
            assert (grid == np.stack([want_host, want_host])).all()
        finally:
            v.close()
            set_default_verifier(None)


@pytest.mark.kernel
@pytest.mark.slow
class TestMeshRealKernelMatrix:
    """Real shard_map ladder compiles beyond the single tier-1 parity
    test: survivor re-mesh on the live kernel and the sharded tables
    program through the production class."""

    def test_real_kernel_survivor_remesh(self):
        mgr = MeshManager(reprobe_s=60.0)
        v = ShardedBatchVerifier(mesh=mgr, min_device_batch=1)
        triples = _triples(10, corrupt={4})
        want = HostBatchVerifier().verify_batch(triples)
        assert (v.verify_batch(triples) == want).all()
        fail.set_device_fault("shard0")
        got = v.verify_batch(triples)  # recompiles over 7 survivors
        assert (got == want).all()
        assert mgr.n_active == 7

    def test_real_sharded_tables_through_production_class(self):
        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(16)]
        pubs = [p.pub_key.data for p in privs]
        import jax.numpy as jnp

        from tendermint_tpu.ops.ed25519_tables import host_build_key_tables

        mgr = MeshManager(reprobe_s=60.0)
        v = ShardedTableBatchVerifier(mesh=mgr, min_device_batch=1)
        tables, ok = host_build_key_tables(pubs)
        v._tables[v._cache_key(tuple(pubs))] = (
            tuple(pubs),
            jnp.asarray(tables),
            ok,
        )
        msgs = [b"t-%d" % i for i in range(16)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        sigs[5] = sigs[5][:10] + bytes([sigs[5][10] ^ 1]) + sigs[5][11:]
        grid = v.verify_commits(pubs, [(msgs, sigs), (msgs, sigs)])
        want = np.ones((2, 16), dtype=bool)
        want[:, 5] = False
        assert (grid == want).all()
