"""Device observatory (PR 13): LaunchLedger ring/persistence, the
ambient one-record-per-launch assembly through the dispatch and
coalescer seams, occupancy/padding accounting on the REAL mesh bucket
geometry, compile-cache and sharded-table placement-cache telemetry,
the `/health` device section, the `launches` dump view, and the
acceptance on a driven load: every launch through the
coalescing+resilient stack yields exactly ONE ledger record, and
`tools/device_report.py` over `dump_telemetry?launches=N` names the top
waste source."""

import json
import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"),
)

from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.telemetry import launchlog
from tendermint_tpu.telemetry.launchlog import LAUNCHLOG, LaunchLedger


@pytest.fixture(autouse=True)
def _ledger_reset():
    """Every test leaves the process-global ledger empty and the
    thread-ambient assembly state clean (the ledger is process-wide,
    like FLIGHT)."""
    LAUNCHLOG.clear()
    launchlog._tls.rec = None
    launchlog._tls.tags = None
    yield
    LAUNCHLOG.clear()
    launchlog._tls.rec = None
    launchlog._tls.tags = None


def _counter(name, **labels) -> float:
    return REGISTRY.counter_value(name, **labels)


def _make_sigs(n: int, salt: bytes = b"ll"):
    from tendermint_tpu.crypto.keys import gen_priv_key

    privs = [gen_priv_key(bytes([40 + i % 8]) * 32) for i in range(min(8, n))]
    msgs = [b'{"s":"%s","i":%d}' % (salt, i) for i in range(n)]
    sigs = [privs[i % len(privs)].sign(m) for i, m in enumerate(msgs)]
    pubs = [privs[i % len(privs)].pub_key.data for i in range(n)]
    return list(zip(pubs, msgs, sigs))


class TestLedger:
    def test_ring_bounded_and_ordered(self):
        led = LaunchLedger(capacity=4)
        for i in range(10):
            led.record({"kind": "verify", "rows": i})
        assert len(led) == 4
        assert [r["rows"] for r in led.recent()] == [6, 7, 8, 9]
        assert led.last()["rows"] == 9
        assert [r["rows"] for r in led.recent(2)] == [8, 9]

    def test_kind_filter(self):
        led = LaunchLedger(capacity=8)
        led.record({"kind": "verify", "rows": 1})
        led.record({"kind": "leaf_hashes", "rows": 2})
        assert [r["rows"] for r in led.recent(kind="leaf_hashes")] == [2]

    def test_jsonl_persist_and_reload(self, tmp_path):
        path = str(tmp_path / "launches.jsonl")
        led = LaunchLedger(path=path, capacity=8, node_id="n1")
        for i in range(3):
            led.record({"kind": "verify", "rows": i, "t": float(i)})
        led.close()
        reloaded = LaunchLedger(path=path, capacity=8)
        assert [r["rows"] for r in reloaded.recent()] == [0, 1, 2]
        assert reloaded.recent()[0]["node"] == "n1"
        reloaded.close()

    def test_compaction_bounds_the_file(self, tmp_path):
        path = str(tmp_path / "launches.jsonl")
        led = LaunchLedger(path=path, capacity=4)
        for i in range(20):
            led.record({"kind": "verify", "rows": i})
        led.close()
        with open(path) as f:
            lines = [ln for ln in f.readlines() if ln.strip()]
        # compaction trims to `capacity` whenever the file doubles past
        # it, so it can never exceed 2*capacity lines
        assert len(lines) <= 8

    def test_dump_all(self, tmp_path):
        LAUNCHLOG.record({"kind": "verify", "rows": 7})
        path = launchlog.dump_all(str(tmp_path), reason="test")
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            payload = json.load(f)
        assert payload["reason"] == "test"
        assert payload["records"][-1]["rows"] == 7

    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_LAUNCHLOG", "0")
        assert launchlog.begin("verify") is None
        launchlog.annotate(rows_padded=5)
        launchlog.observe("verify", "mesh", 8, 0.01)
        assert len(LAUNCHLOG) == 0

    def test_seconds_since_success_tracks_errors(self):
        assert LAUNCHLOG.seconds_since_success() is None
        rec = launchlog.begin("verify")
        launchlog.commit(rec, error=RuntimeError("boom"))
        assert LAUNCHLOG.seconds_since_success() is None  # failed launch
        rec = launchlog.begin("verify")
        launchlog.commit(rec)
        age = LAUNCHLOG.seconds_since_success()
        assert age is not None and age < 5.0


class TestAmbientAssembly:
    def test_dispatch_handle_yields_one_record_with_stages(self):
        from tendermint_tpu.services.dispatch import DispatchQueue

        q = DispatchQueue(depth=2, name="launchlog-test")
        try:
            h = q.submit(
                lambda: launchlog.observe("verify", "mesh", 32, 0.001) or 41,
                lambda v: v + 1,
                kind="verify",
            )
            assert h.result(timeout=10) == 42
        finally:
            q.close()
        recs = LAUNCHLOG.recent()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["kind"] == "verify" and rec["backend"] == "mesh"
        assert rec["rows"] == 32 and rec["queue"] == "launchlog-test"
        for stage in ("queue_wait_s", "host_prep_s", "in_flight_s",
                      "finalize_s", "total_s"):
            assert stage in rec, stage
        assert "error" not in rec
        # assembly-internal keys never leak into records
        assert not any(k.startswith("_") for k in rec)

    def test_launch_error_recorded(self):
        from tendermint_tpu.services.dispatch import DispatchQueue

        q = DispatchQueue(depth=1, name="launchlog-err")
        try:
            h = q.submit(lambda: 1 / 0, kind="hash")
            with pytest.raises(ZeroDivisionError):
                h.result(timeout=10)
        finally:
            q.close()
        recs = LAUNCHLOG.recent()
        assert len(recs) == 1
        assert recs[0]["error"] == "ZeroDivisionError"
        assert recs[0]["kind"] == "hash"

    def test_host_micro_call_outside_launch_records_nothing(self):
        launchlog.observe("verify", "host", 1, 0.0001)
        assert len(LAUNCHLOG) == 0

    def test_sync_device_call_records_standalone(self):
        launchlog.observe("tables", "tables", 256, 0.05)
        recs = LAUNCHLOG.recent()
        assert len(recs) == 1
        assert recs[0]["kind"] == "tables" and recs[0]["rows"] == 256

    def test_implicit_record_from_annotation_commits_at_observe(self):
        # the synchronous-launch shape: padding annotated during lane
        # prep, the backend's observe closes the record
        launchlog.annotate(_additive=True, rows_padded=31)
        launchlog.add_transfer(4096)
        launchlog.observe("verify", "mesh", 33, 0.02)
        recs = LAUNCHLOG.recent()
        assert len(recs) == 1
        assert recs[0]["rows"] == 33 and recs[0]["rows_padded"] == 31
        assert recs[0]["transfer_bytes"] == 4096
        assert launchlog.current() is None

    def test_tags_cross_the_dispatch_thread(self):
        from tendermint_tpu.services.dispatch import DispatchQueue

        q = DispatchQueue(depth=1, name="launchlog-tags")
        try:
            with launchlog.tag(
                consumers={"consensus": 8, "mempool": 4}, rows_cached=3
            ):
                h = q.submit(
                    lambda: launchlog.observe("verify", "mesh", 12, 0.001),
                    kind="verify",
                )
            h.result(timeout=10)
        finally:
            q.close()
        rec = LAUNCHLOG.recent()[0]
        assert rec["consumers"] == {"consensus": 8, "mempool": 4}
        assert rec["rows_cached"] == 3
        # the tag context has exited: later submits carry nothing
        assert launchlog.current_tags() is None

    def test_trace_exemplar_rides_the_record(self):
        from tendermint_tpu.services.dispatch import DispatchQueue
        from tendermint_tpu.telemetry import tracectx as _tc

        ctx = _tc.TraceContext(os.urandom(8), os.urandom(8), "launch-test")
        q = DispatchQueue(depth=1, name="launchlog-trace")
        try:
            with _tc.use(ctx):
                h = q.submit(lambda: None, kind="verify")
            h.result(timeout=10)
        finally:
            q.close()
        assert LAUNCHLOG.recent()[0]["trace"] == ctx.trace

    def test_metrics_observed_at_commit(self):
        u0 = _counter("tendermint_launch_rows", kind="verify", state="useful")
        p0 = _counter("tendermint_launch_rows", kind="verify", state="padded")
        rec = launchlog.begin("verify")
        rec["queue_wait_s"] = 0.001
        launchlog.annotate(_additive=True, rows_padded=7)
        launchlog.observe("verify", "mesh", 9, 0.01)
        launchlog.commit(rec)
        assert (
            _counter("tendermint_launch_rows", kind="verify", state="useful") - u0
            == 9
        )
        assert (
            _counter("tendermint_launch_rows", kind="verify", state="padded") - p0
            == 7
        )


def _host_mesh_verifier(n_devices: int):
    import jax

    from tendermint_tpu.parallel.mesh import MeshManager
    from tendermint_tpu.services.verifier import ShardedBatchVerifier

    mgr = MeshManager(
        devices=list(jax.devices())[:n_devices], executor="host"
    )
    return ShardedBatchVerifier(mesh=mgr, min_device_batch=1), mgr


class TestOccupancyAccounting:
    """The waste math on the REAL mesh pad geometry (per-chip
    power-of-two bucket x active width, `_mesh_flat_launch`), via the
    host-executor mesh — no XLA compile, identical shapes."""

    def test_exact_fit_no_padding(self):
        v, mgr = _host_mesh_verifier(4)
        triples = _make_sigs(32, b"fit")  # 8/chip = the minimum bucket
        assert bool(v.verify_batch(triples).all())
        rec = LAUNCHLOG.recent(kind="verify")[-1]
        assert rec["rows"] == 32
        assert rec.get("rows_padded", 0) == 0
        assert rec["mesh_width"] == 4
        assert rec["backend"] == "mesh"

    def test_bucket_boundary_cross_pads(self):
        v, mgr = _host_mesh_verifier(4)
        # 33 rows / 4 chips -> 9/chip -> bucket 16 -> 64 shipped rows
        triples = _make_sigs(33, b"cross")
        assert bool(v.verify_batch(triples).all())
        rec = LAUNCHLOG.recent(kind="verify")[-1]
        assert rec["rows"] == 33
        assert rec["rows_padded"] == 64 - 33
        # transfer: 4 x (64,32) u8 lane arrays + (64,) i32 powers
        assert rec["transfer_bytes"] == 4 * 64 * 32 + 64 * 4
        summary = launchlog.summarize([rec])["verify"]
        assert summary["occupancy_pct"] == round(100.0 * 33 / 64, 1)
        assert summary["padding_waste_pct"] == round(100.0 * 31 / 64, 1)

    def test_non_divisible_row_count(self):
        v, mgr = _host_mesh_verifier(4)
        triples = _make_sigs(10, b"odd")  # ceil(10/4)=3 -> bucket 8 -> 32
        assert bool(v.verify_batch(triples).all())
        rec = LAUNCHLOG.recent(kind="verify")[-1]
        assert rec["rows"] == 10 and rec["rows_padded"] == 22

    @pytest.mark.parametrize("width", [4, 8])
    def test_on_off_boundary_mix_summary(self, width):
        """A batch mix that sits on, one past and back on a per-chip
        bucket edge: the rollup's occupancy is useful rows over shipped
        rows across the launches (a wrong bucket ladder or a pad rule
        that rounds the whole batch instead of the per-chip share moves
        it)."""
        v, mgr = _host_mesh_verifier(width)
        n_before = len(LAUNCHLOG)
        sizes = (8 * width, 8 * width + 1, 8 * width)
        for size in sizes:
            assert bool(v.verify_batch(_make_sigs(size, b"mix-%d" % size)).all())
        recs = LAUNCHLOG.recent(kind="verify")[n_before:]
        assert [r["rows"] for r in recs] == list(sizes)
        # 8/chip fits the minimum bucket; 9/chip ships the 16-row one
        shipped = 8 * width + 16 * width + 8 * width
        assert sum(r["rows"] + r.get("rows_padded", 0) for r in recs) == shipped
        summary = launchlog.summarize(recs)["verify"]
        assert summary["occupancy_pct"] == round(100.0 * sum(sizes) / shipped, 1)
        assert summary["padding_waste_pct"] == round(
            100.0 * (shipped - sum(sizes)) / shipped, 1
        )

    def test_rows_counters_advance(self):
        u0 = _counter("tendermint_launch_rows", kind="verify", state="useful")
        p0 = _counter("tendermint_launch_rows", kind="verify", state="padded")
        v, mgr = _host_mesh_verifier(4)
        assert bool(v.verify_batch(_make_sigs(10, b"ctr")).all())
        assert (
            _counter("tendermint_launch_rows", kind="verify", state="useful")
            - u0
            == 10
        )
        assert (
            _counter("tendermint_launch_rows", kind="verify", state="padded")
            - p0
            == 22
        )


class TestCacheFilteredLanes:
    def test_coalesced_flush_carries_cache_withholding_and_mix(self):
        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.services.verifier import HostBatchVerifier

        v = CoalescingVerifier(
            HostBatchVerifier(), cache_size=1024, window_s=0.5
        )
        try:
            known = _make_sigs(6, b"known")
            novel = _make_sigs(4, b"novel")
            # prime: prove the known triples (positives enter the cache)
            assert bool(v.verify_batch(known).all())
            n_before = len(LAUNCHLOG)
            # mixed offer: 6 cached lanes withheld, 4 novel dispatched;
            # the barrier join forces the flush
            h = v.verify_batch_async(known + novel, consumer="consensus")
            assert bool(h.result(timeout=10).all())
            recs = LAUNCHLOG.recent()[n_before:]
            assert len(recs) == 1, recs
            rec = recs[0]
            assert rec["rows"] == 4  # only the novel lanes launched
            assert rec["rows_cached"] == 6
            assert rec["consumers"] == {"consensus": 4}
            assert rec["requests"] == 1
        finally:
            v.close()

    def test_fully_cached_offer_launches_nothing(self):
        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.services.verifier import HostBatchVerifier

        v = CoalescingVerifier(
            HostBatchVerifier(), cache_size=1024, window_s=0.001
        )
        try:
            triples = _make_sigs(5, b"allcached")
            assert bool(v.verify_batch(triples).all())
            n_before = len(LAUNCHLOG)
            h = v.verify_batch_async(triples, consumer="rpc")
            assert bool(h.result(timeout=10).all())
            assert len(LAUNCHLOG) == n_before  # no launch, no record
        finally:
            v.close()

    def test_commit_grid_cached_lanes_reduce_requested_rows(self):
        """Cached commit-grid lanes are withheld from the inner backend
        and tagged onto its launch record (the sync tables shape)."""
        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.services.verifier import (
            BatchVerifier,
            HostBatchVerifier,
            _observe_verify,
        )

        class GridBackend(BatchVerifier):
            """Backend with a commit-grid surface that reports itself
            like the real table path (kind=tables)."""

            def __init__(self):
                super().__init__()
                self._host = HostBatchVerifier()

            def verify_batch(self, triples):
                return self._host.verify_batch(triples)

            def verify_commits(self, pubkeys, commits, force_fused=None):
                n = len(pubkeys)
                out = np.zeros((len(commits), n), dtype=bool)
                lanes = 0
                for ci, (msgs, sigs) in enumerate(commits):
                    for i in range(n):
                        if msgs[i] is not None and sigs[i] is not None:
                            lanes += 1
                            out[ci, i] = bool(
                                self._host.verify_batch(
                                    [(pubkeys[i], msgs[i], sigs[i])]
                                )[0]
                            )
                _observe_verify("tables", lanes, 0.001, kind="tables")
                return out

        v = CoalescingVerifier(GridBackend(), cache_size=1024, window_s=0.5)
        try:
            triples = _make_sigs(4, b"grid")
            pubkeys = [pk for pk, _m, _s in triples]
            msgs = [m for _pk, m, _s in triples]
            sigs = [s for _pk, _m, s in triples]
            commit = (list(msgs), list(sigs))
            grid1 = v.verify_commits(pubkeys, [commit])
            assert bool(grid1.all())
            first = LAUNCHLOG.recent(kind="tables")[-1]
            assert first["rows"] == 4 and first.get("rows_cached", 0) == 0
            # second pass: every lane proven -> withheld entirely
            n_before = len(LAUNCHLOG)
            grid2 = v.verify_commits(pubkeys, [commit])
            assert bool(grid2.all())
            assert len(LAUNCHLOG) == n_before  # no novel lanes, no launch
            # third pass: one lane evicted from the cache -> partial
            from tendermint_tpu.services.batcher import VerifiedSigCache

            key = VerifiedSigCache.key(pubkeys[0], msgs[0], sigs[0])
            lock, od = v.cache._shard(key)
            with lock:
                od.pop(key, None)
            grid3 = v.verify_commits(pubkeys, [commit])
            assert bool(grid3.all())
            rec = LAUNCHLOG.recent(kind="tables")[-1]
            assert rec["rows"] == 1 and rec["rows_cached"] == 3
        finally:
            v.close()


class TestCompileCacheTelemetry:
    def test_pre_seeded_from_boot(self):
        # the M001 catalog lint + dashboards see zero-valued series
        # before any compile/placement happens
        for result in ("hit", "miss"):
            assert (
                _counter("tendermint_mesh_compile_total", result=result) >= 0
            )
            assert (
                _counter("tendermint_table_device_cache_total", result=result)
                >= 0
            )
        for kind in ("verify", "hash", "tables", "leaf_hashes"):
            for state in ("useful", "padded", "cached"):
                assert (
                    _counter("tendermint_launch_rows", kind=kind, state=state)
                    >= 0
                )

    def test_step_cache_miss_then_hit(self):
        import jax

        from tendermint_tpu.parallel import mesh as mesh_mod

        mgr = mesh_mod.MeshManager(
            devices=list(jax.devices())[:2], executor="host"
        )
        program = f"launchlog-test-{time.monotonic_ns()}"
        seen_in_progress = []

        def build():
            seen_in_progress.append(mesh_mod.compiles_in_progress())
            time.sleep(0.01)
            return "compiled-step"

        m0 = _counter("tendermint_mesh_compile_total", result="miss")
        h0 = _counter("tendermint_mesh_compile_total", result="hit")
        rec = launchlog.begin("verify")
        step = mgr._cached_step(program, build)
        assert step == "compiled-step"
        assert seen_in_progress == [1]
        assert mesh_mod.compiles_in_progress() == 0
        assert _counter("tendermint_mesh_compile_total", result="miss") - m0 == 1
        assert rec["compile"] == "miss" and rec["compile_s"] > 0
        # second lookup: hit, no rebuild, annotated as such
        step2 = mgr._cached_step(program, lambda: pytest.fail("rebuilt"))
        assert step2 == "compiled-step"
        assert _counter("tendermint_mesh_compile_total", result="hit") - h0 == 1
        assert rec["compile"] == "hit"
        launchlog.commit(rec)

    def test_sharded_table_placement_cache(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from tendermint_tpu.parallel.mesh import MeshManager
        from tendermint_tpu.services.verifier import ShardedTableBatchVerifier

        mgr = MeshManager(devices=list(jax.devices())[:2], executor="host")
        v = ShardedTableBatchVerifier(mesh=mgr, min_device_batch=1)
        tables = jnp.zeros((2, 2, 2, 4), dtype=jnp.int16)
        key_ok = np.ones(4, dtype=bool)
        monkeypatch.setattr(v, "_tables_for", lambda pubs: (tables, key_ok))
        pubs = tuple(bytes([i]) * 32 for i in range(4))
        m0 = _counter("tendermint_table_device_cache_total", result="miss")
        h0 = _counter("tendermint_table_device_cache_total", result="hit")
        rec = launchlog.begin("tables")
        v._tables_for_mesh(pubs, mgr.mesh())
        assert (
            _counter("tendermint_table_device_cache_total", result="miss") - m0
            == 1
        )
        # the miss pays a device_put: bytes + stall on the record
        assert rec["transfer_bytes"] == tables.nbytes
        assert rec["device_put_s"] >= 0
        v._tables_for_mesh(pubs, mgr.mesh())
        assert (
            _counter("tendermint_table_device_cache_total", result="hit") - h0
            == 1
        )
        launchlog.commit(rec)


def _stub_node(**over):
    from tendermint_tpu.telemetry.heightlog import HeightLedger

    ledger = HeightLedger()
    now = time.time()
    for h in (1, 2, 3):
        ledger.record(
            {"height": h, "finality_s": 0.2 if h > 1 else None, "t_commit": now}
        )
    verifier = over.pop(
        "verifier", SimpleNamespace(snapshot=lambda: {"state": "closed"})
    )
    return SimpleNamespace(
        node_id="stub",
        consensus=SimpleNamespace(verifier=verifier, fatal_error=None),
        blockchain_reactor=SimpleNamespace(fast_sync=False),
        statesync_reactor=None,
        switch=SimpleNamespace(n_peers=lambda: 3),
        block_store=SimpleNamespace(height=3),
        hasher=None,
        height_ledger=ledger,
    )


class TestHealthDeviceSection:
    def test_device_section_reported_not_folded(self):
        from tendermint_tpu.telemetry.health import build_health

        node = _stub_node(
            verifier=SimpleNamespace(
                snapshot=lambda: {
                    "state": "closed",
                    "mesh": {"devices_active": 3, "devices_total": 4},
                }
            )
        )
        h = build_health(node)
        dev = h["device"]
        assert dev["mesh_active"] == 3 and dev["mesh_total"] == 4
        assert dev["compile_in_progress"] is False
        # mesh *degradation* folds via the mesh check, the device
        # section itself never does — and a quiet launch ledger must
        # not change the status either
        assert h["status"] == "degraded"  # from the mesh check, 3 < 4
        assert not h["checks"]["mesh"]["ok"]

    def test_last_launch_age(self):
        from tendermint_tpu.telemetry.health import build_health

        h = build_health(_stub_node())
        assert h["device"]["last_launch_age_s"] is None
        rec = launchlog.begin("verify")
        launchlog.observe("verify", "mesh", 8, 0.001)
        launchlog.commit(rec)
        h = build_health(_stub_node())
        assert h["device"]["last_launch_age_s"] is not None
        assert h["device"]["last_launch_age_s"] < 5.0
        assert h["status"] == "ok"

    def test_resolved_devices_reported(self, monkeypatch):
        """The section names the platform / device_kind / count JAX
        resolved (what tells a chip run from a quiet host fallback) and
        reads them from the already-imported jax module only."""
        import sys

        import jax

        from tendermint_tpu.telemetry import health

        dev = health.build_health(_stub_node())["device"]
        assert dev["platform"] == jax.devices()[0].platform == "cpu"
        assert dev["device_kind"] == jax.devices()[0].device_kind
        assert dev["device_count"] == len(jax.devices())
        # a process that never imported jax reports none, and the probe
        # does not import it (nor the kernel modules) to find out
        monkeypatch.delitem(sys.modules, "jax")
        assert health._resolved_devices() == {
            "platform": None, "device_kind": None, "device_count": 0,
        }
        assert "jax" not in sys.modules

    def test_meshless_node_reports_none_widths(self):
        from tendermint_tpu.telemetry.health import build_health

        h = build_health(_stub_node())
        assert h["device"]["mesh_active"] is None
        assert h["device"]["mesh_total"] is None


class TestLaunchesView:
    def test_view_returns_records_and_summary(self):
        from tendermint_tpu.telemetry import views

        launchlog.annotate(_additive=True, rows_padded=2)
        launchlog.observe("verify", "mesh", 6, 0.01)
        out = views.collect(_stub_node(), [("launches", {"n": 10})])
        assert "launches" in out
        view = out["launches"]
        assert view["records"][-1]["rows"] == 6
        assert view["summary"]["verify"]["rows"] == 6
        assert view["summary"]["verify"]["rows_padded"] == 2

    def test_collect_plain_names_still_work(self):
        from tendermint_tpu.telemetry import views

        out = views.collect(_stub_node(), ["launches"])
        assert "launches" in out


class TestDeviceReport:
    def _records(self):
        t = 1000.0
        out = []
        for i in range(4):
            out.append(
                {
                    "t": t + 0.1 * i,  # near back-to-back: idle stays small
                    "kind": "verify",
                    "backend": "mesh",
                    "queue": "coalescer",
                    "node": "n0",
                    "rows": 96,
                    "rows_padded": 32,
                    "rows_cached": 16,
                    "mesh_width": 8,
                    "transfer_bytes": 16384,
                    "consumers": {"consensus": 64, "mempool": 32},
                    "queue_wait_s": 0.001,
                    "host_prep_s": 0.004,
                    "in_flight_s": 0.080,
                    "finalize_s": 0.002,
                    "total_s": 0.087,
                }
            )
        out.append(
            {
                "t": t + 10,
                "kind": "tables",
                "backend": "mesh",
                "queue": "default",
                "node": "n0",
                "rows": 512,
                "rows_padded": 0,
                "compile": "miss",
                "compile_s": 2.5,
                "device_put_s": 0.4,
                "transfer_bytes": 1 << 20,
                "in_flight_s": 0.05,
                "total_s": 2.6,
            }
        )
        return out

    def test_waterfall_and_verdict(self):
        import device_report as dr

        report = dr.build_report(self._records())
        assert report["launches"] == 5
        verify = report["kinds"]["verify"]
        assert verify["launches"] == 4
        assert verify["occupancy_pct"] == 75.0
        assert verify["padding_waste_pct"] == 25.0
        assert verify["cache_withheld_pct"] == round(
            100.0 * 64 / (4 * 96 + 64), 1
        )
        assert verify["consumers"] == {"consensus": 256, "mempool": 128}
        tables = report["kinds"]["tables"]
        assert tables["compile_misses"] == 1 and tables["compile_s"] == 2.5
        # the 2.5s compile stall dominates every other waste source
        assert report["verdict"]["top_waste_source"] == "compile_stalls"
        text = dr.render_text(report)
        assert "compile_stalls" in text and "verdict:" in text
        assert "consumers: consensus 256, mempool 128" in text

    def test_padding_verdict_when_padding_dominates(self):
        import device_report as dr

        recs = [
            {
                "t": 1000.0 + i,
                "kind": "verify",
                "rows": 8,
                "rows_padded": 120,
                "in_flight_s": 1.0,
                "total_s": 1.1,
                "queue": "coalescer",
            }
            for i in range(3)
        ]
        report = dr.build_report(recs)
        assert report["verdict"]["top_waste_source"] == "padding_waste"
        assert "on the chip" in report["verdict"]["reseed_note"]

    def test_load_ledgers_jsonl_and_dump_dedupe(self, tmp_path):
        import device_report as dr

        recs = self._records()
        jsonl = tmp_path / "launches.jsonl"
        with open(jsonl, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        dump = tmp_path / "launchledger-test-1.json"
        with open(dump, "w") as f:
            json.dump({"reason": "test", "records": recs[:2]}, f)
        loaded = dr.load_ledgers([str(jsonl), str(dump)])
        assert len(loaded) == len(recs)  # overlap deduped

    def test_empty_report_has_no_verdict(self):
        import device_report as dr

        report = dr.build_report([])
        assert report["verdict"] is None
        assert "no launches recorded" in dr.render_text(report)


def _coalescing_stack():
    """The production default-verifier SHAPE on CPU: coalescer + dedup
    cache over a resilient host stack — the wrappers the no-double-count
    acceptance is about."""
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.services.resilient import ResilientVerifier
    from tendermint_tpu.services.verifier import HostBatchVerifier

    return CoalescingVerifier(
        ResilientVerifier(HostBatchVerifier(), max_retries=0), cache_size=4096
    )


class TestDeviceObservatoryAcceptance:
    """ISSUE 13 acceptance, on a driven load: four verifier stacks of
    the production default shape, each fed by three consumers from
    their own threads — a KNOWN number of requests, far under the
    ledger's ring, and no net whose speed decides how many launches
    there are. Every launch through the coalescing/resilient stack
    yields exactly one ledger record (records == coalesced launches,
    no double-count through the wrappers, no request lost), the hash
    lane records through the same seam, and `tools/device_report.py`
    over the `dump_telemetry?launches=N` view produces the per-kind
    waterfall and names the top waste source."""

    STACKS = 4
    CONSUMERS = ("consensus", "mempool", "fastsync")
    ROUNDS = 12

    def test_driven_stacks_device_report(self):
        import device_report as dr

        from tendermint_tpu.telemetry import views
        from tendermint_tpu.telemetry.health import build_health

        fam = REGISTRY.get("tendermint_batcher_coalesce_factor")
        coalesce0 = fam._child0().value["count"]
        stacks = [_coalescing_stack() for _ in range(self.STACKS)]
        errors: list = []

        def drive(node: int, v, consumer: str) -> None:
            # 6 novel triples, then every round re-offers 2 the stack's
            # cache has proven beside 4 novel ones: the launch carries
            # the novel rows, the record the withheld ones
            try:
                prev: list = []
                for r in range(self.ROUNDS):
                    novel = _make_sigs(
                        4 if prev else 6,
                        b"acc-%d-%s-%d" % (node, consumer.encode(), r),
                    )
                    h = v.verify_batch_async(prev[:2] + novel, consumer=consumer)
                    assert bool(h.result(timeout=30).all())
                    prev = novel
            except BaseException as e:  # surfaced after the join
                errors.append(e)

        threads = [
            threading.Thread(target=drive, args=(i, v, c), daemon=True)
            for i, v in enumerate(stacks)
            for c in self.CONSUMERS
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errors, errors
            assert not any(t.is_alive() for t in threads)

            # hash lane through the same dispatch seam: one async
            # leaf-hash launch -> exactly one leaf_hashes record
            from tendermint_tpu.services.hasher import TreeHasher
            from tendermint_tpu.services.resilient import ResilientTreeHasher

            hasher = ResilientTreeHasher(
                TreeHasher(backend="host"), TreeHasher(backend="host")
            )
            out = hasher.leaf_hashes_async(
                [b"leaf-%d" % i for i in range(64)]
            ).result(timeout=30)
            assert len(out) == 64
            assert len(LAUNCHLOG.recent(kind="leaf_hashes")) == 1

            # every handle is joined, so nothing is in flight: a record
            # commits at the join, a beat after the flush is counted
            requests = self.STACKS * len(self.CONSUMERS) * self.ROUNDS
            deadline = time.monotonic() + 10
            while True:
                launches = fam._child0().value["count"] - coalesce0
                recs = [
                    r for r in LAUNCHLOG.recent() if r.get("queue") == "coalescer"
                ]
                if len(recs) == launches or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            # far under the ring: the ledger still holds every record
            assert 0 < launches <= requests < launchlog.DEFAULT_CAPACITY
            # EXACTLY one ledger record per coalesced launch: the
            # resilient wrapper inside and the coalescer outside never
            # double-count, and no request rode two launches or none
            assert len(recs) == launches, (len(recs), launches)
            assert sum(r["requests"] for r in recs) == requests
            per_thread_novel = 6 + 4 * (self.ROUNDS - 1)
            n_threads = self.STACKS * len(self.CONSUMERS)
            assert sum(r["rows"] for r in recs) == n_threads * per_thread_novel
            assert sum(r.get("rows_cached", 0) for r in recs) == (
                n_threads * 2 * (self.ROUNDS - 1)
            )
            for rec in recs:
                assert rec["kind"] == "verify"
                assert rec["backend"] == "host"  # CPU: the host executes
                assert rec["rows"] > 0
                assert rec["consumers"], rec
                assert set(rec["consumers"]) <= set(self.CONSUMERS), rec
        finally:
            for v in stacks:
                v.close()

        # the report, over the view `dump_telemetry?launches=512` serves
        # (what rpc/core.py hands to views.collect), as it crosses the wire
        view = json.loads(
            json.dumps(
                views.collect(_stub_node(), [("launches", {"n": 512})])["launches"]
            )
        )
        assert len(view["records"]) == launches + 1  # + the hash lane's
        assert "verify" in view["summary"]
        report = dr.build_report(view["records"])
        assert report["launches"] == launches + 1
        assert report["kinds"]["verify"]["launches"] == launches
        assert report["verdict"] is not None
        assert report["verdict"]["top_waste_source"] in dr._FIXES
        text = dr.render_text(report)
        assert "device observatory" in text and "verdict:" in text

        # health: the device section sees the launches
        health = build_health(_stub_node())
        assert health["device"]["last_launch_age_s"] is not None
