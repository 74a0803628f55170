"""Telemetry subsystem: registry semantics (concurrency, histogram
bucket math, Prometheus golden exposition), the span tracer, the
`GET /metrics` route, and the registry-driven hot-path bench tool.

End-to-end coverage against a full running node (consensus phase
histograms moving, breaker series, `dump_telemetry`) lives in
`tests/test_telemetry_node.py` with the other node-composition suites.
"""

from __future__ import annotations

import json
import math
import sys
import threading
import time
import urllib.request

import pytest

from tendermint_tpu.telemetry import REGISTRY, TRACER
from tendermint_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from tendermint_tpu.telemetry.tracer import CPU_SHARE_NS, Tracer

from tests.helpers import THREAD_CLOCK_STEP_S, cpu_slack


class TestCountersAndGauges:
    def test_counter_basics(self):
        reg = Registry()
        c = Counter("t_total", "help", registry=reg)
        assert c.value == 0.0
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labeled_counter_children(self):
        reg = Registry()
        c = Counter("t_total", "", labelnames=("kind",), registry=reg)
        c.labels(kind="a").inc()
        c.labels("a").inc()  # positional == keyword
        c.labels(kind="b").inc(5)
        assert reg.counter_value("t_total", kind="a") == 2.0
        assert reg.counter_value("t_total", kind="b") == 5.0
        assert reg.counter_value("t_total", kind="never") == 0.0
        with pytest.raises(ValueError):
            c.inc()  # labeled family has no default child
        with pytest.raises(ValueError):
            c.labels("a", "b")  # wrong arity

    def test_duplicate_registration_rejected(self):
        reg = Registry()
        Counter("dup", "", registry=reg)
        with pytest.raises(ValueError):
            Counter("dup", "", registry=reg)

    def test_gauge_set_inc_dec(self):
        reg = Registry()
        g = Gauge("g", "", registry=reg)
        g.set(7)
        g.inc(3)
        g.dec()
        assert g.value == 9.0

    def test_gauge_callback_wins_and_survives_errors(self):
        reg = Registry()
        g = Gauge("g", "", registry=reg)
        g.set(1)
        g.set_function(lambda: 42)
        assert g.value == 42.0
        boom = {"on": False}

        def fn():
            if boom["on"]:
                raise RuntimeError("source gone")
            return 13

        g.set_function(fn)
        assert g.value == 13.0
        boom["on"] = True
        # a dead source keeps the last good value, never breaks a scrape
        assert g.value == 13.0
        assert "g 13" in reg.prometheus_text()


class TestHistogram:
    def test_bucket_math(self):
        reg = Registry()
        h = Histogram("h", "", buckets=(1, 5, 10), registry=reg)
        for v in (0.5, 1.0, 3.0, 7.0, 100.0):
            h.observe(v)
        snap = h.value
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(111.5)
        # cumulative: <=1 gets 0.5 and 1.0; <=5 adds 3.0; <=10 adds 7.0
        assert snap["buckets"] == [
            (1.0, 2),
            (5.0, 3),
            (10.0, 4),
            (math.inf, 5),
        ]

    def test_buckets_are_sorted_on_registration(self):
        reg = Registry()
        h = Histogram("h", "", buckets=(10, 1, 5), registry=reg)
        assert [b for b, _ in h.value["buckets"]] == [1.0, 5.0, 10.0, math.inf]

    def test_quantile_interpolation(self):
        reg = Registry()
        h = Histogram("h", "", buckets=(1, 2, 4), registry=reg)
        for _ in range(50):
            h.observe(0.5)
        for _ in range(50):
            h.observe(3.0)
        # p50 falls at the boundary of the first bucket
        assert h.quantile(0.5) == pytest.approx(1.0)
        # p99 interpolates inside (2, 4]
        assert 2.0 < h.quantile(0.99) <= 4.0
        empty = Histogram("h2", "", buckets=(1,), registry=reg)
        assert math.isnan(empty.quantile(0.5))

    def test_labeled_histogram(self):
        reg = Registry()
        h = Histogram("h", "", labelnames=("backend",), buckets=(1,), registry=reg)
        h.labels(backend="host").observe(0.5)
        h.labels(backend="host").observe(2.0)
        assert h.labels(backend="host").value["count"] == 2
        assert h.labels(backend="device").value["count"] == 0


class TestConcurrency:
    def test_counter_under_threads_is_exact(self):
        reg = Registry()
        c = Counter("c_total", "", labelnames=("k",), registry=reg)
        h = Histogram("lat", "", buckets=(0.5, 1.0), registry=reg)
        n_threads, per_thread = 8, 5_000

        def hammer(i):
            child = c.labels(k=str(i % 2))
            for _ in range(per_thread):
                child.inc()
                h.observe(0.25)

        threads = [
            threading.Thread(target=hammer, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = reg.counter_value("c_total", k="0") + reg.counter_value(
            "c_total", k="1"
        )
        assert total == n_threads * per_thread
        assert h.value["count"] == n_threads * per_thread


class TestPrometheusExposition:
    def test_golden_output(self):
        reg = Registry()
        c = Counter("a_total", "counts things", labelnames=("kind",), registry=reg)
        g = Gauge("b", "a gauge", registry=reg)
        h = Histogram("lat_seconds", "latency", buckets=(0.5, 1.0), registry=reg)
        c.labels(kind="x").inc(3)
        g.set(1.5)
        h.observe(0.25)
        h.observe(0.75)
        assert reg.prometheus_text() == (
            "# HELP a_total counts things\n"
            "# TYPE a_total counter\n"
            'a_total{kind="x"} 3\n'
            "# HELP b a gauge\n"
            "# TYPE b gauge\n"
            "b 1.5\n"
            "# HELP lat_seconds latency\n"
            "# TYPE lat_seconds histogram\n"
            'lat_seconds_bucket{le="0.5"} 1\n'
            'lat_seconds_bucket{le="1"} 2\n'
            'lat_seconds_bucket{le="+Inf"} 2\n'
            "lat_seconds_sum 1\n"
            "lat_seconds_count 2\n"
        )

    def test_label_and_help_escaping(self):
        reg = Registry()
        c = Counter("e_total", 'has "quotes"\nand newline', labelnames=("v",), registry=reg)
        c.labels(v='a"b\\c\nd').inc()
        text = reg.prometheus_text()
        assert '# HELP e_total has "quotes"\\nand newline\n' in text
        assert 'e_total{v="a\\"b\\\\c\\nd"} 1\n' in text

    def test_unlabeled_families_expose_zero_samples(self):
        reg = Registry()
        Counter("idle_total", "", registry=reg)
        Histogram("idle_seconds", "", buckets=(1,), registry=reg)
        text = reg.prometheus_text()
        assert "idle_total 0\n" in text
        assert "idle_seconds_count 0\n" in text

    def test_to_dict_round_trips_through_json(self):
        reg = Registry()
        h = Histogram("h", "", buckets=(1,), registry=reg)
        h.observe(0.5)
        d = json.loads(json.dumps(reg.to_dict()))
        assert d["h"]["type"] == "histogram"
        assert d["h"]["series"][0]["count"] == 1
        assert d["h"]["series"][0]["buckets"][-1][0] == "+Inf"


class TestTracer:
    def test_span_context_manager_records(self):
        tr = Tracer(capacity=8)
        with tr.span("unit.work", n=3):
            pass
        spans = tr.recent()
        assert len(spans) == 1
        assert spans[0]["name"] == "unit.work"
        assert spans[0]["attrs"]["n"] == 3
        assert spans[0]["duration_s"] >= 0

    def test_span_records_errors(self):
        tr = Tracer(capacity=8)
        with pytest.raises(RuntimeError):
            with tr.span("unit.fail"):
                raise RuntimeError("boom")
        assert tr.recent()[0]["attrs"]["error"] == "RuntimeError"

    def test_ring_capacity_and_prefix_filter(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.add(f"a.{i % 2}", 0.0, 1.0, i=i)
        assert len(tr) == 4
        assert all(s["name"].startswith("a.") for s in tr.recent(prefix="a."))
        assert tr.recent(n=2)[-1]["attrs"]["i"] == 9


class TestStage:
    """`TRACER.stage`: a stopwatch that shows in a profiler trace and
    writes no span (fast-sync's per-block stages)."""

    def test_the_duration_is_the_monotonic_clocks_and_goes_to_the_sink(self):
        tracer = Tracer(capacity=4)
        got = []
        with tracer.stage("fastsync.store", lambda *clocks: got.append(clocks)) as st:
            time.sleep(0.01)
        assert 0.009 < st.seconds < 0.5
        # the sink's two values: the wall's seconds, then the thread's CPU seconds
        assert got == [(st.seconds, st.cpu_seconds)] and st.name == "fastsync.store"
        # a stage is no span: the ring stays as it was
        assert len(tracer) == 0

    def test_the_sink_hears_of_a_stage_that_raised(self):
        got = []
        with pytest.raises(KeyError):
            with TRACER.stage("s", lambda *clocks: got.append(clocks)):
                raise KeyError("boom")
        assert len(got) == 1 and len(got[0]) == 2

    def test_a_busy_stage_reads_cpu_under_wall_and_a_sleeping_one_near_none(self):
        # a spin much longer than the clock's step (10 ms on a TPU host)
        spin = max(0.05, 8 * THREAD_CLOCK_STEP_S)
        with TRACER.stage("busy") as busy:
            end = time.perf_counter() + spin
            while time.perf_counter() < end:
                pass
        # it ran all the while (less what the host took the core away for)
        assert 0.4 * spin < busy.cpu_seconds <= busy.seconds + cpu_slack()
        with TRACER.stage("asleep") as asleep:
            time.sleep(0.05)
        assert asleep.seconds >= 0.05 and 0 <= asleep.cpu_seconds < 0.01 + cpu_slack()

    def test_a_stage_does_not_count_another_threads_cpu(self):
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pass

        other = threading.Thread(target=spin, daemon=True)
        other.start()
        try:
            with TRACER.stage("waiting") as st:
                time.sleep(0.1)
        finally:
            stop.set()
            other.join()
        # the other thread burned 0.1 s of CPU meanwhile: none of it is here
        assert st.seconds >= 0.1 and st.cpu_seconds < 0.02 + cpu_slack()

    @staticmethod
    def _clocks_by_hand(monkeypatch):
        """The tracer's two clocks moved by hand, the CPU clock's reads
        counted; no reading of one clock is ever met on the other."""
        from tendermint_tpu.telemetry import tracer

        monkeypatch.setattr(tracer, "_reading", threading.local())

        class Clocks:
            wall = 10**12
            cpu = 10**9
            reads = 0
            time = staticmethod(time.time)

            def perf_counter_ns(self) -> int:
                return self.wall

            def thread_time_ns(self) -> int:
                self.reads += 1
                return self.cpu

            def run(self, ns: int) -> None:
                self.wall += ns
                self.cpu += ns

            def wait(self, ns: int) -> None:
                self.wall += ns

        clocks = Clocks()
        monkeypatch.setattr(tracer, "time", clocks)
        return clocks

    def test_stages_that_meet_at_a_boundary_share_one_reading_of_the_cpu_clock(self, monkeypatch):
        """The clock is a system call of 6-24 us on a TPU host: a chain of
        back-to-back stages reads it once a stage, not twice, and a
        child that ends where its parent ends adds one reading, not two."""
        clocks = self._clocks_by_hand(monkeypatch)
        got = []
        for _ in range(5):
            with TRACER.stage("chain", lambda s, c: got.append((s, c))):
                clocks.run(200_000)
            clocks.run(3_000)  # the sink, the next stage's making
        assert clocks.reads == 6  # the first enter, then every exit
        # the chain's CPU is the thread's own: what ran between two
        # stages is the later one's, nothing of it is lost
        assert [round(c * 1e9) for _s, c in got] == [200_000] + 4 * [203_000]
        assert [round(s * 1e9) for s, _c in got] == 5 * [200_000]
        clocks.run(2 * CPU_SHARE_NS)
        clocks.reads = 0
        with TRACER.stage("parent") as parent:  # 1: its enter
            clocks.run(1_000)
            with TRACER.stage("first child") as first:  # shares the parent's
                clocks.run(200_000)  # 2: its exit
            clocks.run(200_000)
            with TRACER.stage("last child") as last:  # 3: its enter
                clocks.run(150_000)
                clocks.wait(50_000)  # 4: its exit, and the parent's
            clocks.run(2_000)
        assert clocks.reads == 4
        assert round(first.cpu_seconds * 1e9) == 201_000 and round(last.cpu_seconds * 1e9) == 150_000
        # what a shared reading can misplace is under the share
        assert round(parent.cpu_seconds * 1e9) == 551_000 and round(parent.seconds * 1e9) == 603_000

    def test_a_boundary_later_than_the_share_reads_the_clock_anew(self, monkeypatch):
        clocks = self._clocks_by_hand(monkeypatch)
        with TRACER.stage("a"):
            clocks.run(100_000)
        clocks.wait(5_000_000)  # the thread lost the interpreter
        clocks.run(CPU_SHARE_NS)
        with TRACER.stage("b") as b:
            clocks.run(100_000)
        assert clocks.reads == 4 and round(b.cpu_seconds * 1e9) == 100_000

    def test_a_stage_shorter_than_the_share_still_reads_its_own_clock(self, monkeypatch):
        clocks = self._clocks_by_hand(monkeypatch)
        with TRACER.stage("short") as short:
            clocks.run(7_000)
        # never the reading it entered with: that would be no CPU at all
        assert clocks.reads == 2 and round(short.cpu_seconds * 1e9) == 7_000
        with TRACER.stage("empty") as empty:  # shares `short`'s exit, then its own
            pass
        assert clocks.reads == 3 and empty.cpu_seconds == 0.0

    def test_a_reading_is_its_threads_own(self, monkeypatch):
        reads, clock = [], time.thread_time_ns

        def counted() -> int:
            reads.append(threading.get_ident())
            return clock()

        monkeypatch.setattr(time, "thread_time_ns", counted)

        def elsewhere() -> None:
            with TRACER.stage("there"):
                pass

        with TRACER.stage("here"):
            other = threading.Thread(target=elsewhere)
            other.start()
            other.join()
        # microseconds after this thread's reading, the other takes its own two
        assert [t != threading.get_ident() for t in reads].count(True) == 2

    def test_without_jax_loaded_the_stage_is_timed_without_an_annotation(self, monkeypatch):
        monkeypatch.delitem(sys.modules, "jax", raising=False)
        with TRACER.stage("s") as st:
            assert st._annotation is None
        assert st.seconds > 0

    def test_with_jax_loaded_the_stage_holds_a_profiler_annotation(self):
        import jax  # no backend starts: the annotation needs none

        with TRACER.stage("s") as st:
            assert isinstance(st._annotation, jax.profiler.TraceAnnotation)

    def test_a_stage_costs_microseconds_with_no_profiler_session(self):
        import jax  # noqa: F401 - the annotation's path, as a node runs it

        def batch(n=2000) -> float:
            t = time.perf_counter()
            for _ in range(n):
                with TRACER.stage("s"):
                    pass
            return (time.perf_counter() - t) / n

        assert min(batch() for _ in range(5)) < 5e-6


class TestCatalog:
    def test_global_catalog_registered(self):
        # the catalog module must have registered every advertised family
        from tendermint_tpu.telemetry import metrics  # noqa: F401

        for name in (
            "tendermint_consensus_height",
            "tendermint_consensus_phase_seconds",
            "tendermint_consensus_round_skips_total",
            "tendermint_consensus_vote_drain_batch_size",
            "tendermint_verify_batch_size",
            "tendermint_hash_seconds",
            "tendermint_breaker_state",
            "tendermint_breaker_transitions_total",
            "tendermint_p2p_sent_bytes_total",
            "tendermint_mempool_size",
            "tendermint_wal_fsync_seconds",
        ):
            assert REGISTRY.get(name) is not None, name

    def test_breaker_binds_telemetry(self):
        from tendermint_tpu.utils.circuit import CircuitBreaker

        before = REGISTRY.counter_value(
            "tendermint_breaker_transitions_total", kind="t-unit", to="open"
        )
        b = CircuitBreaker(failure_threshold=2, name="t-unit")
        b.record_failure()
        b.record_failure()
        assert b.state == "open"
        assert REGISTRY.counter_value(
            "tendermint_breaker_state", kind="t-unit"
        ) == 2.0
        assert (
            REGISTRY.counter_value(
                "tendermint_breaker_transitions_total", kind="t-unit", to="open"
            )
            == before + 1
        )


class TestMetricsRoute:
    def test_get_metrics_serves_prometheus_text(self):
        from tendermint_tpu.rpc.server import RPCServer

        srv = RPCServer({"echo": lambda: {"ok": True}}, "tcp://127.0.0.1:0")
        srv.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
            # global registry families render, HELP/TYPE lines included
            assert "# TYPE tendermint_consensus_height gauge" in body
            assert "# TYPE tendermint_verify_seconds histogram" in body
            assert "tendermint_p2p_sent_bytes_total" in body
            # the scrape itself is counted
            assert REGISTRY.counter_value(
                "tendermint_rpc_requests_total", method="metrics", result="ok"
            ) >= 1
            # JSON-RPC routes still work beside the exposition route
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/echo", timeout=10
            ) as resp:
                assert json.load(resp)["result"] == {"ok": True}
        finally:
            srv.stop()


class TestSpanPersistence:
    """Span timelines survive restarts: bounded JSONL ring under the
    data dir, replayed into the tracer on boot (ROADMAP observability
    follow-up)."""

    def test_sink_appends_and_load_roundtrips(self, tmp_path):
        from tendermint_tpu.telemetry.spanlog import SpanLog

        tr = Tracer(capacity=16)
        log = SpanLog(str(tmp_path / "spans.jsonl"), capacity=16)
        tr.add_sink(log.append)
        tr.add("consensus.propose", 1.0, 2.0, height=7)
        tr.add("verify.batch", 2.0, 2.5, n=64)
        tr.remove_sink(log.append)
        log.close()
        loaded = SpanLog(str(tmp_path / "spans.jsonl"), capacity=16).load()
        assert [d["name"] for d in loaded] == [
            "consensus.propose",
            "verify.batch",
        ]
        assert loaded[0]["attrs"]["height"] == 7

    def test_ring_compacts_to_capacity(self, tmp_path):
        from tendermint_tpu.telemetry.spanlog import SpanLog

        path = str(tmp_path / "spans.jsonl")
        log = SpanLog(path, capacity=8)
        tr = Tracer(capacity=64)
        tr.add_sink(log.append)
        for i in range(40):
            tr.add("s", float(i), float(i) + 0.5, i=i)
        log.close()
        loaded = SpanLog(path, capacity=8).load()
        assert len(loaded) <= 8
        # the NEWEST spans survive compaction
        assert loaded[-1]["attrs"]["i"] == 39

    def test_persist_spans_replays_then_sinks(self, tmp_path):
        from tendermint_tpu.telemetry.spanlog import SpanLog, persist_spans

        path = str(tmp_path / "spans.jsonl")
        first = SpanLog(path, capacity=32)
        tr0 = Tracer(capacity=32)
        tr0.add_sink(first.append)
        tr0.add("consensus.commit", 10.0, 11.0, height=42)
        first.close()

        # "restart": a fresh tracer replays the persisted window and
        # keeps persisting new spans
        tr1 = Tracer(capacity=32)
        log = persist_spans(tr1, path, capacity=32)
        restored = tr1.recent()
        assert restored[0]["name"] == "consensus.commit"
        assert restored[0]["attrs"]["restored"] is True
        assert restored[0]["attrs"]["height"] == 42
        tr1.add("consensus.propose", 11.0, 12.0, height=43)
        tr1.remove_sink(log.append)
        log.close()
        names = [d["name"] for d in SpanLog(path, capacity=32).load()]
        # the replayed span is NOT re-appended; the new one is
        assert names == ["consensus.commit", "consensus.propose"]

    def test_torn_final_line_is_skipped(self, tmp_path):
        from tendermint_tpu.telemetry.spanlog import SpanLog

        path = tmp_path / "spans.jsonl"
        path.write_text(
            '{"name":"ok","start":1.0,"end":2.0}\n{"name":"torn","sta'
        )
        loaded = SpanLog(str(path), capacity=8).load()
        assert [d["name"] for d in loaded] == ["ok"]

    def test_remove_sink_only_removes_own_sink(self):
        tr = Tracer(capacity=4)
        mine, theirs = [], []
        tr.add_sink(mine.append)
        tr.add_sink(theirs.append)  # a successor joined
        tr.remove_sink(mine.append)  # stopping node must not strip it
        tr.add("s", 0.0, 1.0)
        assert len(theirs) == 1 and not mine


class TestHistogramExemplars:
    """Exemplar trace ids on histogram observations: the breadcrumb
    from an aggregate back to one concrete traced request (JSON dump
    only — text exposition 0.0.4 has no exemplar syntax)."""

    def test_observe_with_exemplar_surfaces_in_snapshots(self):
        reg = Registry()
        h = Histogram("h", "", buckets=(1.0,), registry=reg)
        h.observe(0.5)
        assert "exemplar" not in h.value
        h.observe(0.7, exemplar="feedface01")
        assert h.value["exemplar"] == "feedface01"
        series = reg.to_dict()["h"]["series"][0]
        assert series["exemplar"] == "feedface01"
        # text exposition is unchanged by exemplars
        assert "exemplar" not in reg.prometheus_text()

    def test_labeled_children_keep_independent_exemplars(self):
        reg = Registry()
        h = Histogram("h", "", labelnames=("stage",), buckets=(1.0,), registry=reg)
        h.labels(stage="drain").observe(0.1, exemplar="aaaa")
        h.labels(stage="verify").observe(0.2)
        assert h.labels(stage="drain").value["exemplar"] == "aaaa"
        assert "exemplar" not in h.labels(stage="verify").value


class _Node:
    """Something the collector tracks and a `weakref` can watch."""


@pytest.fixture
def heap():
    """The collector's policy is the process's: every test here leaves
    it as it found it (nothing frozen, the old thresholds, the old mark),
    so the rest of the suite runs as before."""
    import gc

    from tendermint_tpu.telemetry import process

    process.install_gc_telemetry()
    thresholds, unsettled = gc.get_threshold(), process._heap_unsettled
    gc.unfreeze()
    process.mark_heap_unsettled()
    yield process
    gc.unfreeze()
    gc.set_threshold(*thresholds)
    process._heap_unsettled = unsettled


def _compile_event() -> None:
    """What `jax.monitoring` tells the listener of `utils/jax_cache.py`
    when an executable has been built or loaded."""
    from tendermint_tpu.utils import jax_cache

    jax_cache._on_duration(jax_cache._COMPILE_EVENT, 0.01, fun_name="heap_settle_probe")


def _gen2_collections() -> float:
    return REGISTRY.counter_value("tendermint_process_gc_collections_total", gen="2")


def _settles() -> float:
    return REGISTRY.counter_value("tendermint_process_heap_settles_total")


def _frozen() -> float:
    return REGISTRY.counter_value("tendermint_process_gc_frozen_objects")


class TestHeapSettle:
    """`telemetry/process.py` `settle_heap`: what the process keeps for
    life is frozen once, and again only after an executable was met."""

    def test_a_settle_freezes_what_was_tracked(self, heap):
        import gc

        gc.collect()  # the garbage is not counted below
        tracked, settles = len(gc.get_objects()), _settles()
        assert heap.settle_heap() is True
        assert gc.get_freeze_count() >= 0.95 * tracked
        assert len(gc.get_objects()) < 0.05 * tracked
        assert _frozen() == gc.get_freeze_count()
        assert _settles() == settles + 1

    def test_the_thresholds_in_force_after_a_settle_are_the_constants(self, heap):
        import gc

        _young, middle, old = gc.get_threshold()
        assert heap.settle_heap() is True
        assert gc.get_threshold() == (heap.YOUNG_GENERATION_THRESHOLD, middle, old)
        assert heap.YOUNG_GENERATION_THRESHOLD == 50_000

    def test_a_second_settle_with_nothing_traced_since_is_a_no_op(self, heap):
        assert heap.settle_heap() is True
        collections, settles = _gen2_collections(), _settles()
        assert heap.settle_heap() is False
        assert _gen2_collections() == collections  # no collection was paid for
        assert _settles() == settles

    @pytest.mark.parametrize("by", ["settle_heap", "the_hook"])
    def test_a_compile_event_unsettles_and_the_next_settle_freezes_again(self, heap, by):
        """Explicitly (`Node.start`), or at the end of the collector's
        own next full collection, which has just paid for the walk."""
        import gc

        assert heap.settle_heap() is True
        frozen, settles = gc.get_freeze_count(), _settles()
        traced = [[i] for i in range(2_000)]  # stands for an executable's jaxprs
        _compile_event()
        assert heap._heap_unsettled
        if by == "settle_heap":
            assert heap.settle_heap() is True
        else:
            gc.collect()
        assert not heap._heap_unsettled
        assert gc.get_freeze_count() >= frozen + len(traced)
        assert _settles() == settles + 1

    def test_the_hook_leaves_a_process_that_never_settled_alone(self, heap, monkeypatch):
        import gc

        monkeypatch.setattr(heap, "_settles", 0)
        gc.collect()
        frozen = gc.get_freeze_count()  # CPython's own few hundred, not 0
        _compile_event()
        gc.collect()
        assert gc.get_freeze_count() == frozen and heap._heap_unsettled

    def test_a_frozen_object_is_freed_when_its_last_reference_goes(self, heap):
        import gc
        import weakref

        node = _Node()
        watch = weakref.ref(node)
        assert heap.settle_heap() is True
        collections = _gen2_collections()
        gc.disable()
        try:
            del node
            assert watch() is None  # by reference count: nothing collected it
        finally:
            gc.enable()
        assert _gen2_collections() == collections

    def test_a_cycle_made_after_the_settle_is_collected(self, heap):
        import gc
        import weakref

        assert heap.settle_heap() is True
        node = _Node()
        node.me = node
        watch = weakref.ref(node)
        del node
        assert watch() is not None
        gc.collect()
        assert watch() is None

    def test_the_pause_sum_the_benchmark_reads_is_the_sum_over_gen(self, heap):
        """`process.gc_pause_share` takes the rise of `..._seconds_sum`
        over whatever labels the series carries (`benchmark/lib/rpc.py`
        `metric`): with `gen` on it, still every pause of every generation."""
        import gc

        from benchmark.lib import rpc

        for generation in (0, 1, 2):
            gc.collect(generation)
        parsed = rpc.parse_metrics(REGISTRY.prometheus_text())
        name = "tendermint_process_gc_pause_seconds_sum"
        assert sorted(labels["gen"] for labels, _v in parsed[name]) == ["0", "1", "2"]
        by_gen = [rpc.metric(parsed, name, gen=g) for g in ("0", "1", "2")]
        assert all(seconds > 0 for seconds in by_gen)
        assert rpc.metric(parsed, name) == pytest.approx(sum(by_gen))
