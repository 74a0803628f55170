"""Contention observatory (PR 12): sampling profiler classification +
on-CPU/blocked split, ranked-lock contention timing under real
multi-thread contention, collapsed-stack golden output, process
resource telemetry, the unified queue-wait view — and the acceptance on
a driven load: a mempool + verify-stack run through a breaker trip
whose `tools/contention_report.py` waterfall names the most-contended
lock and the dominant blocked subsystem."""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"),
)

from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.telemetry.profiler import (
    PROFILER,
    ContentionProfiler,
    blocked_reason,
    classify_thread,
    collapse,
)
from tendermint_tpu.utils import lockrank


@pytest.fixture(autouse=True)
def _observatory_reset():
    """Every test leaves the process-global observatory disarmed and
    empty (the profiler + lock stats are process-wide, like FLIGHT)."""
    yield
    PROFILER.stop()
    PROFILER.reset()
    lockrank.reset_contention()


def _hist_count(name: str, **labels) -> int:
    fam = REGISTRY.get(name)
    if fam is None:
        return 0
    if not labels:  # every series of the family (the gc pauses are by `gen`)
        return sum(snap["count"] for _values, snap in fam.samples())
    want = tuple(str(labels[n]) for n in fam.labelnames)
    for values, snap in fam.samples():
        if values == want:
            return snap["count"]
    return 0


class TestClassification:
    def test_name_map_covers_node_thread_vocabulary(self):
        expect = {
            "consensus-recv": "consensus",
            "consensus-timeout": "consensus",
            "consensus-heartbeat": "consensus",
            "gossip-votes-abcdef": "consensus",
            "mempool-ingress": "ingress",
            "mempool-ingress-join": "ingress",
            "mempool-bcast-abcdef": "p2p_send",
            "verify-coalescer": "coalescer",
            "verify-coalescer-join": "coalescer",
            "dispatch-consensus": "dispatch",
            "dispatch-default": "dispatch",
            "mconn-recv": "p2p_recv",
            "mconn-send": "p2p_send",
            "mconn-ping": "p2p_send",
            "p2p-accept": "p2p_recv",
            "p2p-handshake": "p2p_recv",
            "pex-ensure": "p2p_send",
            "persistent-dial-x": "p2p_send",
            "evidence-gossip": "p2p_send",
            "statesync": "statesync",
            # the thread that stores and applies, told from state sync's
            "fastsync": "fastsync",
            "txindex-merge": "txindex_merge",
            "warm-build-kernel": "dispatch",
            "table-prebuild": "dispatch",
            "rpc-http": "rpc",
            "rpc-conn": "rpc",
            "abci-accept": "abci",
            "abci-conn": "abci",
            "MainThread": "main",
        }
        for name, sub in expect.items():
            assert classify_thread(name) == sub, name

    def test_stack_fallback_classifies_unnamed_threads(self):
        """An HTTP-handler-style thread (generic name) classifies by
        the innermost tendermint_tpu frame."""
        from tendermint_tpu.p2p.connection import parse_frame

        try:
            parse_frame(None)  # TypeError somewhere under p2p/
            pytest.fail("expected a TypeError")
        except Exception as e:
            tb = e.__traceback__
            while tb.tb_next is not None:
                tb = tb.tb_next
            frame = tb.tb_frame
        assert classify_thread("Thread-42 (worker)", frame) == "p2p_recv"

    def test_unknown_is_other(self):
        assert classify_thread("Thread-7") == "other"

    def test_blocked_reason_lock(self):
        cond = threading.Condition()
        seen = threading.Event()

        def waiter():
            with cond:
                seen.set()
                cond.wait(timeout=10)

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        assert seen.wait(5)
        time.sleep(0.05)
        frame = sys._current_frames().get(t.ident)
        assert frame is not None
        assert blocked_reason(frame) == "lock"
        with cond:
            cond.notify_all()
        t.join(5)


class TestCollapsedStacks:
    def test_collapse_golden(self):
        """The flamegraph line format is a stable contract: subsystem
        root, file:func frames, state leaf."""
        line = collapse(
            "consensus",
            ("state.py:_receive_loop", "state.py:_handle_vote"),
            "on_cpu",
        )
        assert line == (
            "consensus;state.py:_receive_loop;state.py:_handle_vote;[on_cpu]"
        )
        assert collapse("ingress", (), "blocked:lock") == "ingress;[blocked:lock]"

    def test_collapsed_output_golden(self):
        """collapsed() is deterministic: count desc, then lexical —
        byte-stable input for flamegraph tooling."""
        p = ContentionProfiler()
        with p._lock:
            p._stacks.update(
                {
                    "consensus;a.py:f;[on_cpu]": 3,
                    "ingress;b.py:g;[blocked:lock]": 7,
                    "consensus;a.py:f;[blocked:other]": 3,
                }
            )
        assert p.collapsed() == [
            "ingress;b.py:g;[blocked:lock] 7",
            "consensus;a.py:f;[blocked:other] 3",
            "consensus;a.py:f;[on_cpu] 3",
        ]


class TestLockContention:
    def test_two_threads_fighting_one_ranked_lock(self):
        """The satellite acceptance: real contention advances the wait
        histogram and attributes holds/waits to the acquiring site."""
        lk = lockrank.RankedLock("profiler.test.lock")
        before = _hist_count(
            "tendermint_lock_wait_seconds", lock="profiler.test.lock"
        )
        lockrank.set_timing(True)
        try:

            def fight():
                for _ in range(60):
                    with lk:
                        time.sleep(0.001)

            ts = [threading.Thread(target=fight) for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
        finally:
            lockrank.set_timing(False)

        snap = lockrank.contention_snapshot()
        rows = {r["lock"]: r for r in snap["locks"]}
        row = rows["profiler.test.lock"]
        assert row["wait_count"] == 120
        assert row["hold_count"] == 120
        assert row["wait_s"] > 0.01  # two threads serialized on 1ms holds
        assert row["hold_s"] > 0.1
        # per-site attribution points at the `with lk:` line above
        assert row["top_sites"], "contended waits must carry a site"
        assert row["top_sites"][0]["site"].startswith("test_profiler.py:")
        # the exported histogram advanced (contended waits >= the floor)
        after = _hist_count(
            "tendermint_lock_wait_seconds", lock="profiler.test.lock"
        )
        assert after > before

    def test_disarmed_records_nothing(self):
        lk = lockrank.RankedLock("profiler.test.idle")
        assert not lockrank.timing_enabled()
        for _ in range(10):
            with lk:
                pass
        rows = {r["lock"] for r in lockrank.contention_snapshot()["locks"]}
        assert "profiler.test.idle" not in rows

    def test_condition_integration_times_reacquire(self):
        """Condition(ranked_lock) keeps working with timing armed (the
        wait() release/reacquire cycle records a hold pair, never
        corrupts the hold stack)."""
        cond = threading.Condition(lockrank.RankedLock("profiler.test.cond"))
        lockrank.set_timing(True)
        try:
            done = threading.Event()

            def waiter():
                with cond:
                    cond.wait(timeout=5)
                done.set()

            t = threading.Thread(target=waiter, daemon=True)
            t.start()
            time.sleep(0.05)
            with cond:
                cond.notify_all()
            assert done.wait(5)
            t.join(5)
        finally:
            lockrank.set_timing(False)
        rows = {r["lock"]: r for r in lockrank.contention_snapshot()["locks"]}
        assert rows["profiler.test.cond"]["hold_count"] >= 2


from tendermint_tpu.telemetry import profiler as profiler_mod


@pytest.mark.skipif(
    not profiler_mod._CPU_CLOCKS,
    reason="per-thread CPU clocks unavailable",
)
class TestOnCpuSplit:
    def test_spinner_on_cpu_sleeper_blocked(self):
        """The GIL-pressure signal: a busy-spinning thread samples
        on-CPU, a sleeping one blocked — measured via per-thread CPU
        clocks, attributed via thread names."""
        stop = threading.Event()

        def spin():
            x = 0
            while not stop.is_set():
                x += 1

        def sleeper():
            while not stop.is_set():
                time.sleep(0.005)

        threading.Thread(target=spin, name="dispatch-bench-spin", daemon=True).start()
        threading.Thread(target=sleeper, name="mconn-recv-bench", daemon=True).start()
        p = ContentionProfiler()
        p.start(hz=100)
        try:
            time.sleep(0.7)
        finally:
            p.stop()
            stop.set()
        snap = p.snapshot()
        assert snap["cpu_clock"] is True
        assert snap["samples"] > 10
        # the subsystem buckets exist (they also absorb parked worker
        # threads left over from earlier tests in a full-suite run, so
        # the on-CPU/blocked story is asserted on the uniquely-named
        # per-thread table below)
        assert "dispatch" in snap["subsystems"]
        assert "p2p_recv" in snap["subsystems"]
        spin_th = snap["threads"]["dispatch-bench-spin"]
        assert spin_th["subsystem"] == "dispatch"
        assert spin_th["samples"] > 5
        assert spin_th["on_cpu"] > spin_th["samples"] * 0.5, spin_th
        sleep_th = snap["threads"]["mconn-recv-bench"]
        assert sleep_th["subsystem"] == "p2p_recv"
        assert sleep_th["samples"] > 5
        assert sleep_th["on_cpu"] < sleep_th["samples"] * 0.5, sleep_th

    def test_boost_window_auto_disarms(self):
        p = ContentionProfiler()
        p.boost(duration_s=0.3, hz=50)
        assert p.running()
        assert lockrank.timing_enabled()
        deadline = time.monotonic() + 5
        while p.running() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not p.running()
        # the expiring sampler thread disarms the lock timers too
        deadline = time.monotonic() + 5
        while lockrank.timing_enabled() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not lockrank.timing_enabled()

    def test_env_arming(self, monkeypatch):
        from tendermint_tpu.telemetry.profiler import maybe_start_env

        monkeypatch.setenv("TENDERMINT_TPU_PROFILE_HZ", "0")
        assert maybe_start_env() is False
        monkeypatch.setenv("TENDERMINT_TPU_PROFILE_HZ", "53")
        try:
            assert maybe_start_env() is True
            assert PROFILER.running()
            assert PROFILER.hz() == 53
        finally:
            PROFILER.stop()


class TestProcessTelemetry:
    def test_gauges_read_live_values(self):
        assert REGISTRY.counter_value("tendermint_process_rss_bytes") > 1e6
        assert REGISTRY.counter_value("tendermint_process_open_fds") > 0
        assert REGISTRY.counter_value("tendermint_process_threads") >= 1

    def test_gc_pause_timing(self):
        import gc

        from tendermint_tpu.telemetry.process import install_gc_telemetry

        assert install_gc_telemetry()
        assert install_gc_telemetry()  # idempotent
        before = _hist_count("tendermint_process_gc_pause_seconds")
        gen2 = REGISTRY.counter_value(
            "tendermint_process_gc_collections_total", gen="2"
        )
        gc.collect()
        assert _hist_count("tendermint_process_gc_pause_seconds") > before
        assert (
            REGISTRY.counter_value(
                "tendermint_process_gc_collections_total", gen="2"
            )
            > gen2
        )


    def test_a_collection_inside_a_scrape_of_its_own_series_does_not_wait(self):
        """A scrape allocates under the family lock, so a collection can
        start right there, on that thread: the hook must not wait for
        the lock (it waited for itself, and `/metrics` was dead from
        then on). What it cannot count it counts at the next collection."""
        from tendermint_tpu.telemetry import metrics, process

        process.install_gc_telemetry()
        pauses = metrics.PROCESS_GC_PAUSE
        counts = metrics.PROCESS_GC_COLLECTIONS
        before = _hist_count("tendermint_process_gc_pause_seconds")
        gen0 = REGISTRY.counter_value("tendermint_process_gc_collections_total", gen="0")
        done = []

        def collection_inside_samples():
            with pauses._lock, counts._lock:  # as samples() holds them, in turn
                process._gc_callback("start", {"generation": 0})
                process._gc_callback("stop", {"generation": 0})
            done.append(True)

        worker = threading.Thread(target=collection_inside_samples, daemon=True)
        worker.start()
        worker.join(5)
        assert done, "the hook waited for a lock its own thread holds"
        process._gc_callback("start", {"generation": 0})
        process._gc_callback("stop", {"generation": 0})
        assert _hist_count("tendermint_process_gc_pause_seconds") >= before + 2
        assert REGISTRY.counter_value(
            "tendermint_process_gc_collections_total", gen="0"
        ) >= gen0 + 2


class TestQueueWaitView:
    def test_unified_queue_table(self):
        """The queue-wait unification: waits the subsystems already
        measure fold into one table keyed by the profiler vocabulary."""
        import numpy as np

        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.telemetry import views

        class _Fake:
            def verify_batch(self, triples):
                return np.ones(len(triples), dtype=bool)

        v = CoalescingVerifier(_Fake(), cache_size=0, window_s=0.001)
        try:
            h = v.verify_batch_async(
                [(b"p" * 32, b"m", b"s" * 64)], consumer="consensus"
            )
            assert bool(h.result(timeout=10).all())
        finally:
            v.close()
        table = views.queue_wait_summary(None)
        assert set(table) >= {
            "dispatch",
            "coalescer",
            "ingress",
            "consensus",
            "p2p_send",
        }
        assert table["coalescer"]["consensus"]["count"] >= 1
        row = table["coalescer"]["consensus"]
        assert row["p99_ms"] >= row["p50_ms"] >= 0

    def test_profile_view_shape(self):
        from tendermint_tpu.telemetry import views

        out = views.collect(None, ["profile"])
        prof = out["profile"]
        assert set(prof) == {"profiler", "locks", "queues"}
        assert "subsystems" in prof["profiler"]
        assert "locks" in prof["locks"]


def _resilient_verifier(threshold=2, reset_s=0.5):
    from tendermint_tpu.services.resilient import ResilientVerifier
    from tendermint_tpu.services.verifier import HostBatchVerifier
    from tendermint_tpu.utils.circuit import CircuitBreaker

    return ResilientVerifier(
        HostBatchVerifier(),
        breaker=CircuitBreaker(
            failure_threshold=threshold, reset_timeout_s=reset_s
        ),
        max_retries=0,
    )


class TestContentionAcceptance:
    """ISSUE 12 acceptance, on a driven load that needs no consensus
    progress: a sharded mempool with batched ingress over the
    coalescing + resilient verify stack, threads under the node's own
    names fighting over a lock of `consensus.state`'s rank, profiled
    through a breaker trip — the profiler thread survives and stays
    bounded, and `tools/contention_report.py` over the view
    `dump_telemetry?profile=1` serves produces the per-subsystem on-CPU
    vs blocked waterfall naming the most-contended lock, the dominant
    blocked subsystem, and the move-out-first verdict."""

    def test_driven_load_contention_report(self):
        import contention_report as cr

        from tendermint_tpu.abci.apps import KVStoreApp
        from tendermint_tpu.crypto.keys import gen_priv_key
        from tendermint_tpu.mempool import Mempool, make_signed_tx
        from tendermint_tpu.abci.client import local_client_creator
        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.telemetry import views
        from tendermint_tpu.utils import fail

        priv = gen_priv_key(b"\x55" * 32)
        resilient = _resilient_verifier()
        verifier = CoalescingVerifier(resilient, cache_size=4096)
        mempool = Mempool(
            local_client_creator(KVStoreApp())().mempool,
            lanes=4,
            ingress_batch=True,
            verifier=verifier,
        )
        state_lock = lockrank.ranked_rlock("consensus.state")
        stop = threading.Event()
        errors: list = []

        def guarded(fn, *args):
            try:
                fn(*args)
            except BaseException as e:  # surfaced after the join
                errors.append(e)

        def consensus_recv():
            # the receive loop's shape: single votes verified on this
            # thread (the on-CPU work the waterfall must see), then a
            # vote run verified through the coalescer while the state
            # lock is held
            for i in range(2000):
                if stop.is_set():
                    return
                for j in range(32):
                    single = b"single-%d-%d" % (i, j)
                    assert priv.pub_key.verify(single, priv.sign(single))
                votes = [
                    (priv.pub_key.data, m, priv.sign(m))
                    for m in (b"vote-%d-%d" % (i, j) for j in range(4))
                ]
                with state_lock:
                    h = verifier.verify_batch_async(votes, consumer="consensus")
                    assert bool(h.result(timeout=30).all())

        def gossip_votes():
            # the gossip routines' shape: read the round state, often
            while not stop.is_set():
                with state_lock:
                    pass
                time.sleep(0.0005)

        def pump(k: int):
            # an RPC front end at full tilt: signing is the on-CPU work
            # the waterfall must see; bounded, so the pool is too
            for i in range(3000):
                if stop.is_set():
                    return
                tx = make_signed_tx(priv, b"prof-%d-%d=%d" % (k, i, i))
                mempool.check_tx_async(tx, lambda res: None)

        threads = [
            threading.Thread(target=guarded, args=args, name=name, daemon=True)
            for name, args in (
                ("consensus-recv", (consensus_recv,)),
                ("gossip-votes-acc", (gossip_votes,)),
                ("rpc-http", (pump, 0)),
                ("rpc-http", (pump, 1)),
            )
        ]

        def wait_for(cond, what: str) -> None:
            deadline = time.monotonic() + 20
            while not cond():
                assert time.monotonic() < deadline, what
                assert not errors, errors
                time.sleep(0.01)

        PROFILER.reset()
        lockrank.reset_contention()
        PROFILER.start(hz=97)
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)
            # nemesis leg: the device dies under load, the breaker
            # degrades to host, heals — the profiler must ride through
            opened0 = resilient.snapshot()["times_opened"]
            fail.set_device_fault("verify")
            wait_for(
                lambda: resilient.snapshot()["times_opened"] > opened0,
                "the breaker never opened under the injected fault",
            )
            fail.clear_device_faults()
            wait_for(
                lambda: resilient.snapshot()["state"] == "closed",
                "the breaker never closed again",
            )
            time.sleep(0.3)
            stop.set()
            for t in threads:
                t.join(30)
            assert not errors, errors
            assert not any(t.is_alive() for t in threads)

            # survives + bounded
            assert PROFILER.running(), "profiler thread died mid-chaos"
            snap = PROFILER.snapshot()
            assert snap["samples"] > 50
            assert len(snap["threads"]) <= PROFILER.MAX_THREADS
            with PROFILER._lock:
                n_stacks = len(PROFILER._stacks)
            assert n_stacks <= PROFILER.MAX_STACKS

            # the report, over the view `dump_telemetry?profile=1`
            # serves (rpc/core.py -> views.collect), as it crosses the wire
            profile = json.loads(
                json.dumps(views.collect(None, ["profile"])["profile"])
            )
            report = cr.build_report(profile)

            assert report["samples"] > 50
            waterfall = {r["subsystem"]: r for r in report["waterfall"]}
            for sub in ("consensus", "ingress", "coalescer", "dispatch"):
                assert sub in waterfall, waterfall.keys()
            total_on_cpu = sum(r["on_cpu"] for r in report["waterfall"])
            total_blocked = sum(r["blocked"] for r in report["waterfall"])
            assert total_on_cpu > 0 and total_blocked > 0

            # the three named answers the issue demands
            lock = report["most_contended_lock"]
            assert lock is not None and lock["lock"], report
            assert lock["wait_count"] > 0
            contended = {
                r["lock"] for r in profile["locks"]["locks"] if r["wait_count"]
            }
            assert "consensus.state" in contended, contended
            dom = report["dominant_blocked_subsystem"]
            assert dom is not None and dom["subsystem"]
            verdict = report["verdict"]
            assert verdict is not None
            assert verdict["move_out_first"] not in ("main", "other")
            assert "ROADMAP item 4" in verdict["reason"]

            text = cr.render_text(report)
            assert "most-contended lock: " + lock["lock"] in text
            assert "dominant blocked subsystem: " + dom["subsystem"] in text
            assert "verdict: " in text

            # flamegraph output is non-empty, well-formed lines
            lines = cr.collapsed_lines(profile)
            assert lines
            for line in lines[:5]:
                stack, count = line.rsplit(" ", 1)
                assert ";" in stack and int(count) > 0

            # the unified queue table rode along
            assert "queues" in profile
            assert "dispatch" in profile["queues"]
        finally:
            stop.set()
            fail.clear_device_faults()
            PROFILER.stop()
            PROFILER.reset()
            lockrank.reset_contention()
            mempool.close()
            verifier.close()
