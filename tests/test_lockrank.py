"""utils/lockrank.py: the runtime half of tmlint.

Covers rank-inversion detection (with both threads' stacks in the
report), order-graph cycle detection across 3 threads, a deliberate
ABBA deadlock caught WITHOUT hanging, same-rank lane seq ordering,
Condition integration, zero-overhead pass-through when disabled, and
the acceptance scenario: a deliberate inversion injected against live
mempool admission traffic is detected and reported while the suite
keeps running."""

import threading
import time

import pytest

from tendermint_tpu.utils import lockrank
from tendermint_tpu.utils.lockrank import (
    LockRankViolation,
    RankedLock,
    RankedRLock,
    ranked_lock,
    ranked_rlock,
)


@pytest.fixture(autouse=True)
def _clean_lockrank():
    """Isolate the process-global graph/violations per test, and drain
    deliberate violations so the conftest guard doesn't re-fail us."""
    lockrank.reset()
    lockrank.set_raise(None)
    yield
    lockrank.set_raise(None)
    lockrank.reset()


class TestRankInversion:
    def test_ascending_order_is_clean(self):
        lo = RankedLock("mempool.wal")
        hi = RankedLock("mempool.counter")
        with lo:
            with hi:
                pass
        assert lockrank.violations() == []

    def test_inversion_recorded_with_stack(self):
        lo = RankedLock("mempool.wal")
        hi = RankedLock("mempool.counter")
        with hi:
            with lo:
                pass
        vs = lockrank.violations()
        assert len(vs) == 1
        assert vs[0]["kind"] == "rank_inversion"
        assert "mempool.wal" in vs[0]["message"]
        assert "mempool.counter" in vs[0]["message"]
        report = lockrank.render_report()
        assert "test_inversion_recorded_with_stack" in report

    def test_inversion_report_carries_both_threads_stacks(self):
        lo = RankedLock("mempool.wal")
        hi = RankedLock("mempool.counter")

        def legal():
            with lo:
                with hi:
                    # tmlint: disable=L002 -- test scaffolding: widens the window in which the legal edge is observed first
                    time.sleep(0.01)

        t = threading.Thread(target=legal, name="legal-order")
        t.start()
        t.join()
        with hi:
            with lo:
                pass
        (v,) = lockrank.violations()
        labels = [s["label"] for s in v["stacks"]]
        assert any("this acquire" in lb or "acquire of" in lb for lb in labels)
        # the legal direction was observed first on the other thread
        assert any("first observed" in lb for lb in labels)
        threads = {s["thread"] for s in v["stacks"]}
        assert "legal-order" in threads

    def test_same_rank_ascending_seq_allowed(self):
        lanes = [RankedRLock("mempool.lane", seq=i) for i in range(4)]
        for ln in lanes:  # index order, like Mempool.lock()
            ln.acquire()
        for ln in reversed(lanes):
            ln.release()
        assert lockrank.violations() == []

    def test_same_rank_descending_seq_flagged(self):
        lanes = [RankedRLock("mempool.lane", seq=i) for i in range(2)]
        lanes[1].acquire()
        lanes[0].acquire()
        lanes[0].release()
        lanes[1].release()
        vs = lockrank.violations()
        assert len(vs) == 1 and vs[0]["kind"] == "rank_inversion"

    def test_rlock_reentry_is_not_a_violation(self):
        mtx = RankedRLock("consensus.state")
        with mtx:
            with mtx:
                pass
        assert lockrank.violations() == []

    def test_unranked_locks_skip_rank_check(self):
        a = RankedLock("custom.a", rank=None)
        b = RankedLock("custom.b", rank=None)
        with b:
            with a:
                pass
        assert lockrank.violations() == []


class TestCycleDetection:
    def test_two_lock_aba_cycle(self):
        a = RankedLock("custom.a", rank=None)
        b = RankedLock("custom.b", rank=None)
        done = threading.Event()

        def t1():
            with a:
                with b:
                    done.set()

        th = threading.Thread(target=t1, name="ab-thread")
        th.start()
        th.join()
        with b:
            with a:  # closes the cycle in the order graph — no contention
                pass
        vs = lockrank.violations()
        assert len(vs) == 1
        assert vs[0]["kind"] == "cycle"
        assert "custom.a" in vs[0]["message"]
        threads = {s["thread"] for s in vs[0]["stacks"]}
        assert "ab-thread" in threads  # both sides' stacks present
        assert len(threads) >= 2

    def test_three_thread_three_lock_cycle(self):
        a = RankedLock("custom.a", rank=None)
        b = RankedLock("custom.b", rank=None)
        c = RankedLock("custom.c", rank=None)

        def nest(outer, inner, name):
            def run():
                with outer:
                    with inner:
                        pass

            t = threading.Thread(target=run, name=name)
            t.start()
            t.join()

        nest(a, b, "t-ab")
        nest(b, c, "t-bc")
        nest(c, a, "t-ca")  # a->b->c->a
        vs = [v for v in lockrank.violations() if v["kind"] == "cycle"]
        assert len(vs) == 1
        msg = vs[0]["message"]
        for name in ("custom.a", "custom.b", "custom.c"):
            assert name in msg
        threads = {s["thread"] for s in vs[0]["stacks"]}
        assert {"t-ab", "t-bc"} <= threads  # prior edges' stacks included

    def test_no_false_cycle_on_diamond(self):
        a = RankedLock("custom.a", rank=None)
        b = RankedLock("custom.b", rank=None)
        c = RankedLock("custom.c", rank=None)
        for outer, inner in ((a, b), (a, c), (b, c)):
            with outer:
                with inner:
                    pass
        assert lockrank.violations() == []


class TestAbbaRegression:
    def test_abba_deadlock_caught_without_hanging(self):
        """Two threads take A/B in opposite orders with real contention.
        In raise mode the second order raises BEFORE blocking, so the
        would-be deadlock terminates with a report instead of hanging."""
        lockrank.set_raise(True)
        a = RankedLock("mempool.wal")  # rank 48
        b = RankedLock("mempool.counter")  # rank 52
        a_held = threading.Event()
        release_a = threading.Event()
        outcomes = {}

        def legal():
            with a:
                a_held.set()
                release_a.wait(5)  # hold A while the bad thread runs
                with b:
                    outcomes["legal"] = "ok"

        def inverted():
            a_held.wait(5)
            b.acquire()  # rank 52 first...
            try:
                try:
                    a.acquire()  # ...then 48: raises pre-block
                    a.release()
                    outcomes["inverted"] = "acquired"
                except LockRankViolation:
                    outcomes["inverted"] = "caught"
            finally:
                b.release()
                release_a.set()

        t1 = threading.Thread(target=legal, name="abba-legal")
        t2 = threading.Thread(target=inverted, name="abba-inverted")
        t1.start()
        t2.start()
        t1.join(10)
        t2.join(10)
        assert not t1.is_alive() and not t2.is_alive(), "ABBA test wedged"
        assert outcomes == {"legal": "ok", "inverted": "caught"}
        assert lockrank.drain()  # the violation was also recorded


class TestConditionIntegration:
    def test_condition_wait_notify_roundtrip(self):
        cond = threading.Condition(ranked_lock("mempool.avail"))
        hits = []

        def waiter():
            with cond:
                while not hits:
                    if not cond.wait(5):
                        return
            hits.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            hits.append("set")
            cond.notify_all()
        t.join(5)
        assert not t.is_alive()
        assert hits == ["set", "woke"]
        assert lockrank.violations() == []

    def test_condition_lock_participates_in_ranking(self):
        cond_lock = ranked_lock("mempool.avail")  # rank 30
        lane = ranked_rlock("mempool.lane")  # rank 40
        cond = threading.Condition(cond_lock)
        with cond:
            with lane:  # avail -> lane: the documented get_after order
                pass
        assert lockrank.violations() == []
        with lane:
            # tmlint: disable=L001 -- deliberate inversion: this test asserts the runtime sanitizer flags it
            with cond:  # lane -> avail: the forbidden direction
                pass
        assert any(
            v["kind"] == "rank_inversion" for v in lockrank.drain()
        )


class TestDisabledPassThrough:
    def test_factories_return_plain_locks_when_disabled(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_LOCKRANK", "0")
        lock = ranked_lock("mempool.wal")
        rlock = ranked_rlock("mempool.lane")
        assert type(lock) is type(threading.Lock())
        assert "RLock" in type(rlock).__name__
        assert not isinstance(lock, RankedLock)
        # misuse with plain locks records nothing
        hi = ranked_lock("mempool.counter")
        with hi:
            # tmlint: disable=L001 -- deliberate inversion: proves the disabled factories record nothing
            with lock:
                pass
        assert lockrank.violations() == []

    def test_factories_instrument_when_enabled(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_LOCKRANK", "1")
        assert isinstance(ranked_lock("mempool.wal"), RankedLock)
        assert isinstance(ranked_rlock("mempool.lane"), RankedRLock)


class TestMempoolAcceptance:
    """Acceptance: a deliberate inversion injected against a REAL
    mempool under concurrent admission traffic is detected and reported
    with both threads' stacks — and nothing deadlocks (nemesis-style:
    contention is real, timing is controlled)."""

    def test_injected_inversion_under_live_admissions(self):
        from tendermint_tpu.abci.apps import KVStoreApp
        from tendermint_tpu.abci.client import local_client_creator
        from tendermint_tpu.mempool.mempool import Mempool

        mp = Mempool(
            local_client_creator(KVStoreApp())().mempool,
            lanes=2,
            ingress_batch=False,
            signed_txs=False,
        )
        if not isinstance(mp._wal_lock, RankedLock):
            pytest.skip("lockrank disabled in this environment")
        stop = threading.Event()

        def traffic():
            i = 0
            while not stop.is_set():
                mp.check_tx(b"k%d=v" % i)  # lane -> wal -> counter (legal)
                i += 1

        t = threading.Thread(target=traffic, name="admission-traffic")
        t.start()
        time.sleep(0.05)
        # the inversion: counter (52) held while taking a lane (40). The
        # sanitizer judges the order at the attempt, before it waits, so
        # the attempt is bounded: when the admission thread holds that
        # lane and wants the counter this is a real ABBA, and an
        # unbounded wait here wedged the whole worker
        lane_lock = mp._lanes[0].lock
        with mp._counter_lock:
            if lane_lock.acquire(timeout=0.2):
                lane_lock.release()
        stop.set()
        t.join(10)
        assert not t.is_alive(), "admission thread wedged"
        vs = [
            v
            for v in lockrank.drain()
            if v["kind"] == "rank_inversion"
            and "mempool.lane" in v["message"]
        ]
        assert vs, "injected inversion not detected"
        report = lockrank.render_violation(vs[0])
        assert "mempool.counter" in report
        # both sides: this test's stack plus the legal-order edge stack
        # recorded from the admission thread
        assert "test_injected_inversion_under_live_admissions" in report
        assert "admission-traffic" in report
        # the pool still works after the report
        res = mp.check_tx(b"post=ok")
        assert res.is_ok
