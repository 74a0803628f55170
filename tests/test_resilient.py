"""Fault-tolerant device dispatch: circuit breaker + resilient services.

The degradation contract under test (services/resilient.py): a sick
device backend must cost at most `threshold` failed dispatches before
every caller transparently runs on the host fallback; a recovered
device must be re-adopted after one successful probe; verdicts/roots
must be correct in every state.
"""

from __future__ import annotations

import numpy as np
import pytest

from tendermint_tpu.services.hasher import TreeHasher
from tendermint_tpu.services.resilient import (
    ResilientTreeHasher,
    ResilientVerifier,
)
from tendermint_tpu.services.verifier import BatchVerifier, HostBatchVerifier
from tendermint_tpu.utils import fail
from tendermint_tpu.utils.circuit import CLOSED, HALF_OPEN, OPEN, CircuitBreaker

from tests.helpers import det_priv_keys


@pytest.fixture(autouse=True)
def _clean_faults():
    fail.clear_device_faults()
    yield
    fail.clear_device_faults()


def _triples(n, corrupt=()):
    keys = det_priv_keys(n)
    out = []
    for i, k in enumerate(keys):
        msg = bytes([i]) * 8
        sig = k.sign(msg)
        if i in corrupt:
            sig = sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]
        out.append((k.pub_key.data, msg, sig))
    return out


class _FlakyVerifier(BatchVerifier):
    """Programmable primary: fails while `broken`, else verifies on host."""

    def __init__(self):
        super().__init__()
        self.broken = False
        self.calls = 0
        self._host = HostBatchVerifier()

    def verify_batch(self, triples):
        self.calls += 1
        if self.broken:
            raise RuntimeError("device exploded")
        return self._host.verify_batch(triples)


class TestCircuitBreaker:
    def test_trips_after_threshold_consecutive_failures(self):
        clock = [0.0]
        br = CircuitBreaker(failure_threshold=3, reset_timeout_s=5.0, clock=lambda: clock[0])
        assert br.state == CLOSED
        for _ in range(2):
            br.record_failure()
        assert br.state == CLOSED  # 2 < threshold
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow()

    def test_success_resets_failure_streak(self):
        br = CircuitBreaker(failure_threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == CLOSED  # never 2 consecutive

    def test_half_open_admits_one_probe(self):
        clock = [0.0]
        br = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0, clock=lambda: clock[0])
        br.record_failure()
        assert br.state == OPEN and not br.allow()
        clock[0] = 5.1
        assert br.state == HALF_OPEN
        assert br.allow()  # the probe
        assert not br.allow()  # concurrent caller blocked while probe in flight
        br.record_success()
        assert br.state == CLOSED and br.allow()

    def test_failed_probe_reopens_for_full_window(self):
        clock = [0.0]
        br = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0, clock=lambda: clock[0])
        br.record_failure()
        clock[0] = 5.1
        assert br.allow()
        br.record_failure()  # probe failed
        assert br.state == OPEN
        clock[0] = 10.0  # 4.9s after reopen: still open
        assert not br.allow()
        clock[0] = 10.3
        assert br.allow()

    def test_state_change_callback_and_snapshot(self):
        transitions = []
        br = CircuitBreaker(
            failure_threshold=1,
            reset_timeout_s=0.0,
            on_state_change=lambda old, new: transitions.append((old, new)),
        )
        br.record_failure()
        br.allow()
        br.record_success()
        assert (CLOSED, OPEN) in transitions
        assert transitions[-1][1] == CLOSED
        snap = br.snapshot()
        assert snap["times_opened"] == 1
        assert snap["total_failures"] == 1


class TestResilientVerifier:
    def _rv(self, primary, threshold=2, reset_s=0.05):
        return ResilientVerifier(
            primary,
            breaker=CircuitBreaker(failure_threshold=threshold, reset_timeout_s=reset_s),
            max_retries=0,
        )

    def test_verdicts_correct_in_every_state(self):
        primary = _FlakyVerifier()
        rv = self._rv(primary)
        triples = _triples(4, corrupt=(2,))
        expect = [True, True, False, True]

        assert list(rv.verify_batch(triples)) == expect  # healthy
        primary.broken = True
        assert list(rv.verify_batch(triples)) == expect  # fallback, breaker counting
        assert list(rv.verify_batch(triples)) == expect
        assert rv.breaker.state == OPEN
        assert rv.degraded
        calls_when_open = primary.calls
        assert list(rv.verify_batch(triples)) == expect  # open: primary not touched
        assert primary.calls == calls_when_open

    def test_breaker_recloses_after_recovery(self):
        import time

        primary = _FlakyVerifier()
        rv = self._rv(primary)
        triples = _triples(2)
        primary.broken = True
        rv.verify_batch(triples)
        rv.verify_batch(triples)
        assert rv.breaker.state == OPEN
        primary.broken = False
        time.sleep(0.06)  # reset window elapses -> half-open probe
        assert list(rv.verify_batch(triples)) == [True, True]
        assert rv.breaker.state == CLOSED
        assert not rv.degraded

    def test_env_fault_injection_counts_down(self):
        primary = _FlakyVerifier()
        rv = self._rv(primary, threshold=5)
        fail.set_device_fault("verify", count=2)
        triples = _triples(2)
        before = primary.calls
        rv.verify_batch(triples)  # injected fault -> fallback
        rv.verify_batch(triples)  # injected fault -> fallback
        assert primary.calls == before  # primary never reached
        assert list(rv.verify_batch(triples)) == [True, True]  # budget spent
        assert primary.calls == before + 1

    def test_verify_commits_host_fallback_shape(self):
        primary = _FlakyVerifier()  # no verify_commits attribute
        rv = self._rv(primary)
        keys = det_priv_keys(3)
        pubs = [k.pub_key.data for k in keys]
        msgs = [bytes([i]) for i in range(3)]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]
        commits = [
            (msgs, sigs),
            ([msgs[0], None, msgs[2]], [sigs[0], None, sigs[2]]),
        ]
        grid = rv.verify_commits(pubs, commits)
        assert grid.shape == (2, 3)
        assert grid[0].tolist() == [True, True, True]
        assert grid[1].tolist() == [True, False, True]

    def test_dispatch_timeout_counts_as_failure(self):
        class Hanging(BatchVerifier):
            def verify_batch(self, triples):
                import time

                time.sleep(5)
                return np.ones(len(triples), dtype=bool)

        rv = ResilientVerifier(
            Hanging(),
            breaker=CircuitBreaker(failure_threshold=1, reset_timeout_s=60),
            max_retries=0,
            dispatch_timeout_s=0.1,
        )
        triples = _triples(1)
        assert list(rv.verify_batch(triples)) == [True]  # host answered
        assert rv.breaker.state == OPEN


class TestResilientTreeHasher:
    class _FlakyHasher(TreeHasher):
        def __init__(self):
            super().__init__(backend="host")
            self.broken = False

        def root_from_items(self, items):
            if self.broken:
                raise RuntimeError("device tree exploded")
            return super().root_from_items(items)

        def root_from_hashes(self, hashes):
            if self.broken:
                raise RuntimeError("device tree exploded")
            return super().root_from_hashes(hashes)

    def test_roots_identical_across_degradation(self):
        primary = self._FlakyHasher()
        rh = ResilientTreeHasher(
            primary,
            breaker=CircuitBreaker(failure_threshold=1, reset_timeout_s=60),
            max_retries=0,
        )
        items = [bytes([i]) * 10 for i in range(7)]
        healthy = rh.root_from_items(items)
        primary.broken = True
        degraded = rh.root_from_items(items)
        assert healthy == degraded
        assert rh.breaker.state == OPEN
        host = TreeHasher(backend="host")
        assert degraded == host.root_from_items(items)

    def test_hash_fault_injection_env_spec(self):
        rh = ResilientTreeHasher(
            self._FlakyHasher(),
            breaker=CircuitBreaker(failure_threshold=1, reset_timeout_s=60),
            max_retries=0,
        )
        fail.set_device_fault("hash")
        items = [b"a", b"b", b"c"]
        assert rh.root_from_items(items) == TreeHasher(backend="host").root_from_items(items)
        assert rh.breaker.state == OPEN


class TestFaultSpecParsing:
    def test_env_spec_kinds_and_budgets(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_DEVICE_FAIL", "verify:1,hash")
        fail.clear_device_faults()
        monkeypatch.setattr(fail, "_device_faults", None)
        with pytest.raises(fail.InjectedDeviceFault):
            fail.device_fail_point("verify")
        fail.device_fail_point("verify")  # budget of 1 spent: no raise
        with pytest.raises(fail.InjectedDeviceFault):
            fail.device_fail_point("hash")  # unbounded
        with pytest.raises(fail.InjectedDeviceFault):
            fail.device_fail_point("hash")

    def test_all_kind_hits_everything(self):
        fail.set_device_fault("all")
        for kind in ("verify", "hash"):
            with pytest.raises(fail.InjectedDeviceFault):
                fail.device_fail_point(kind)
        fail.clear_device_faults()
        fail.device_fail_point("verify")  # cleared: silent

    def test_default_factories_wrap_when_armed(self, monkeypatch):
        from tendermint_tpu.services import hasher as hasher_mod
        from tendermint_tpu.services import verifier as verifier_mod

        from tendermint_tpu.services.batcher import CoalescingVerifier

        fail.set_device_fault("verify")
        monkeypatch.setattr(verifier_mod, "_DEFAULT", None)
        v = verifier_mod.default_verifier()
        # the coalescing facade is always outermost; the resilient wrap
        # appears underneath it when faults are armed
        assert isinstance(v, CoalescingVerifier)
        assert isinstance(v.inner, ResilientVerifier)
        h = hasher_mod.auto_hasher()
        assert isinstance(h, ResilientTreeHasher)
        monkeypatch.setattr(verifier_mod, "_DEFAULT", None)
        fail.clear_device_faults()
        v2 = verifier_mod.default_verifier()
        assert isinstance(v2, CoalescingVerifier)
        assert isinstance(v2.inner, HostBatchVerifier)  # CPU, no faults armed


class TestTableBuildBreaker:
    """The table-CONSTRUCTION path behind its own breaker (ROADMAP open
    item): a build fault must degrade — small sets host-build their
    tables, large sets answer with host crypto — never raise out of
    verify_commits. (The device verify kernel itself is exercised in the
    kernel-marked suites; these tests stay on the degradation paths.)"""

    def _commit_shape(self, n, corrupt=()):
        triples = _triples(n, corrupt=corrupt)
        pubs = [t[0] for t in triples]
        return pubs, [([t[1] for t in triples], [t[2] for t in triples])]

    def test_build_fault_host_builds_small_sets(self):
        from tendermint_tpu.services.verifier import TableBatchVerifier

        tv = TableBatchVerifier(min_device_batch=1)
        pubs, _ = self._commit_shape(3)
        fail.set_device_fault("tables", 1)
        tables, ok, how, keys_new = tv._build_tables(tuple(pubs))  # degrades, no raise
        assert ok.all() and tables is not None
        assert (how, keys_new) == ("host_build", 3)
        snap = tv._build_breaker.snapshot()
        assert snap["total_failures"] == 1
        assert snap["state"] == CLOSED  # one fault < threshold

    def test_build_fault_on_large_set_degrades_to_host_crypto(self):
        from tendermint_tpu.services.verifier import TableBatchVerifier

        tv = TableBatchVerifier(min_device_batch=1)
        tv.MAX_INCREMENTAL_KEYS = 0  # every set counts as "too large"
        fail.set_device_fault("tables")  # forever, until cleared
        pubs, commits = self._commit_shape(3, corrupt=(1,))
        out = tv.verify_commits(pubs, commits)  # must not raise
        assert out.shape == (1, 3)
        assert bool(out[0, 0]) and not bool(out[0, 1]) and bool(out[0, 2])

    def test_open_build_breaker_stops_dialing_device_builds(self):
        from tendermint_tpu.services.verifier import (
            TableBatchVerifier,
            TableBuildError,
        )

        tv = TableBatchVerifier(min_device_batch=1)
        tv.MAX_INCREMENTAL_KEYS = 0
        tv._build_breaker = CircuitBreaker(
            failure_threshold=2, reset_timeout_s=60, name=None
        )
        fail.set_device_fault("tables")
        pubs, commits = self._commit_shape(2)
        tv.verify_commits(pubs, commits)
        tv.verify_commits(pubs, commits)
        assert tv._build_breaker.state == OPEN
        fail.clear_device_faults()
        # breaker OPEN: the device builder is not dialed at all, the
        # degradation answers immediately
        with pytest.raises(TableBuildError):
            tv._build_tables(tuple(pubs))
        out = tv.verify_commits(pubs, commits)  # still answers via host
        assert out.all()

    def test_table_build_telemetry_counters(self):
        from tendermint_tpu.services.verifier import TableBatchVerifier
        from tendermint_tpu.telemetry import REGISTRY

        tv = TableBatchVerifier(min_device_batch=1)
        before = REGISTRY.counter_value(
            "tendermint_verify_table_cache_total", event="host_build"
        )
        pubs, _ = self._commit_shape(2)
        fail.set_device_fault("tables", 1)
        tv._build_tables(tuple(pubs))
        assert (
            REGISTRY.counter_value(
                "tendermint_verify_table_cache_total", event="host_build"
            )
            == before + 1
        )
