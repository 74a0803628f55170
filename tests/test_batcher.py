"""Cross-subsystem verify coalescer + verified-signature dedup cache
(`services/batcher.py`).

Covers the PR's acceptance surface: negatives are never cached (a
forged sig for a cached-positive triple's pubkey is still rejected),
cache keys cannot alias across field boundaries (byte-boundary fuzz),
round-robin fairness under a starving consumer, all three flush reasons
(window/size/barrier), per-consumer drain-order preservation with
device faults mid-coalesce, dedup-cache concurrency, and the nemesis
assertion that cache hits never mask a breaker-faulted launch. All
CPU-safe, no kernel marks.
"""

from __future__ import annotations

import random
import threading
import time

import numpy as np
import pytest

from tendermint_tpu.crypto.keys import gen_priv_key
from tendermint_tpu.services.batcher import (
    CoalescingVerifier,
    VerifiedSigCache,
    VerifyCoalescer,
    consumer_kwargs,
)
from tendermint_tpu.services.verifier import BatchVerifier, HostBatchVerifier
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.utils import fail


@pytest.fixture(autouse=True)
def _clean_faults():
    fail.clear_device_faults()
    yield
    fail.clear_device_faults()


def _triples(n, salt=b"", start=0):
    out = []
    for i in range(start, start + n):
        priv = gen_priv_key(bytes([i % 251 + 1]) * 32)
        msg = b"batcher-msg-%d-" % i + salt
        out.append((priv.pub_key.data, msg, priv.sign(msg)))
    return out


def _counter(name, **labels):
    return REGISTRY.counter_value(name, **labels)


class _CountingVerifier(BatchVerifier):
    """Host verifier that records every underlying verify call."""

    def __init__(self):
        super().__init__()
        self._host = HostBatchVerifier()
        self.calls: list[int] = []
        self.lock = threading.Lock()

    def verify_batch(self, triples):
        with self.lock:
            self.calls.append(len(triples))
        return self._host.verify_batch(triples)


class TestVerifiedSigCache:
    def test_positive_only_contract_and_hit_metrics(self):
        cache = VerifiedSigCache(capacity=64)
        (pk, msg, sig) = _triples(1)[0]
        key = VerifiedSigCache.key(pk, msg, sig)
        h0 = _counter("tendermint_verify_cache_hits_total")
        m0 = _counter("tendermint_verify_cache_misses_total")
        assert not cache.hit(key)
        cache.add(key)
        assert cache.hit(key)
        assert _counter("tendermint_verify_cache_hits_total") == h0 + 1
        assert _counter("tendermint_verify_cache_misses_total") == m0 + 1

    def test_lru_eviction_bounded_and_counted(self):
        cache = VerifiedSigCache(capacity=VerifiedSigCache.SHARDS * 4)
        e0 = _counter("tendermint_verify_cache_evictions_total")
        for i in range(VerifiedSigCache.SHARDS * 16):
            cache.add(VerifiedSigCache.key(b"\x01" * 32, b"m%d" % i, b"\x02" * 64))
        assert len(cache) <= cache.capacity
        assert _counter("tendermint_verify_cache_evictions_total") > e0

    def test_key_never_aliases_across_field_boundaries(self):
        """Property fuzz: re-split the same concatenated bytes at every
        boundary — distinct (pubkey, msg, sig) splits must key apart
        (the raw-concat key would collide on ALL of these)."""
        rng = random.Random(0xBEEF)
        for _trial in range(50):
            blob = bytes(rng.getrandbits(8) for _ in range(rng.randint(3, 48)))
            keys = set()
            splits = 0
            for a in range(len(blob) + 1):
                for b in range(a, len(blob) + 1):
                    keys.add(VerifiedSigCache.key(blob[:a], blob[a:b], blob[b:]))
                    splits += 1
            assert len(keys) == splits

    def test_shifted_msg_vs_pubkey_boundary(self):
        pk, msg = b"\xaa" * 32, b"hello-world"
        sig = b"\x05" * 64
        k1 = VerifiedSigCache.key(pk, msg, sig)
        k2 = VerifiedSigCache.key(pk + msg[:1], msg[1:], sig)
        k3 = VerifiedSigCache.key(pk, msg + sig[:1], sig[1:])
        assert len({k1, k2, k3}) == 3

    def test_concurrent_add_and_hit(self):
        cache = VerifiedSigCache(capacity=1024)
        keys = [
            VerifiedSigCache.key(b"\x07" * 32, b"c%d" % i, b"\x01" * 64)
            for i in range(256)
        ]
        errors = []

        def worker(seed):
            rng = random.Random(seed)
            try:
                for _ in range(500):
                    k = keys[rng.randrange(len(keys))]
                    if rng.random() < 0.5:
                        cache.add(k)
                    else:
                        cache.hit(k)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(cache) <= cache.capacity


class TestNegativeVerdictsNeverCached:
    def test_forged_sig_for_cached_positive_pubkey_rejected(self):
        v = CoalescingVerifier(HostBatchVerifier(), window_s=0.001)
        try:
            (pk, msg, sig) = _triples(1, salt=b"neg")[0]
            assert v.verify_batch([(pk, msg, sig)]).all()
            # the genuine triple is now cached-positive; forging a sig
            # for the SAME pubkey (same and different msg) must still
            # reject — the cache keys on the full triple and negatives
            # never enter
            forged = b"\xff" * 64
            assert not v.verify_batch([(pk, msg, forged)]).any()
            assert not v.verify_batch([(pk, b"other-msg", forged)]).any()
            assert not v.verify_batch_async(
                [(pk, msg, forged)], consumer="rpc"
            ).result(timeout=10).any()
            # and the failures did not poison the cache
            assert VerifiedSigCache.key(pk, msg, forged) not in v.cache
            assert v.verify_batch([(pk, msg, sig)]).all()
        finally:
            v.close()

    def test_failed_lane_reverifies_every_time(self):
        counting = _CountingVerifier()
        v = CoalescingVerifier(counting, window_s=0.001)
        try:
            (pk, msg, _sig) = _triples(1, salt=b"re")[0]
            bad = (pk, msg, b"\x01" * 64)
            for _ in range(3):
                assert not v.verify_batch([bad]).any()
            # all three attempts reached the backend — nothing served
            # the forged triple from cache
            assert len(counting.calls) == 3
        finally:
            v.close()


class TestFlushReasons:
    @staticmethod
    def _wait_done(*handles, timeout=10.0):
        """Wait for flush WITHOUT joining — result() on an unflushed
        request would trigger a barrier and mask the reason under test."""
        deadline = time.monotonic() + timeout
        while not all(h.done() for h in handles):
            if time.monotonic() > deadline:
                raise TimeoutError("coalesced handles never resolved")
            time.sleep(0.002)

    def test_window_flush_merges_concurrent_consumers(self):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=0.05, max_batch=4096)
        try:
            f0 = _counter("tendermint_batcher_flush_total", reason="window")
            h1 = v.submit(_triples(3, salt=b"w1"), consumer="consensus")
            h2 = v.submit(_triples(3, salt=b"w2", start=100), consumer="fastsync")
            # neither consumer joins: the window timer must flush both
            # as ONE merged launch
            self._wait_done(h1, h2)
            assert h1.result(timeout=10).all()
            assert h2.result(timeout=10).all()
            assert counting.calls == [6]
            assert (
                _counter("tendermint_batcher_flush_total", reason="window")
                == f0 + 1
            )
        finally:
            v.close()

    def test_size_flush_fires_before_window(self):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=10.0, max_batch=4)
        try:
            s0 = _counter("tendermint_batcher_flush_total", reason="size")
            h = v.submit(_triples(4, salt=b"sz"), consumer="consensus")
            self._wait_done(h)  # resolved long before the 10 s window
            assert h.result(timeout=10).all()
            assert (
                _counter("tendermint_batcher_flush_total", reason="size")
                == s0 + 1
            )
        finally:
            v.close()

    def test_barrier_flush_on_early_join(self):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=30.0, max_batch=4096)
        try:
            b0 = _counter("tendermint_batcher_flush_total", reason="barrier")
            t0 = time.perf_counter()
            h = v.submit(_triples(2, salt=b"bar"), consumer="statesync")
            assert h.result(timeout=10).all()
            assert time.perf_counter() - t0 < 5.0  # did not wait the window
            assert (
                _counter("tendermint_batcher_flush_total", reason="barrier")
                == b0 + 1
            )
        finally:
            v.close()

    def test_coalesce_factor_and_wait_telemetry_move(self):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=0.05)
        try:
            fam = REGISTRY.get("tendermint_batcher_coalesce_factor")
            c0 = fam.value["count"]
            h1 = v.submit(_triples(1, salt=b"cf1"), consumer="consensus")
            h2 = v.submit(_triples(1, salt=b"cf2", start=50), consumer="rpc")
            h1.result(timeout=10)
            h2.result(timeout=10)
            snap = fam.value
            assert snap["count"] > c0
            wait = REGISTRY.get("tendermint_batcher_wait_seconds")
            assert wait.labels(consumer="consensus").value["count"] > 0
        finally:
            v.close()


class TestFairness:
    def test_starving_consumer_rides_the_first_take(self, monkeypatch):
        """A hot consumer with a deep backlog must not starve a
        one-request consumer: the round-robin take puts the starving
        request into the very next merged launch, not behind the whole
        backlog. Exercised at the `_take_locked` level with the flusher
        parked so the take composition is deterministic."""
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=30.0, max_batch=8)
        monkeypatch.setattr(v, "_ensure_threads", lambda: None)
        hot = [
            v.submit(_triples(4, salt=b"hot%d" % i, start=10 * i), "fastsync")
            for i in range(6)
        ]
        starving = v.submit(_triples(1, salt=b"starve", start=200), "rpc")
        with v._cond:
            first = v._take_locked()
        consumers = [r.consumer for r in first]
        assert "rpc" in consumers, f"starving consumer not in first take: {consumers}"
        # one-per-consumer cycles: hot[0], starving, hot[1] fill the cap
        assert consumers == ["fastsync", "rpc", "fastsync"]
        # per-consumer FIFO: the hot requests taken are the OLDEST two
        assert first[0] is hot[0]._req and first[2] is hot[1]._req
        v.close()

    def test_rotation_does_not_pin_the_first_consumer(self, monkeypatch):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=30.0, max_batch=1)
        monkeypatch.setattr(v, "_ensure_threads", lambda: None)
        v.submit(_triples(1, salt=b"a"), "consensus")
        v.submit(_triples(1, salt=b"b", start=50), "rpc")
        v.submit(_triples(1, salt=b"c", start=60), "consensus")
        v.submit(_triples(1, salt=b"d", start=70), "rpc")
        takes = []
        for _ in range(4):
            with v._cond:
                takes.extend(r.consumer for r in v._take_locked())
        # both consumers got served in the first two takes (rotation),
        # not consensus twice then rpc twice
        assert set(takes[:2]) == {"consensus", "rpc"}
        v.close()

    def test_per_consumer_fifo_order_is_preserved(self):
        counting = _CountingVerifier()
        v = VerifyCoalescer(counting, cache=None, window_s=30.0, max_batch=3)
        try:
            batches = [_triples(2, salt=b"fifo%d" % i, start=20 * i) for i in range(4)]
            handles = [v.submit(b, consumer="consensus") for b in batches]
            v.request_barrier()
            # joining in submission order always succeeds (no handle
            # depends on a later flush than a successor's)
            for h in handles:
                assert h.result(timeout=10).all()
        finally:
            v.close()


class TestFaultsMidCoalesce:
    def test_drain_order_with_breaker_faults(self):
        """Faults injected mid-coalesce degrade through the resilient
        handle INSIDE the merged launch: every sub-handle still resolves
        to host-truth verdicts, in per-consumer submission order."""
        from tendermint_tpu.services.resilient import ResilientVerifier
        from tendermint_tpu.services.verifier import DeviceBatchVerifier

        # default min_device_batch keeps post-fault launches on the host
        # short-circuit (an actual XLA:CPU curve compile has no place in
        # tier-1); the injected faults fire BEFORE the backend runs
        inner = ResilientVerifier(DeviceBatchVerifier())
        v = CoalescingVerifier(inner, cache_size=0, window_s=0.005)
        try:
            fail.set_device_fault("verify", 2)  # first two launches fault
            good = _triples(3, salt=b"fault")
            bad = [(good[0][0], good[0][1], b"\x09" * 64)]
            handles = []
            for i in range(4):
                handles.append(
                    v.verify_batch_async(good, consumer="consensus")
                )
                handles.append(v.verify_batch_async(bad, consumer="rpc"))
            for i, h in enumerate(handles):
                out = h.result(timeout=20)
                if i % 2 == 0:
                    assert out.all(), f"batch {i} lost verdicts to the fault"
                else:
                    assert not out.any(), f"forged batch {i} passed"
        finally:
            v.close()

    def test_cache_hits_never_mask_a_breaker_faulted_launch(self):
        """Nemesis assertion: a proven-positive cache entry must come
        from a REAL verification (device or host fallback), and cache
        hits must never turn a faulted launch into a false positive for
        novel triples sharing the batch."""
        from tendermint_tpu.services.resilient import ResilientVerifier
        from tendermint_tpu.services.verifier import DeviceBatchVerifier

        inner = ResilientVerifier(DeviceBatchVerifier())
        v = CoalescingVerifier(inner, window_s=0.005)
        try:
            fb0 = _counter(
                "tendermint_device_fallback_calls_total", kind="verify"
            )
            fail.set_device_fault("verify")  # every device launch faults
            good = _triples(2, salt=b"mask")
            forged = (good[0][0], good[0][1], b"\x0c" * 64)
            # first pass: faulted launch -> host fallback proves the
            # positives; those (and only those) enter the cache
            assert v.verify_batch_async(good, consumer="consensus").result(
                timeout=20
            ).all()
            assert (
                _counter(
                    "tendermint_device_fallback_calls_total", kind="verify"
                )
                > fb0
            )
            # second pass mixes cached positives with a forged triple:
            # the cached lanes answer True, the forged lane re-verifies
            # (still under fault -> host fallback) and must reject
            out = v.verify_batch_async(
                good + [forged], consumer="consensus"
            ).result(timeout=20)
            assert out[0] and out[1] and not out[2]
            assert VerifiedSigCache.key(*forged) not in v.cache
        finally:
            v.close()


class TestCommitGridDedup:
    def _commit_fixture(self, n=4):
        triples = _triples(n, salt=b"grid")
        pubs = [t[0] for t in triples]
        commits = [([t[1] for t in triples], [t[2] for t in triples])]
        return pubs, commits, triples

    def test_cached_lanes_skip_the_backend(self):
        counting = _CountingVerifier()
        v = CoalescingVerifier(counting, window_s=0.001)
        try:
            pubs, commits, triples = self._commit_fixture()
            assert v.verify_batch(triples).all()  # gossip pass: populate
            calls_before = len(counting.calls)
            grid = v.verify_commits(pubs, commits)  # commit pass
            assert grid.all()
            # every lane was cached -> no backend call for the grid
            assert len(counting.calls) == calls_before
        finally:
            v.close()

    def test_partial_cache_sends_only_novel_lanes(self):
        counting = _CountingVerifier()
        v = CoalescingVerifier(counting, window_s=0.001)
        try:
            pubs, commits, triples = self._commit_fixture()
            assert v.verify_batch(triples[:2]).all()  # half cached
            grid = v.verify_commits_async(pubs, commits, consumer="fastsync")
            assert grid.result(timeout=10).all()
            # the grid launch carried exactly the two novel lanes
            assert counting.calls[-1] == 2
        finally:
            v.close()

    def test_forged_lane_rejected_despite_cached_neighbors(self):
        v = CoalescingVerifier(HostBatchVerifier(), window_s=0.001)
        try:
            pubs, commits, triples = self._commit_fixture()
            assert v.verify_batch(triples).all()
            msgs, sigs = [list(x) for x in commits[0]]
            sigs[1] = b"\x0d" * 64  # forge one lane
            grid = v.verify_commits(pubs, [(msgs, sigs)])
            assert grid[0, 0] and grid[0, 2] and grid[0, 3]
            assert not grid[0, 1]
        finally:
            v.close()


class TestValidatorSetRouting:
    def _chain_fixture(self, n_vals=4):
        from tendermint_tpu.testing.nemesis import make_genesis
        from tendermint_tpu.types import BlockID
        from tendermint_tpu.types.part_set import PartSetHeader
        from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote
        from tendermint_tpu.types.vote_set import VoteSet

        genesis, privs = make_genesis(n_vals, chain_id="batcher-vs")
        valset = genesis.validator_set()
        block_id = BlockID(b"\x11" * 20, PartSetHeader(total=1, hash=b"\x22" * 20))
        vote_set = VoteSet("batcher-vs", 5, 0, VOTE_TYPE_PRECOMMIT, valset)
        for i, priv in enumerate(privs):
            vote = Vote(
                validator_address=priv.address,
                validator_index=i,
                height=5,
                round=0,
                timestamp=1,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            vote_set.add_vote(priv.sign_vote("batcher-vs", vote))
        return valset, block_id, vote_set.make_commit()

    def test_verify_commit_batched_through_coalescer(self):
        valset, block_id, commit = self._chain_fixture()
        v = CoalescingVerifier(HostBatchVerifier(), window_s=0.001)
        try:
            valset.verify_commit_batched(
                "batcher-vs",
                [(block_id, 5, commit)],
                verifier=v,
                consumer="statesync",
            )
            # second walk over the same commit is answered by the cache
            h0 = _counter("tendermint_verify_cache_hits_total")
            valset.verify_commit_batched(
                "batcher-vs", [(block_id, 5, commit)], verifier=v,
                consumer="rpc",
            )
            assert _counter("tendermint_verify_cache_hits_total") >= h0 + 4
        finally:
            v.close()

    def test_certifier_walk_hits_the_cache(self):
        """The light-client/statesync certifier re-walk: certifying the
        same FullCommit twice verifies its signatures once."""
        from tendermint_tpu.certifiers.certifier import StaticCertifier

        valset, block_id, commit = self._chain_fixture()
        v = CoalescingVerifier(HostBatchVerifier(), window_s=0.001)
        try:
            entries = [(block_id, 5, commit)]
            cert = StaticCertifier("batcher-vs", valset, verifier=v)
            m0 = _counter("tendermint_verify_cache_misses_total")
            valset.verify_commit_batched(
                "batcher-vs", entries, verifier=v, consumer=cert.consumer
            )
            misses_first = (
                _counter("tendermint_verify_cache_misses_total") - m0
            )
            assert misses_first >= 4
            m1 = _counter("tendermint_verify_cache_misses_total")
            valset.verify_commit_batched(
                "batcher-vs", entries, verifier=v, consumer=cert.consumer
            )
            assert _counter("tendermint_verify_cache_misses_total") == m1
        finally:
            v.close()

    def test_consumer_kwargs_gate(self):
        v = CoalescingVerifier(HostBatchVerifier(), window_s=0.001)
        try:
            assert consumer_kwargs(v, "rpc") == {"consumer": "rpc"}

            class _Minimal:
                def verify_batch(self, triples):
                    return np.ones(len(triples), dtype=bool)

            assert consumer_kwargs(_Minimal(), "rpc") == {}
        finally:
            v.close()


class TestDedupConcurrency:
    def test_overlapping_submissions_from_many_threads(self):
        counting = _CountingVerifier()
        v = CoalescingVerifier(counting, window_s=0.002)
        try:
            shared = _triples(8, salt=b"conc")
            errors = []

            def worker(seed):
                rng = random.Random(seed)
                try:
                    for _ in range(20):
                        batch = rng.sample(shared, rng.randint(1, len(shared)))
                        out = v.verify_batch_async(
                            batch, consumer=f"c{seed % 4}"
                        ).result(timeout=20)
                        if not np.asarray(out).all():
                            errors.append(("verdict", batch))
                except Exception as e:
                    errors.append(("exc", e))

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
            # dedup engaged: far fewer triples reached the backend than
            # the ~720 requested (8 threads x 20 rounds x avg 4.5);
            # generous bound absorbs pre-cache concurrent first rounds
            assert sum(counting.calls) < 300
        finally:
            v.close()


class TestAdaptiveKnobs:
    def test_launch_apply_ratio_from_overlap_histogram(self):
        from tendermint_tpu.services.dispatch import (
            measured_launch_apply_ratio,
        )
        from tendermint_tpu.telemetry import metrics as _metrics

        _metrics.DISPATCH_OVERLAP.labels(queue="ratio-test").observe(0.5)
        r = measured_launch_apply_ratio("ratio-test")
        assert r == pytest.approx(1.0)
        assert measured_launch_apply_ratio("no-such-queue") is None
