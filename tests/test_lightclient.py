"""Light-client serving layer: bisection certifier, certified-commit
cache/store, 0x68 reactor, replica mode, forged-FullCommit attribution
(tendermint_tpu/lightclient/, PR 15 / ROADMAP item 1).
"""

import threading
import time

import pytest

from tendermint_tpu.certifiers.provider import MemProvider
from tendermint_tpu.db.fullcommit import FullCommitStore
from tendermint_tpu.db.kv import MemDB
from tendermint_tpu.lightclient import (
    BisectingCertifier,
    CertifiedCommitCache,
    extract_double_sign_evidence,
)
from tendermint_tpu.types.errors import (
    ErrNoSourceCommit,
    ErrTooMuchChange,
    ErrTrustExpired,
    ValidationError,
)

from tests.test_certifiers import _full_commit, _privs, _valset

CHAIN = "light-chain"


def _chain_source(heights, privs_for):
    """MemProvider of FullCommits: privs_for(h) -> priv list at h."""
    src = MemProvider()
    fcs = {}
    for h in heights:
        fcs[h] = _full_commit(h, privs_for(h))
        src.store_commit(fcs[h])
    return src, fcs


class TestFullCommitStore:
    def test_roundtrip_floor_exact_latest(self):
        store = FullCommitStore(MemDB())
        privs = _privs(range(1, 5))
        for h in (2, 5, 9):
            store.store_commit(_full_commit(h, privs))
        assert store.get_by_height(1) is None
        assert store.get_by_height(5).height() == 5
        assert store.get_by_height(8).height() == 5
        assert store.get_exact(5).height() == 5
        assert store.get_exact(6) is None
        assert store.latest_commit().height() == 9
        assert store.latest_height() == 9
        assert len(store) == 3

    def test_survives_reopen(self):
        db = MemDB()
        store = FullCommitStore(db)
        privs = _privs(range(1, 5))
        fc = _full_commit(12, privs)
        store.store_commit(fc)
        again = FullCommitStore(db)  # fresh index over the same DB
        got = again.get_by_height(100)
        assert got.height() == 12
        assert got.header.hash() == fc.header.hash()
        assert got.validators.hash() == fc.validators.hash()

    def test_prune_keeps_recent(self):
        store = FullCommitStore(MemDB())
        privs = _privs(range(1, 5))
        for h in range(1, 11):
            store.store_commit(_full_commit(h, privs))
        assert store.prune(3) == 7
        assert store.heights() == [8, 9, 10]
        assert store.get_by_height(7) is None
        assert store.get_by_height(9).height() == 9


class TestCertifiedCommitCache:
    def test_positives_only_surface(self):
        """The ONLY write path is put_certified/store_commit — there is
        no API to record a rejection, so a forged commit re-verifies on
        every offer (the VerifiedSigCache discipline)."""
        cache = CertifiedCommitCache()
        assert not hasattr(cache, "put_rejected")
        assert cache.get_exact(5) is None  # miss, nothing pinned
        privs = _privs(range(1, 5))
        cache.put_certified(_full_commit(5, privs))
        assert cache.get_exact(5).height() == 5
        assert cache.get_by_height(9).height() == 5
        assert cache.get_by_height(4) is None

    def test_eviction_oldest_first(self):
        cache = CertifiedCommitCache(capacity=3)
        privs = _privs(range(1, 5))
        for h in range(1, 6):
            cache.put_certified(_full_commit(h, privs))
        assert len(cache) == 3
        assert cache.get_exact(1) is None
        assert cache.get_exact(5).height() == 5

    def test_store_fallback_readmission_stays_evictable(self):
        """A store-backed hit re-admitted to the hot tier must re-enter
        the height index — otherwise the evictor (which only drops
        heights popped from the index) never sees it and shard dicts
        grow without bound under historical-read workloads."""
        store = FullCommitStore(MemDB())
        privs = _privs(range(1, 5))
        for h in range(1, 11):
            store.store_commit(_full_commit(h, privs))
        cache = CertifiedCommitCache(capacity=3, store=store)
        for h in range(1, 11):
            assert cache.get_exact(h).height() == h  # store fallback
        shard_entries = sum(len(entries) for _, entries in cache._shards)
        assert shard_entries <= 3
        assert len(cache) <= 3

    def test_write_through_store_and_warm_reload(self):
        db = MemDB()
        cache = CertifiedCommitCache(store=FullCommitStore(db))
        privs = _privs(range(1, 5))
        cache.put_certified(_full_commit(7, privs))
        # a fresh cache over the same DB reloads proven trust
        cache2 = CertifiedCommitCache(store=FullCommitStore(db))
        assert cache2.latest_height() == 7
        assert cache2.get_exact(7).height() == 7
        stats = cache2.stats()
        assert stats["entries"] == 1 and stats["latest_height"] == 7


class TestBisectionMath:
    def test_stable_valset_single_round(self):
        """A 256-height jump over an unchanged valset is ONE combined
        round and at most a couple dozen commit verifies (the probe
        ladder rides a single launch) — the acceptance criterion's
        shape."""
        privs = _privs(range(1, 5))
        src, fcs = _chain_source((1, 64, 128, 200, 256), lambda h: privs)
        cert = BisectingCertifier(
            CHAIN, seed=fcs[1], trusted=MemProvider(), source=src
        )
        cert.verify_to_height(256)
        assert cert.last_height == 256
        assert cert.last_walk_rounds == 1  # ONE batched launch
        assert cert.last_walk_verifies <= 36  # "dozens", not 256 * 4

    def test_rotating_chain_bisects(self):
        """Heights 1..4 rotate one validator each (the inquirer test's
        chain): a 1->4 jump changes 3 of 4 — must bridge via 2 and 3."""
        sets = {
            1: _privs([1, 2, 3, 4]),
            2: _privs([1, 2, 3, 5]),
            3: _privs([1, 2, 5, 6]),
            4: _privs([1, 5, 6, 7]),
        }
        src, fcs = _chain_source(sets, lambda h: sets[h])
        trusted = MemProvider()
        cert = BisectingCertifier(CHAIN, seed=fcs[1], trusted=trusted, source=src)
        cert.certify(fcs[4])
        assert cert.last_height == 4
        # intermediate hops became trusted (the memoization)
        assert trusted.get_by_height(3).height() >= 2

    @pytest.mark.parametrize(
        "tip,every,max_rounds", [(64, 4, 8), (256, 8, 12)]
    )
    def test_dense_rotation_long_chain(self, tip, every, max_rounds):
        """`tip` heights rotating one of 8 validators every `every`
        heights: bisection must converge in far fewer verifies than the
        sequential walk's one-commit-per-height."""
        base = list(range(1, 9))

        def privs_for(h):
            rotated = (h - 1) // every  # rotations accumulated by height h
            ids = base[rotated % 8:] + [100 + i for i in range(rotated)]
            return _privs(sorted(ids[-8:]))

        heights = list(range(1, tip + 1))
        src, fcs = _chain_source(heights, privs_for)
        cert = BisectingCertifier(
            CHAIN, seed=fcs[1], trusted=MemProvider(), source=src
        )
        cert.verify_to_height(tip)
        assert cert.last_height == tip
        sequential_verifies = tip * 8
        assert cert.last_walk_verifies < sequential_verifies / 2
        # the on-device cost term is LAUNCHES (rounds), not rows: the
        # sequential walk pays one per height, bisection a handful total
        assert cert.last_walk_rounds <= max_rounds

    def test_unbridgeable_gap_raises_too_much_change(self):
        sets = {
            1: _privs([1, 2, 3, 4]),
            4: _privs([1, 5, 6, 7]),
        }
        src, fcs = _chain_source(sets, lambda h: sets[h])
        cert = BisectingCertifier(
            CHAIN, seed=fcs[1], trusted=MemProvider(), source=src
        )
        with pytest.raises(ErrTooMuchChange):
            cert.certify(fcs[4])

    def test_trust_period_boundary(self):
        """An expired trusted state must refuse to walk (the skip
        rule's slashing backstop is gone); a fresh one proceeds."""
        privs = _privs(range(1, 5))
        src, fcs = _chain_source((1, 10), lambda h: privs)
        # header times are h * 1e9 ns (test fixture); trust 1 hour
        period_ns = int(3600 * 1e9)
        expired_now = fcs[1].header.time + period_ns + 1
        cert = BisectingCertifier(
            CHAIN,
            seed=fcs[1],
            trusted=MemProvider(),
            source=src,
            trust_period_ns=period_ns,
            now_ns=lambda: expired_now,
        )
        with pytest.raises(ValidationError, match="trust expired"):
            cert.verify_to_height(10)
        fresh = BisectingCertifier(
            CHAIN,
            seed=fcs[1],
            trusted=MemProvider(),
            source=src,
            trust_period_ns=period_ns,
            now_ns=lambda: fcs[1].header.time + period_ns - 1,
        )
        fresh.verify_to_height(10)
        assert fresh.last_height == 10

    def test_one_third_overlap_boundary(self):
        """The skip rule is STRICTLY more than 1/3 of trusted power:
        exactly 1/3 overlap cannot jump, just above it can."""
        old = _privs(range(1, 10))  # 9 validators, power 10 each
        exactly_third = _privs([1, 2, 3] + list(range(20, 26)))  # keep 3/9
        just_above = _privs([1, 2, 3, 4] + list(range(20, 25)))  # keep 4/9
        for new, ok in ((exactly_third, False), (just_above, True)):
            src = MemProvider()
            seed = _full_commit(1, old)
            src.store_commit(seed)
            src.store_commit(_full_commit(2, new))
            cert = BisectingCertifier(
                CHAIN, seed=seed, trusted=MemProvider(), source=src
            )
            if ok:
                cert.verify_to_height(2)
                assert cert.last_height == 2
            else:
                with pytest.raises(ErrTooMuchChange):
                    cert.verify_to_height(2)

    def test_address_reuse_with_attacker_keys_cannot_hijack(self):
        """The trust-hijack regression: a candidate valset reusing
        every TRUSTED address but binding attacker pubkeys, fully
        signed by the attacker keys, passes its own >2/3 quorum by
        construction — it must earn ZERO old-set credit (the trusted
        validator's KEY doesn't match the key the lane signature was
        verified under), never the >1/3 overlap that would pin the
        client to the forged chain."""
        from tendermint_tpu.certifiers.certifier import FullCommit
        from tendermint_tpu.types import Validator, ValidatorSet
        from tendermint_tpu.types.block import Commit, Header
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.part_set import PartSetHeader
        from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

        trusted_privs = _privs(range(1, 5))
        seed = _full_commit(1, trusted_privs)
        attackers = _privs(range(11, 15))
        forged_vs = ValidatorSet(
            [
                Validator(
                    address=v.address,
                    pub_key=att.pub_key,
                    voting_power=v.voting_power,
                )
                for v, att in zip(seed.validators.validators, attackers)
            ]
        )
        by_pub = {a.pub_key.data: a for a in attackers}
        header = Header(
            chain_id=CHAIN,
            height=10,
            time=10_000_000_000,
            num_txs=0,
            last_block_id=BlockID.zero(),
            last_commit_hash=b"",
            data_hash=b"",
            validators_hash=forged_vs.hash(),
            app_hash=b"evil",
        )
        bid = BlockID(
            header.hash(), PartSetHeader(total=1, hash=header.hash()[:20])
        )
        precommits = []
        for idx, val in enumerate(forged_vs.validators):
            vote = Vote(
                validator_address=val.address,
                validator_index=idx,
                height=10,
                round=0,
                timestamp=idx + 1,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=bid,
            )
            signer = by_pub[val.pub_key.data]._signer
            precommits.append(
                vote.with_signature(signer.sign(vote.sign_bytes(CHAIN)))
            )
        forged = FullCommit(
            header=header,
            commit=Commit(block_id=bid, precommits=precommits),
            validators=forged_vs,
        )
        src = MemProvider()
        src.store_commit(seed)
        src.store_commit(forged)
        trusted = MemProvider()
        cert = BisectingCertifier(CHAIN, seed=seed, trusted=trusted, source=src)
        with pytest.raises(ErrTooMuchChange):
            cert.verify_to_height(10)
        assert cert.last_height == 1  # trust never moved
        assert trusted.latest_commit().height() == 1

    def test_environmental_failures_are_typed_not_forged(self):
        """Trust expiry and fetch failure are client-side conditions:
        typed errors, separate metric labels — the forgery signal
        operators alert on must not move."""
        from tendermint_tpu.telemetry import REGISTRY

        def forged_count():
            return REGISTRY.counter_value(
                "tendermint_lightclient_bisections_total", result="forged"
            )

        privs = _privs(range(1, 5))
        src, fcs = _chain_source((1, 10), lambda h: privs)
        base = forged_count()
        # empty source: ErrNoSourceCommit, result="no_source"
        cert = BisectingCertifier(
            CHAIN, seed=fcs[1], trusted=MemProvider(), source=MemProvider()
        )
        ns_base = REGISTRY.counter_value(
            "tendermint_lightclient_bisections_total", result="no_source"
        )
        with pytest.raises(ErrNoSourceCommit):
            cert.verify_to_height(10)
        assert (
            REGISTRY.counter_value(
                "tendermint_lightclient_bisections_total", result="no_source"
            )
            == ns_base + 1
        )
        # expired pin: ErrTrustExpired, result="trust_expired"
        period_ns = int(3600 * 1e9)
        expired = BisectingCertifier(
            CHAIN,
            seed=fcs[1],
            trusted=MemProvider(),
            source=src,
            trust_period_ns=period_ns,
            now_ns=lambda: fcs[1].header.time + period_ns + 1,
        )
        te_base = REGISTRY.counter_value(
            "tendermint_lightclient_bisections_total", result="trust_expired"
        )
        with pytest.raises(ErrTrustExpired):
            expired.verify_to_height(10)
        # the direct same-valset certify path is trust-gated too
        with pytest.raises(ErrTrustExpired):
            expired.certify(fcs[10])
        assert (
            REGISTRY.counter_value(
                "tendermint_lightclient_bisections_total", result="trust_expired"
            )
            == te_base + 1
        )
        assert forged_count() == base  # the forgery signal never moved

    def test_forged_signature_is_hard_failure_and_never_cached(self):
        privs = _privs(range(1, 5))
        src, fcs = _chain_source((1, 10), lambda h: privs)
        bad = fcs[10].commit.precommits[1]
        sig = bytearray(bad.signature)
        sig[5] ^= 1
        fcs[10].commit.precommits[1] = bad.with_signature(bytes(sig))
        trusted = MemProvider()
        cert = BisectingCertifier(CHAIN, seed=fcs[1], trusted=trusted, source=src)
        with pytest.raises(ValidationError, match="forged|invalid"):
            cert.verify_to_height(10)
        assert trusted.latest_commit().height() == 1  # forgery never stored

    def test_quorumless_candidate_is_forged(self):
        """A commit that cannot certify its own header (single signer)
        is a provider lie, not a bisection trigger."""
        from tendermint_tpu.types.block import Commit

        privs = _privs(range(1, 5))
        seed = _full_commit(1, privs)  # sign ascending: HRS guard
        fc = _full_commit(10, privs)
        keep = next(
            i for i, p in enumerate(fc.commit.precommits) if p is not None
        )
        fc.commit = Commit(
            block_id=fc.commit.block_id,
            precommits=[
                p if i == keep else None
                for i, p in enumerate(fc.commit.precommits)
            ],
        )
        src = MemProvider()
        src.store_commit(seed)
        src.store_commit(fc)
        cert = BisectingCertifier(CHAIN, seed=seed, trusted=MemProvider(), source=src)
        with pytest.raises(ValidationError, match="quorum"):
            cert.verify_to_height(10)

    def test_trusted_cache_memoizes_walks(self):
        """A second certifier sharing the trusted store restarts at the
        proven height: zero verifies to re-reach it."""
        privs = _privs(range(1, 5))
        src, fcs = _chain_source((1, 256), lambda h: privs)
        db = MemDB()
        cache = CertifiedCommitCache(store=FullCommitStore(db))
        cert = BisectingCertifier(CHAIN, seed=fcs[1], trusted=cache, source=src)
        cert.verify_to_height(256)
        assert cert.last_height == 256
        # fresh certifier, same durable trust, EMPTY source
        cert2 = BisectingCertifier(
            CHAIN,
            seed=fcs[1],
            trusted=CertifiedCommitCache(store=FullCommitStore(db)),
            source=MemProvider(),
        )
        cert2.verify_to_height(256)
        assert cert2.last_height == 256
        assert cert2.last_walk_verifies == 0


class TestBatchedLaunches:
    def test_one_coalesced_launch_per_bisection_round(self):
        """The launch-ledger assertion: every bisection round's commit
        verifies merge into ONE coalesced launch tagged
        consumer=lightclient — never one launch per probed height."""
        from tendermint_tpu.services.batcher import CoalescingVerifier
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.telemetry.launchlog import LAUNCHLOG

        sets = {
            1: _privs([1, 2, 3, 4]),
            2: _privs([1, 2, 3, 5]),
            3: _privs([1, 2, 5, 6]),
            4: _privs([1, 5, 6, 7]),
        }
        src, fcs = _chain_source(sets, lambda h: sets[h])
        verifier = CoalescingVerifier(HostBatchVerifier(), cache_size=0)
        LAUNCHLOG.clear()  # process-global forensics ring: fresh window
        try:
            cert = BisectingCertifier(
                CHAIN,
                seed=fcs[1],
                trusted=MemProvider(),
                source=src,
                verifier=verifier,
            )
            cert.verify_to_height(4)
        finally:
            verifier.close()
        rounds = cert.last_walk_rounds
        assert rounds >= 2  # the rotation forced at least one bisection
        lc_records = [
            r
            for r in LAUNCHLOG.recent()
            if "lightclient" in (r.get("consumers") or {})
        ]
        assert len(lc_records) == rounds, (
            f"expected one coalesced launch per round ({rounds}), "
            f"saw {len(lc_records)}"
        )
        for rec in lc_records:
            assert set(rec["consumers"]) == {"lightclient"}


class TestEvidenceExtraction:
    def _pair(self, double_signer_idx=0):
        from tendermint_tpu.testing.byzantine import forge_fullcommit

        honest = _full_commit(5, _privs(range(1, 5)))
        forged = forge_fullcommit(
            honest, self._ordered(honest)[double_signer_idx], CHAIN
        )
        return honest, forged

    @staticmethod
    def _ordered(fc):
        privs = _privs(range(1, 5))
        by_addr = {p.address: p for p in privs}
        return [by_addr[v.address] for v in fc.validators.validators]

    def test_double_sign_becomes_evidence(self):
        honest, forged = self._pair()
        evs = extract_double_sign_evidence(forged, honest, CHAIN)
        assert len(evs) == 1
        ev = evs[0]
        ev.verify(CHAIN, honest.validators)  # genuine, chain-committable
        assert ev.height == 5

    def test_garbage_signature_yields_nothing(self):
        """A forged precommit with a junk sig is peer noise — it must
        never convict the validator it names."""
        honest, forged = self._pair()
        for i, pc in enumerate(forged.commit.precommits):
            if pc is not None:
                forged.commit.precommits[i] = pc.with_signature(b"\x01" * 64)
        assert extract_double_sign_evidence(forged, honest, CHAIN) == []

    def test_different_round_cannot_pair(self):
        honest, forged = self._pair()
        from dataclasses import replace

        for i, pc in enumerate(forged.commit.precommits):
            if pc is not None:
                forged.commit.precommits[i] = replace(pc, round=1)
        assert extract_double_sign_evidence(forged, honest, CHAIN) == []

    def test_same_block_is_no_conflict(self):
        honest = _full_commit(5, _privs(range(1, 5)))
        assert extract_double_sign_evidence(honest, honest, CHAIN) == []

    def test_height_mismatch_yields_nothing(self):
        honest = _full_commit(5, _privs(range(1, 5)))
        other = _full_commit(6, _privs(range(1, 5)))
        assert extract_double_sign_evidence(other, honest, CHAIN) == []


class TestReactorRoundTrip:
    def _wired_pair(self, serve_cache, client_subscribes=False, certifier=None):
        from tendermint_tpu.lightclient.reactor import LightClientReactor
        from tendermint_tpu.p2p.peer import NodeInfo
        from tendermint_tpu.p2p.switch import Switch, connect_switches

        server = LightClientReactor(chain_id=CHAIN, cache=serve_cache)
        client = LightClientReactor(
            chain_id=CHAIN, subscribe=client_subscribes, certifier=certifier,
            cache=CertifiedCommitCache(),
        )
        sws = []
        for name, reactor in (("server", server), ("client", client)):
            sw = Switch(
                NodeInfo(node_id=f"lc-{name}", moniker=name, chain_id=CHAIN)
            )
            sw.add_reactor("lightclient", reactor)
            sw.start()
            sws.append(sw)
        connect_switches(sws[0], sws[1])
        return server, client, sws

    def test_request_response_serves_certified_cache(self):
        cache = CertifiedCommitCache()
        privs = _privs(range(1, 5))
        cache.put_certified(_full_commit(3, privs))
        cache.put_certified(_full_commit(7, privs))
        server, client, sws = self._wired_pair(cache)
        try:
            fc = client.request_commit(7)
            assert fc is not None and fc.height() == 7
            # floor fallback for a between-heights ask
            fc5 = client.request_commit(5)
            assert fc5 is not None and fc5.height() == 3
            # tip ask
            tip = client.request_commit(0)
            assert tip is not None and tip.height() == 7
        finally:
            for sw in sws:
                sw.stop()

    def test_concurrent_same_height_requests_all_served(self):
        """Wait slots are per-request, not per-height: concurrent
        fetches of the same height must each get the response instead
        of clobbering a shared slot and orphaning each other."""
        cache = CertifiedCommitCache()
        privs = _privs(range(1, 5))
        cache.put_certified(_full_commit(7, privs))
        server, client, sws = self._wired_pair(cache)
        try:
            results = []
            lock = threading.Lock()

            def fetch():
                fc = client.request_commit(7)
                with lock:
                    results.append(fc)

            threads = [threading.Thread(target=fetch) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 4
            assert all(fc is not None and fc.height() == 7 for fc in results)
            assert client._waits == {}  # every waiter cleaned up
        finally:
            for sw in sws:
                sw.stop()

    def test_environmental_push_failure_does_not_score_peer(self):
        """An honest peer pushing the tip while the CLIENT's pin is
        expired (or a bisection fetch times out) must not be banned —
        only genuine forgeries route to misbehavior."""
        from tendermint_tpu.lightclient.reactor import LightClientReactor

        privs = _privs(range(1, 5))
        seed = _full_commit(1, privs)
        period_ns = int(3600 * 1e9)
        expired_cert = BisectingCertifier(
            CHAIN,
            seed=seed,
            trusted=CertifiedCommitCache(),
            source=MemProvider(),
            trust_period_ns=period_ns,
            now_ns=lambda: seed.header.time + period_ns + 1,
        )
        reactor = LightClientReactor(
            chain_id=CHAIN,
            subscribe=True,
            certifier=expired_cert,
            cache=CertifiedCommitCache(),
        )

        class _SwitchStub:
            def __init__(self):
                self.reports = []

            def report_misbehavior(self, peer_id, kind, detail=None):
                self.reports.append((peer_id, kind))

            def peers(self):
                return []

        stub = _SwitchStub()
        reactor.switch = stub
        # a perfectly honest tip push at a new height (valset changed
        # only in the sense that trust can't walk there: expired pin)
        reactor._on_push("honest-peer", _full_commit(5, _privs(range(1, 6))))
        assert stub.reports == []  # no ban, no debit
        assert reactor.cache.get_exact(5) is None  # and nothing cached

    def test_push_certifies_then_forwards(self):
        """A pushed FullCommit is certified through the client's pin
        before caching; the proven tip then fans on to the client's own
        subscribers (replica chains)."""
        privs = _privs(range(1, 5))
        seed = _full_commit(1, privs)
        serve_cache = CertifiedCommitCache()
        serve_cache.put_certified(seed)
        certifier = BisectingCertifier(
            CHAIN, seed=seed, trusted=CertifiedCommitCache(), source=None
        )
        server, client, sws = self._wired_pair(
            serve_cache, client_subscribes=True, certifier=certifier
        )
        try:
            fc5 = _full_commit(5, privs)
            server.cache.put_certified(fc5)
            server.announce(fc5)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.cache.get_exact(5) is not None:
                    break
                time.sleep(0.02)
            assert client.cache.get_exact(5) is not None
            stats = client.serving_stats()
            assert stats["last_push_age_s"] is not None
        finally:
            for sw in sws:
                sw.stop()

    def test_forged_push_scores_peer_and_extracts_evidence(self):
        from tendermint_tpu.evidence import EvidencePool
        from tendermint_tpu.testing.byzantine import forge_fullcommit
        from tendermint_tpu.telemetry import REGISTRY

        privs = _privs(range(1, 5))
        seed = _full_commit(1, privs)
        honest5 = _full_commit(5, privs)
        client_cache = CertifiedCommitCache()
        pool = EvidencePool(chain_id=CHAIN)
        certifier = BisectingCertifier(
            CHAIN, seed=seed, trusted=client_cache, source=None
        )
        server, client, sws = self._wired_pair(
            CertifiedCommitCache(), client_subscribes=True, certifier=certifier
        )
        client.evidence_pool = pool
        try:
            # client already trusts the honest height 5
            client.cache.put_certified(honest5)
            certifier.certify(honest5)
            by_addr = {p.address: p for p in privs}
            compromised = by_addr[honest5.validators.validators[0].address]
            forged = forge_fullcommit(honest5, compromised, CHAIN)
            base = REGISTRY.counter_value(
                "tendermint_p2p_peer_misbehavior_total", kind="forged_fullcommit"
            )
            # push the forgery from the SERVER switch's peer object
            from tendermint_tpu.lightclient.reactor import (
                LIGHTCLIENT_CHANNEL,
                _enc_fc_announce,
            )

            peer = sws[0].peers()[0]
            peer.try_send(LIGHTCLIENT_CHANNEL, _enc_fc_announce(forged))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and pool.depth() == 0:
                time.sleep(0.02)
            assert pool.depth() == 1, "double-sign evidence not extracted"
            ev = pool.pending_evidence()[0]
            assert ev.address == compromised.address
            delta = (
                REGISTRY.counter_value(
                    "tendermint_p2p_peer_misbehavior_total",
                    kind="forged_fullcommit",
                )
                - base
            )
            assert delta >= 1
            # weight 100 = instant ban of the serving peer
            assert sws[1].scorer.is_banned("lc-server")
            # the forgery never entered the certified cache
            assert client.cache.get_exact(5).header.app_hash == honest5.header.app_hash
        finally:
            pool.close()
            for sw in sws:
                sw.stop()


class TestReplicaAcceptance:
    """Live 4-validator + 2-replica net: replicas bootstrap, follow via
    fast-sync tail + FullCommit subscription, serve proofs over p2p and
    RPC, and a light client walks against a REPLICA (not a validator)."""

    def test_replicas_follow_and_serve(self, tmp_path):
        import json
        import urllib.request

        from tendermint_tpu.certifiers.certifier import FullCommit
        from tendermint_tpu.certifiers.node_provider import NodeProvider
        from tendermint_tpu.rpc.client import HTTPClient
        from tendermint_tpu.testing.nemesis import FullNemesisNode, Nemesis

        def replica_mutator(cfg):
            cfg.replica.enable = True

        net = Nemesis(
            4, home=str(tmp_path), node_factory=Nemesis.full_node_factory()
        )
        with net:
            net.wait_height(2, timeout=90)
            reps = []
            for i in (4, 5):
                rep = FullNemesisNode(
                    i,
                    net.genesis,
                    net.privs,
                    str(tmp_path),
                    net.chain_id,
                    config_mutator=replica_mutator,
                )
                net.add_node(rep)
                reps.append(rep)
            # replicas follow the chain without joining consensus
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline and not all(
                r.height >= 3 for r in reps
            ):
                time.sleep(0.1)
            assert all(r.height >= 3 for r in reps), [r.height for r in reps]
            assert all(r.node.consensus is None for r in reps)
            # subscription stream certified the tip into the cache
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not all(
                r.node.fullcommit_cache.latest_height() >= 3 for r in reps
            ):
                time.sleep(0.1)
            assert all(
                r.node.fullcommit_cache.latest_height() >= 3 for r in reps
            )
            rep = reps[0]
            # health: ready, follow-mode sync check, serving section
            h = rep.node.health()
            assert h["status"] in ("ok", "degraded")
            assert h["checks"]["sync"]["follow"] is True
            assert h["serving"]["replica"] is True
            assert h["serving"]["serving_lag"] is not None
            assert h["serving"]["last_push_age_s"] is not None
            # RPC full_commit route serves a decodable proof unit
            url = f"http://127.0.0.1:{rep.rpc_port}/full_commit?height=2"
            with urllib.request.urlopen(url, timeout=10) as resp:
                out = json.load(resp)["result"]
            fc = FullCommit.decode(bytes.fromhex(out["full_commit"]))
            assert fc.height() == 2
            # a light client walks against the REPLICA fleet
            client_cert = BisectingCertifier(
                net.chain_id,
                validators=net.genesis.validator_set(),
                height=0,
                trusted=CertifiedCommitCache(),
                source=NodeProvider(HTTPClient(f"127.0.0.1:{rep.rpc_port}")),
            )
            target = rep.height
            client_cert.verify_to_height(target)
            assert client_cert.last_height >= 2
            assert client_cert.last_walk_rounds <= 3  # skipping, not walking
