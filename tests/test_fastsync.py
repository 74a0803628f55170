"""Fast-sync: BlockPool scheduling + syncing a 200-block store into a
fresh node over the p2p network with window-batched commit verification
(reference `blockchain/pool_test.go`, `blockchain/reactor.go:191-289`;
BASELINE config 3 shape).
"""

import functools
import time

import pytest

from tendermint_tpu.abci.apps import KVStoreApp
from tendermint_tpu.abci.client import local_client_creator
from tendermint_tpu.blockchain import BlockchainReactor, BlockPool, BlockStore
from tendermint_tpu.db.kv import MemDB
from tendermint_tpu.p2p import NodeInfo, Switch, connect_switches
from tendermint_tpu.state import make_genesis_state

from tests.helpers import CHAIN_ID as CHAIN
from tests.helpers import ChainSim


def wait_until(pred, timeout=60.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


class TestBlockPool:
    def test_schedules_up_to_cap(self):
        pool = BlockPool(start_height=1, max_pending=8)
        pool.set_peer_height("p1", 100)
        pool.set_peer_height("p2", 100)
        reqs, evict = pool.schedule_requests(now=0.0)
        assert len(reqs) == 8 and not evict
        assert {h for _, h in reqs} == set(range(1, 9))
        # both peers get load
        assert {p for p, _ in reqs} == {"p1", "p2"}
        # nothing new while outstanding
        assert pool.schedule_requests(now=1.0) == ([], [])

    def test_timeout_evicts_peer_and_reassigns(self):
        pool = BlockPool(start_height=1, max_pending=4)
        pool.set_peer_height("p1", 100)
        reqs, evict = pool.schedule_requests(now=0.0)
        assert {p for p, _ in reqs} == {"p1"} and not evict
        pool.set_peer_height("p2", 100)
        # p1 never answers: evicted at timeout, heights rescheduled to
        # p2 in the same tick (byzantine defense: a peer advertising an
        # unserved height can no longer pin max_peer_height forever)
        reqs2, evict2 = pool.schedule_requests(now=100.0)
        assert evict2 == ["p1"]
        assert {p for p, _ in reqs2} == {"p2"}
        assert {h for _, h in reqs2} == set(range(1, 5))
        assert pool.num_peers() == 1

    def test_slow_drip_peer_evicted_below_min_recv_rate(self):
        """A peer that keeps responding but below the 10 kB/s floor is
        evicted (reference pool.go:33,121-126) while the healthy peer
        keeps the sync going — a trickle must not throttle the window."""
        import types

        clock = [0.0]
        pool = BlockPool(start_height=1, max_pending=8, time_fn=lambda: clock[0])
        pool.set_peer_height("slow", 100)
        pool.set_peer_height("fast", 100)
        reqs, evict = pool.schedule_requests(now=clock[0])
        assert not evict and {p for p, _ in reqs} == {"slow", "fast"}
        by_peer = {}
        for p, h in reqs:
            by_peer.setdefault(p, []).append(h)

        def blk(h):
            return types.SimpleNamespace(
                header=types.SimpleNamespace(height=h)
            )

        # 5 seconds pass: fast delivers all its blocks at ~40 kB/s,
        # slow drips one tiny response (~20 B/s) — alive, but a trickle
        for t in (1.0, 2.0, 3.0, 4.0, 5.0):
            clock[0] = t
            for h in by_peer["fast"]:
                pool.add_block("fast", blk(h), size=8000)
            by_peer["fast"] = []
        pool.add_block("slow", blk(by_peer["slow"][0]), size=100)

        reqs2, evict2 = pool.schedule_requests(now=clock[0])
        assert evict2 == ["slow"]
        assert pool.num_peers() == 1
        # the freed heights rescheduled to the healthy peer in-tick
        assert reqs2 and {p for p, _ in reqs2} == {"fast"}

    def test_rejects_unrequested_blocks(self):
        import types

        pool = BlockPool(start_height=1)
        pool.set_peer_height("p1", 10)
        pool.schedule_requests(now=0.0)
        fake = types.SimpleNamespace(
            header=types.SimpleNamespace(height=1)
        )
        assert not pool.add_block("stranger", fake)  # wrong peer
        req_peer = pool._requests[1].peer_id
        assert pool.add_block(req_peer, fake)
        assert pool.peek(1) == [fake]

    def test_redo_drops_suffix_and_names_peer(self):
        """Since PR 47 a redo names the peer that delivered the block and
        forgets what THAT peer delivered or owes, not the suffix: another
        peer's blocks and requests stay, above the height as below it."""
        import types

        pool = BlockPool(start_height=1, max_pending=8)
        pool.set_peer_height("p1", 10)
        pool.set_peer_height("p2", 10)
        reqs, _ = pool.schedule_requests(now=0.0)
        asked = dict((h, p) for p, h in reqs)
        assert set(asked.values()) == {"p1", "p2"}
        for h in range(1, 7):  # 7 and 8 stay in flight
            blk = types.SimpleNamespace(header=types.SimpleNamespace(height=h))
            pool.add_block(asked[h], blk)
        assert len(pool.peek(8)) == 6
        liar = asked[2]
        other = "p2" if liar == "p1" else "p1"
        mine = [h for h in range(1, 7) if asked[h] == liar]
        bad, blamed, others = pool.redo(2)
        assert (bad, blamed, others) == (liar, len(mine), 0)
        # every block the other peer delivered is still there, and its requests
        assert sorted(pool._blocks) == [h for h in range(1, 7) if asked[h] == other]
        assert sorted(pool._requests) == [h for h in (7, 8) if asked[h] == other]
        # a height with no block names nobody and forgets nothing
        assert pool.redo(2) == (None, 0, 0)
        # the freed heights go to whoever is left at the next tick
        pool.remove_peer(liar)
        reqs2, _ = pool.schedule_requests(now=0.1)
        assert {p for p, _ in reqs2} == {other}
        assert {h for _, h in reqs2} >= {h for h in range(1, 9) if asked[h] == liar}

    def test_remove_peer_forgets_what_it_delivered(self):
        """A dropped peer's delivered, unapplied blocks go with its
        requests (a banned liar's block kept would be found false later
        and the peer debited a second time)."""
        import types

        pool = BlockPool(start_height=1, max_pending=6)
        pool.set_peer_height("p1", 10)
        pool.set_peer_height("p2", 10)
        reqs, _ = pool.schedule_requests(now=0.0)
        asked = dict((h, p) for p, h in reqs)
        for h in range(1, 5):
            blk = types.SimpleNamespace(header=types.SimpleNamespace(height=h))
            pool.add_block(asked[h], blk)
        assert pool.remove_peer("p1") == sum(1 for h in range(1, 5) if asked[h] == "p1")
        assert all(p == "p2" for _, p in pool._blocks.values())
        assert all(r.peer_id == "p2" for r in pool._requests.values())
        assert pool.remove_peer("p1") == 0 and pool.num_peers() == 1


def _pipelined_reactor(sim: ChainSim, depth=2, verifier=None, app=None):
    """A fresh fast-syncing reactor with `sim`'s whole chain pre-loaded
    into its pool (the bench/ordering harness: drive `_try_sync`
    directly, no network, so pipeline drains are deterministic)."""
    from tendermint_tpu.abci.apps import KVStoreApp

    fresh_state = make_genesis_state(MemDB(), sim.genesis)
    fresh_state.save()
    store = BlockStore(MemDB())
    conns = local_client_creator(app if app is not None else KVStoreApp())()
    reactor = BlockchainReactor(
        state=fresh_state,
        store=store,
        app_conn=conns.consensus,
        fast_sync=True,
        verifier=verifier,
        pipeline_depth=depth,
    )
    reactor.pool.set_peer_height("srv", len(sim.blocks))
    for h, b in enumerate(sim.blocks, start=1):
        reactor.pool._blocks[h] = (b, "srv")
    return reactor, fresh_state, store


def plain_reference(served, record) -> tuple[list[str], int]:
    """What a sound node may do with what it was served, from the list
    of (height, server, bytes served) and the chain's record {height:
    bytes}, and nothing of the reactor's: the peers whose bytes are not
    the record's are the liars; and the node may have applied as far as
    the last height whose block, every block below it, and the block
    above it (which carries its commit) are the record's."""
    liars = sorted({server for h, server, raw in served if raw != record[h]})
    got = {h: raw for h, _server, raw in served}
    sound = 0
    while sound + 1 in got and got[sound + 1] == record[sound + 1]:
        sound += 1
    return liars, max(sound - 1, 0)


def _flip_sig(block):
    """The block with one bit of one signature of its last_commit
    flipped: the header stays, the bytes and so the part-set root move."""
    import dataclasses

    votes = list(block.last_commit.precommits)
    sig = bytearray(votes[1].signature)
    sig[5] ^= 0x10
    votes[1] = votes[1].with_signature(bytes(sig))
    commit = dataclasses.replace(block.last_commit, precommits=votes)
    return dataclasses.replace(block, last_commit=commit)


def _changed_block_id(how: str):
    """A lie of block i + 1's server: one bit of the hash in the
    `block_id` its last_commit carries, in the commit's own field alone
    or in every vote's too."""
    import dataclasses

    def lie(block):
        commit = block.last_commit
        changed = dataclasses.replace(
            commit.block_id,
            hash=bytes([commit.block_id.hash[0] ^ 1]) + commit.block_id.hash[1:],
        )
        votes = list(commit.precommits)
        if how == "votes_too":
            votes = [dataclasses.replace(v, block_id=changed) for v in votes]
        return dataclasses.replace(
            block, last_commit=dataclasses.replace(commit, block_id=changed, precommits=votes)
        )

    return lie


@functools.cache
def _chain_of_40() -> ChainSim:
    sim = ChainSim(n_vals=4)
    for _ in range(40):
        sim.advance()
    return sim


class _FedByHand:
    """A fast-syncing reactor on the host verifier (no JAX), its pool fed
    by hand as the pool's first wave feeds it: height h from peer
    (h - 1) % 3, three peers, so that a window's first and seventeenth
    block have different servers. `forged` maps a height to the lie its
    server tells there. `served` is what went in, for the plain
    reference: (height, server, bytes); `debited` the peers the reactor
    debits, in order."""

    PEERS = 3

    def __init__(self, forged: dict, depth=None, sim=None, app=None):
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.types.block import Block

        self.sim = sim = sim or _chain_of_40()
        self.reactor, self.state, self.store = _pipelined_reactor(
            sim, depth=depth, verifier=HostBatchVerifier(), app=app
        )
        self.debited: list[str] = []
        fed = self

        class Switch:
            def report_misbehavior(self, peer_id, kind, detail="", weight=None):
                assert kind == "forged_block"
                fed.debited.append(peer_id)

            def peers(self):
                return []

        self.reactor.switch = Switch()
        pool = self.reactor.pool
        pool.remove_peer("srv")
        for i in range(self.PEERS):
            pool.set_peer_height(self.server(i + 1), len(sim.blocks))
        self.record = {h: b.encode() for h, b in enumerate(sim.blocks, start=1)}
        self.served = []
        for h, sound in enumerate(sim.blocks, start=1):
            raw = forged[h](sound).encode() if h in forged else self.record[h]
            pool._blocks[h] = (Block.decode(raw), self.server(h))
            self.served.append((h, self.server(h), raw))

    def server(self, height: int) -> str:
        return f"peer{(height - 1) % self.PEERS}"


def _redos() -> dict:
    from tendermint_tpu.telemetry.metrics import FASTSYNC_REDO_CAUSES, FASTSYNC_REDOS

    return {c: FASTSYNC_REDOS.labels(cause=c).value for c in FASTSYNC_REDO_CAUSES}


def _rise(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


class TestFastSyncPipeline:
    """Software-pipeline ordering: while window K's verdict is in
    flight, K+1 preps and K-1 applies — and any redo / verdict failure
    / valset boundary must drain the in-flight suffix WITHOUT applying
    stale blocks (ISSUE 4 acceptance)."""

    @pytest.mark.parametrize("depth", [1, 2, 3, None])
    def test_pipelined_sync_applies_full_chain(self, depth):
        """Any depth applies the same chain; no depth given means
        `PIPELINE_DEPTH` = 2. Counts: every window launched is one handle
        on the `fastsync` queue, joined once (one overlap observation a
        window), and never more than `depth` of them are unjoined."""
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.telemetry import REGISTRY

        windows_fam = REGISTRY.get("tendermint_fastsync_windows_total")
        joins = REGISTRY.get("tendermint_dispatch_overlap_ratio").labels(
            queue="fastsync"
        )

        sim = ChainSim(n_vals=4)
        for _ in range(48):
            sim.advance()
        # a verifier of its own: through `default_verifier()` the dedup
        # cache, which proved these votes when the chain was made,
        # answers every window without a launch
        reactor, state, store = _pipelined_reactor(
            sim, depth=depth, verifier=HostBatchVerifier()
        )
        assert reactor.pipeline_depth == (2 if depth is None else depth)
        peak = [0]
        submit = reactor._queue().submit

        def counting_submit(*a, **kw):
            handle = submit(*a, **kw)
            peak[0] = max(peak[0], reactor._queue().inflight())
            return handle

        reactor._queue().submit = counting_submit
        windows0, joins0 = windows_fam.sum_total(), joins.value["count"]
        reactor._try_sync()
        assert store.height == 47, f"depth {depth}"
        assert state.last_block_height == 47
        for h in (1, 20, 47):
            assert store.load_block(h).hash() == sim.blocks[h - 1].hash()
        windows = windows_fam.sum_total() - windows0
        assert windows == 3  # 47 commits in windows of 16
        assert joins.value["count"] - joins0 == windows
        assert peak[0] == min(reactor.pipeline_depth, windows)

    def test_linkage_break_mid_pipeline_applies_intact_prefix_only(self):
        """Window 2's commit linkage breaks while window 1 is in
        flight: window 1 (verified under intact linkage) must still
        apply, and of window 2 the blocks before the refuted one, once
        their own commits pass; the refuted block is never applied."""
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tests.helpers import make_block_id

        sim = ChainSim(n_vals=4)
        for _ in range(40):
            sim.advance()
        # blocks[20] (height 21) carries height 20's commit; point it at
        # a wrong block so window-2 prep hits the linkage mismatch
        import dataclasses

        bad = dataclasses.replace(
            sim.blocks[20].last_commit, block_id=make_block_id(b"forged")
        )
        sim.blocks[20] = dataclasses.replace(sim.blocks[20], last_commit=bad)
        reactor, _state, store = _pipelined_reactor(
            sim, depth=2, verifier=HostBatchVerifier()
        )
        redos0 = _redos()
        reactor._try_sync()
        # the commit does not verify over the id it carries, so block 21
        # made it up: redone there. Window 2 goes on as heights 17..20
        # and applies 17..19 (height 20's commit rode in block 21)
        assert _rise(redos0, _redos()) == {"block_id": 1}
        assert store.height == 19
        assert store.load_block(20) is None
        assert reactor.pool.height == 20
        # one server served everything: all of it is forgotten
        assert reactor.pool.peek(50) == []

    def test_forged_verdict_mid_pipeline_drains_without_applying(self):
        """A forged commit that only the verdict can find: the block that
        carries it is the LAST of window 2 (height 33, so no successor in
        the window holds its id against a commit). The verdict fails at
        the JOIN, naming entry 15, height 32: window 1 applies, and of
        window 2 the fifteen entries before the one named, which are
        verified (the store ends at 31 where it ended at 16); block 33's
        server is debited, not block 17's; block 32, whose id the forged
        commit does carry, stays in the pool, neither proved nor refuted;
        window 3 never applies. (Until PR 47 this test forged height 20's
        commit in place and never reached a verdict: block 21's own id
        no longer matched and `_claim_window` named it first.)"""
        from tendermint_tpu.telemetry.metrics import FASTSYNC_PREFIX_BLOCKS_APPLIED

        fed = _FedByHand(forged={33: _flip_sig}, depth=2)
        redos0, prefix0 = _redos(), FASTSYNC_PREFIX_BLOCKS_APPLIED.value
        fed.reactor._try_sync()
        # window 3's claim holds block 33's id against block 34's commit
        # first (the pool had it); then window 2's verdict: one debit
        assert _rise(redos0, _redos()) == {"block_id": 1, "verdict": 1}
        assert fed.debited == [fed.server(33)] != [fed.server(17)]
        assert fed.store.height == 31
        assert FASTSYNC_PREFIX_BLOCKS_APPLIED.value - prefix0 == 15
        assert fed.reactor.pool.height == 32
        assert fed.reactor.pool.peek(1)[0].hash() == fed.sim.blocks[31].hash()
        assert fed.store.load_block(32) is None and fed.store.load_block(33) is None
        # depth 1 joins window 2 before anything holds block 33's id
        # against a commit: the verdict alone names it
        fed = _FedByHand(forged={33: _flip_sig}, depth=1)
        redos0 = _redos()
        fed.reactor._try_sync()
        assert _rise(redos0, _redos()) == {"verdict": 1}
        assert fed.debited == [fed.server(33)] and fed.store.height == 31

    @pytest.mark.parametrize("position", range(2, 35))
    def test_a_forged_block_debits_its_server_alone_at_every_position(self, position):
        """Three peers serve round-robin and one forges the block at
        `position` (`flip_sig`: one signature bit of its last_commit), at
        every position of the first two windows: the debited peers are
        the plain reference's liars, once; the store stands at the
        reference's limit before anything is fetched again; the liar's
        blocks are gone from the pool and nobody else's; and with the
        freed heights served again by the others the sync ends at the
        chain's head."""
        from tendermint_tpu.telemetry.metrics import (
            FASTSYNC_REDO_BLOCKS_DROPPED,
            FASTSYNC_REDO_RECOVER_SECONDS,
        )

        fed = _FedByHand(forged={position: _flip_sig})
        liars, applied_limit = plain_reference(fed.served, fed.record)
        assert liars == [fed.server(position)] and applied_limit == position - 2
        others0 = FASTSYNC_REDO_BLOCKS_DROPPED.labels(whose="others").value
        recovered0 = FASTSYNC_REDO_RECOVER_SECONDS.value["count"]
        fed.reactor._try_sync()
        assert fed.debited == liars
        assert fed.store.height == applied_limit
        pool = fed.reactor.pool
        left = {h: server for h, (_, server) in pool._blocks.items()}
        assert left == {
            h: server for h, server, _ in fed.served
            if h > applied_limit and server not in liars
        }
        assert FASTSYNC_REDO_BLOCKS_DROPPED.labels(whose="others").value == others0
        # the refetch: the heights the pool forgot, from the peers left
        assert pool.num_peers() == 2
        requests, evictions = pool.schedule_requests(now=0.0)
        assert not evictions and liars[0] not in {p for p, _ in requests}
        assert {h for _, h in requests} == {
            h for h, server, _ in fed.served if h > applied_limit and server in liars
        }
        for peer_id, h in requests:
            assert pool.add_block(peer_id, fed.sim.blocks[h - 1])
        while fed.store.height < len(fed.sim.blocks) - 1:
            before = fed.store.height
            fed.reactor._try_sync()
            assert fed.store.height > before
        assert fed.debited == liars
        assert fed.store.load_block(position).hash() == fed.sim.blocks[position - 1].hash()
        assert FASTSYNC_REDO_RECOVER_SECONDS.value["count"] > recovered0

    @pytest.mark.parametrize("how", ["commit_alone", "votes_too"])
    @pytest.mark.parametrize("position", [9, 17])
    def test_a_block_id_changed_by_the_successors_server_debits_that_server(self, position, how):
        """Block i + 1's server changes one bit of the `block_id` its
        last_commit carries (in the commit alone, or in every vote too):
        block i's id no longer matches, but the commit does not verify
        over the id it carries, so block i + 1's server made it up and
        is the one debited; block i stays."""
        fed = _FedByHand(forged={position + 1: _changed_block_id(how)})
        liars, applied_limit = plain_reference(fed.served, fed.record)
        assert liars == [fed.server(position + 1)] != [fed.server(position)]
        redos0 = _redos()
        fed.reactor._try_sync()
        assert _rise(redos0, _redos()) == {"block_id": 1}
        assert fed.debited == liars
        assert fed.store.height == applied_limit == position - 1
        assert fed.reactor.pool.peek(1)[0].hash() == fed.sim.blocks[position - 1].hash()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_a_malformed_commit_is_refused_at_prep_and_its_carrier_debited(self, depth):
        """Block 17, the last of window 1, carries a commit with one
        vote's round changed: `_collect_commit_sigs` refuses entry 15
        before anything is launched. Block 17's server is debited, and
        the window goes on without it: fifteen commits, verified and
        applied."""
        import dataclasses

        def wrong_round(block):
            votes = list(block.last_commit.precommits)
            votes[2] = dataclasses.replace(votes[2], round=votes[2].round + 1)
            return dataclasses.replace(
                block, last_commit=dataclasses.replace(block.last_commit, precommits=votes)
            )

        fed = _FedByHand(forged={17: wrong_round}, depth=depth)
        redos0 = _redos()
        fed.reactor._try_sync()
        assert _rise(redos0, _redos()) == {"prep": 1}
        assert fed.debited == [fed.server(17)] == plain_reference(fed.served, fed.record)[0]
        assert fed.store.height == 15

    @pytest.mark.parametrize("lie", ["flip_sig", "block_id"])
    def test_a_set_boundarys_lone_block_blames_the_successors_server(self, lie):
        """The block before a validator-set change is verified alone, by
        the commit its successor carries (`_sync_one`). A forged commit
        there, or one that carries another `block_id`, is the successor's
        server's lie: it is debited, not the block's own server."""
        from tendermint_tpu.abci.apps import PersistentKVStoreApp

        sim = ChainSim(n_vals=4, app=PersistentKVStoreApp())
        for _ in range(8):
            sim.advance()
        pub = sim.state.validators.validators[0].pub_key.data.hex()
        sim.advance(txs=[f"val:{pub}/25".encode()])
        for _ in range(8):
            sim.advance()
        # the first block under the new set carries the lone block's commit
        after = next(
            h for h in range(2, len(sim.blocks) + 1)
            if sim.blocks[h - 1].header.validators_hash != sim.blocks[h - 2].header.validators_hash
        )
        told = _flip_sig if lie == "flip_sig" else _changed_block_id("commit_alone")
        fed = _FedByHand(forged={after: told}, sim=sim, app=PersistentKVStoreApp())
        assert fed.server(after) != fed.server(after - 1)
        redos0 = _redos()
        fed.reactor._try_sync()
        assert _rise(redos0, _redos()) == {"verdict" if lie == "flip_sig" else "block_id": 1}
        assert fed.debited == [fed.server(after)] == plain_reference(fed.served, fed.record)[0]
        assert fed.store.height == after - 2
        assert fed.reactor.pool.peek(1)[0].hash() == sim.blocks[after - 2].hash()

    def test_valset_rotation_boundary_drains_and_crosses(self):
        """A validator-power rotation mid-chain: pipelined windows never
        span the boundary (validators_hash changes), the pipeline drains,
        `_sync_one` walks the boundary block, and sync continues under
        the new set to the chain head."""
        from tendermint_tpu.abci.apps import PersistentKVStoreApp

        sim = ChainSim(n_vals=4, app=PersistentKVStoreApp())
        for _ in range(20):
            sim.advance()
        pub = sim.state.validators.validators[0].pub_key.data.hex()
        sim.advance(txs=[f"val:{pub}/25".encode()])  # height 21 rotates power
        assert sim.state.validators.hash() != sim.blocks[0].header.validators_hash
        for _ in range(19):
            sim.advance()
        reactor, state, store = _pipelined_reactor(
            sim, depth=2, app=PersistentKVStoreApp()
        )
        reactor._try_sync()
        assert store.height == 39
        assert state.validators.hash() == sim.state.validators.hash()

    def test_device_faults_mid_pipeline_fall_back_in_order(self):
        """TENDERMINT_TPU_DEVICE_FAIL mid-pipeline: faulted in-flight
        window launches resolve via host re-verify inside their handles
        and the sync completes — every apply in height order (any
        reorder would break the app_hash/validators_hash lineage and
        stall the sync short of the head)."""
        from tendermint_tpu.services.resilient import ResilientVerifier
        from tendermint_tpu.services.verifier import TableBatchVerifier
        from tendermint_tpu.utils import fail
        from tendermint_tpu.utils.circuit import CircuitBreaker

        sim = ChainSim(n_vals=4)
        for _ in range(48):
            sim.advance()
        verifier = ResilientVerifier(
            TableBatchVerifier(min_device_batch=10**6),
            breaker=CircuitBreaker(failure_threshold=100, reset_timeout_s=60),
        )
        fail.clear_device_faults()
        fail.set_device_fault("verify", 2)  # first two window launches fault
        try:
            reactor, _state, store = _pipelined_reactor(
                sim, depth=2, verifier=verifier
            )
            reactor._try_sync()
        finally:
            fail.clear_device_faults()
        assert store.height == 47
        assert verifier._dispatch.fallback_calls == 2


def _verifier(kind: str):
    from tendermint_tpu.services.verifier import HostBatchVerifier, TableBatchVerifier
    from tests.test_commit_bytes import FlatRecorder, GridRecorder

    return {
        "flat_async": HostBatchVerifier,
        "grid_async": lambda: TableBatchVerifier(min_device_batch=10**9),
        # `verify_batch` alone (no async surface: a window's handle is a
        # `CompletedHandle`), and the synchronous commit-grid surface
        "flat_sync": FlatRecorder,
        "grid_sync": GridRecorder,
    }[kind]()


class TestTheVerdictNamesItsEntry:
    """A refused commit of a batch is data, not text (`ErrCommitRefused`):
    on every path a verdict takes, for a bad signature, for too little
    power and for a malformed commit alike."""

    FAULTS = ("signature", "power", "malformed")

    @staticmethod
    def window(fault: str, at: int):
        """Five commits of one chain, the one at index `at` faulted."""
        import dataclasses

        from tendermint_tpu.types import BlockID

        sim = _chain_of_40()
        entries = []
        for i in range(5):
            block, commit = sim.blocks[i], sim.blocks[i + 1].last_commit
            votes = list(commit.precommits)
            if i == at and fault == "signature":
                votes[2] = votes[2].with_signature(votes[1].signature)
            elif i == at and fault == "power":
                votes[0] = votes[3] = None
            elif i == at and fault == "malformed":
                votes[2] = dataclasses.replace(votes[2], round=votes[2].round + 1)
            block_id = BlockID(block.hash(), block.make_part_set().header)
            entries.append(
                (block_id, block.header.height, dataclasses.replace(commit, precommits=votes))
            )
        return sim, entries

    @pytest.mark.parametrize("kind", ["flat_async", "grid_async", "flat_sync", "grid_sync"])
    @pytest.mark.parametrize("fault", FAULTS)
    @pytest.mark.parametrize("path", ["batched", "batched_async"])
    def test_on_every_path(self, path, fault, kind):
        from tendermint_tpu.types.errors import ErrCommitRefused

        sim, entries = self.window(fault, at=3)
        vals = sim.state.validators
        with pytest.raises(ErrCommitRefused) as refused:
            if path == "batched":
                vals.verify_commit_batched(CHAIN, entries, _verifier(kind))
            else:
                vals.verify_commit_batched_async(CHAIN, entries, _verifier(kind)).result()
        e = refused.value
        assert (e.entry, e.height) == (3, 4)
        assert e.validator == (2 if fault == "signature" else None)
        # the tally walked entries 0..2 before it refused; a malformed
        # commit is found before anything is verified
        assert e.prefix_verified is (fault != "malformed")
        if fault == "signature":
            # what benchmark/lib/checks.py REFUSED parses
            assert str(e) == "invalid commit signature from validator 2 (batch entry 3, height 4)"
        elif fault == "power":
            assert str(e).startswith("insufficient voting power: 20 of 40 (batch entry 3")

    def test_one_commit_alone_says_no_entry_in_its_message(self):
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.types.errors import ErrCommitRefused

        sim, entries = self.window("signature", at=0)
        block_id, height, commit = entries[0]
        with pytest.raises(ErrCommitRefused, match="from validator 2$") as refused:
            sim.state.validators.verify_commit(
                CHAIN, block_id, height, commit, HostBatchVerifier()
            )
        assert (refused.value.entry, refused.value.height) == (0, 1)


def _serving_node(sim: ChainSim, store: BlockStore):
    """A node that serves `store` over the blockchain channel."""
    sw = Switch(NodeInfo(node_id="server", moniker="server", chain_id=CHAIN))
    reactor = BlockchainReactor(
        state=sim.state, store=store, app_conn=sim.conns.consensus, fast_sync=False
    )
    sw.add_reactor("blockchain", reactor)
    sw.start()
    return sw


class TestFastSyncEndToEnd:
    @pytest.mark.slow
    def test_syncs_200_block_store_into_fresh_node(self):
        # build a 200-block chain and store it
        sim = ChainSim(n_vals=4)
        store = BlockStore(MemDB())
        for _ in range(200):
            block = sim.advance()
            parts = block.make_part_set()
            store.save_block(block, parts, sim.commits[-1])
        assert store.height == 200

        server = _serving_node(sim, store)

        # fresh node: genesis state, empty store
        db = MemDB()
        fresh_state = make_genesis_state(db, sim.genesis)
        fresh_state.save()
        fresh_store = BlockStore(MemDB())
        conns = local_client_creator(KVStoreApp())()
        caught_up = []
        client_reactor = BlockchainReactor(
            state=fresh_state,
            store=fresh_store,
            app_conn=conns.consensus,
            fast_sync=True,
            on_caught_up=lambda st: caught_up.append(st.last_block_height),
        )
        client = Switch(NodeInfo(node_id="fresh", moniker="fresh", chain_id=CHAIN))
        client.add_reactor("blockchain", client_reactor)
        client.start()
        try:
            connect_switches(server, client)
            wait_until(
                lambda: fresh_store.height >= 199,
                timeout=90,
                msg="fresh node synced",
            )
            # state replicated: same app hash lineage and validators
            assert fresh_state.last_block_height >= 199
            for h in (1, 50, 199):
                assert (
                    fresh_store.load_block(h).hash() == store.load_block(h).hash()
                )
            # windows were batch-verified, not one-by-one (the device
            # batching seam): blocks_synced counts applies
            assert client_reactor.blocks_synced >= 199
            wait_until(lambda: bool(caught_up), timeout=30, msg="caught-up fired")
        finally:
            server.stop()
            client.stop()
