"""Fast-sync: BlockPool scheduling + syncing a 200-block store into a
fresh node over the p2p network with window-batched commit verification
(reference `blockchain/pool_test.go`, `blockchain/reactor.go:191-289`;
BASELINE config 3 shape).
"""

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
        import types

        pool = BlockPool(start_height=1)
        pool.set_peer_height("p1", 10)
        pool.schedule_requests(now=0.0)
        for h in range(1, 4):
            blk = types.SimpleNamespace(header=types.SimpleNamespace(height=h))
            pool.add_block(pool._requests[h].peer_id, blk)
        assert len(pool.peek(3)) == 3
        bad = pool.redo(2)
        assert bad == "p1"
        assert len(pool.peek(3)) == 1  # height 1 survives


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
        apply; the broken suffix must be dropped un-applied."""
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
        reactor, _state, store = _pipelined_reactor(sim, depth=2)
        reactor._try_sync()
        # window 1 = heights 1..17 peeked, 16 applied; the redo at
        # height 20 dropped the pool suffix before it could ever apply
        assert store.height == 16
        assert store.load_block(20) is None
        assert reactor.pool.height == 17
        # the bad suffix is gone from the pool: nothing stale remains
        assert all(b.header.height < 20 for b in reactor.pool.peek(50))

    def test_forged_verdict_mid_pipeline_drains_without_applying(self):
        """Window 2's commit signatures are forged: its verdict fails at
        the JOIN (after younger windows were already submitted) — the
        older window applies, the failed one and everything behind it
        drain un-applied."""
        sim = ChainSim(n_vals=4)
        for _ in range(40):
            sim.advance()
        # forge quorum-breaking signatures in height 20's commit (rides
        # in blocks[20].last_commit); linkage stays intact so the fault
        # surfaces at verdict-join time, not prep time
        commit = sim.blocks[20].last_commit
        for i in range(3):
            commit.precommits[i] = commit.precommits[i].with_signature(bytes(64))
        reactor, _state, store = _pipelined_reactor(sim, depth=2)
        reactor._try_sync()
        assert store.height == 16  # window 1 applied, window 2 rejected
        assert store.load_block(17) is None
        assert store.load_block(20) is None

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
