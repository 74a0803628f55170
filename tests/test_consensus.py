"""Consensus state machine: progress, locking safety, WAL crash recovery.

Port of the reference harness pattern (`consensus/common_test.go`):
MockTicker fires only height-start timeouts; tests drive all other
transitions by injecting signed votes directly.
"""

import queue
import threading
import time

import pytest

from tendermint_tpu.abci.apps import KVStoreApp
from tendermint_tpu.abci.client import local_client_creator
from tendermint_tpu.blockchain import BlockStore
from tendermint_tpu.consensus import (
    ConsensusConfig,
    ConsensusState,
    MockTicker,
    TimeoutTicker,
)
from tendermint_tpu.consensus.round_state import RoundStepType
from tendermint_tpu.consensus.wal import WAL, EndHeightMessage, MsgRecord
from tendermint_tpu.db.kv import MemDB
from tendermint_tpu.state import make_genesis_state
from tendermint_tpu.types import events as ev
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.priv_validator import PrivValidator
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE, Vote

from tests.helpers import make_genesis

CHAIN = "cons-test"


class Fixture:
    """One in-process consensus node + scripted co-validators."""

    def __init__(
        self,
        n_vals=4,
        wal_path=None,
        db=None,
        store_db=None,
        config=None,
        real_ticker=False,
        verifier=None,
    ):
        self.genesis, self.privs = make_genesis(n_vals, chain_id=CHAIN)
        self.db = db if db is not None else MemDB()
        self.store = BlockStore(store_db if store_db is not None else MemDB())
        state = make_genesis_state(self.db, self.genesis)
        state.save()
        self.app = KVStoreApp()
        conns = local_client_creator(self.app)()
        self.config = config or ConsensusConfig.test_config()
        # our validator is privs[0] (valset order)
        self.cs = ConsensusState(
            config=self.config,
            state=state,
            app_conn=conns.consensus,
            block_store=self.store,
            priv_validator=self.privs[0],
            wal_path=wal_path,
            ticker=TimeoutTicker() if real_ticker else MockTicker(),
            verifier=verifier,
        )
        self.events: "queue.Queue[tuple[str, object]]" = queue.Queue()
        for name in (
            ev.EVENT_NEW_ROUND_STEP,
            ev.EVENT_NEW_BLOCK,
            ev.EVENT_LOCK,
            ev.EVENT_UNLOCK,
            ev.EVENT_RELOCK,
            ev.EVENT_POLKA,
        ):
            self.cs.event_switch.add_listener(
                "test", name, lambda data, n=name: self.events.put((n, data))
            )

    def wait_event(self, name, timeout=10.0, pred=None):
        deadline = time.time() + timeout
        while True:
            remaining = deadline - time.time()
            assert remaining > 0, f"timed out waiting for {name}"
            got, data = self.events.get(timeout=remaining)
            if got == name and (pred is None or pred(data)):
                return data

    def wait_step(self, step_name, timeout=10.0):
        return self.wait_event(
            ev.EVENT_NEW_ROUND_STEP, timeout, lambda d: d.step == step_name
        )

    def wait_height(self, height, timeout=20.0):
        while True:
            data = self.wait_event(ev.EVENT_NEW_BLOCK, timeout)
            if data.block.header.height >= height:
                return data.block

    def inject_votes(self, type_, block_id, val_indices, height=None, round_=0):
        """Sign + inject votes from co-validators (scripted signers)."""
        height = height if height is not None else self.cs.height
        for i in val_indices:
            vote = Vote(
                validator_address=self.privs[i].address,
                validator_index=i,
                height=height,
                round=round_,
                timestamp=time.time_ns(),
                type=type_,
                block_id=block_id,
            )
            vote = self.privs[i].sign_vote(CHAIN, vote)
            self.cs.add_vote(vote, peer_id=f"peer{i}")

    def proposal_block_id(self, timeout=10.0):
        """Wait until our node has a complete proposal block; return its id."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            rs = self.cs.get_round_state()
            if rs.proposal_block is not None:
                return BlockID(
                    rs.proposal_block.hash(), rs.proposal_block_parts.header
                )
            time.sleep(0.01)
        raise AssertionError("no complete proposal block")

    def stop(self):
        self.cs.stop()


class TestSoloValidator:
    def test_commits_blocks_alone(self):
        f = Fixture(n_vals=1)
        try:
            f.cs.start()
            block = f.wait_height(3)
            assert block.header.height >= 3
            assert f.store.height >= 3
            assert f.cs.state.last_block_height >= 3
        finally:
            f.stop()

    def test_raising_listener_does_not_stall_consensus(self):
        # EventSwitch.fire must isolate listener exceptions: a raising
        # NewBlock subscriber fires between commit and _schedule_round0,
        # and an escaping exception there would stall the node at the
        # new height (round-2 advisor finding).
        f = Fixture(n_vals=1, real_ticker=True)

        def bomb(_data):
            raise RuntimeError("subscriber bug")

        f.cs.event_switch.add_listener("bomb", ev.EVENT_NEW_BLOCK, bomb)
        try:
            f.cs.start()
            f.wait_height(3)  # keeps committing despite the raising listener
        finally:
            f.stop()

    def test_app_state_follows(self):
        f = Fixture(n_vals=1)
        try:
            f.cs.start()
            f.wait_height(2)
            assert f.app._height >= 2 or f.cs.state.app_hash == b""
        finally:
            f.stop()


class TestPipelinedFinalize:
    """Cross-height pipelined commit (PR 14): the apply launches as a
    dispatch handle, H+1 enters on a speculated state, and the join
    barrier swaps the applied truth in before anything reads it."""

    def test_pipelined_commit_records_overlap_and_joins(self):
        f = Fixture(n_vals=1)
        try:
            f.cs.start()
            f.wait_height(3)
            recs = f.cs.height_ledger.recent()
            pipelined = [r for r in recs if r.get("pipelined")]
            assert pipelined, "no height took the pipelined tail"
            for r in pipelined:
                assert "apply_overlap_s" in r
            assert f.cs.pipeline_stats["joins"] >= len(pipelined)
            # EVENT_NEW_BLOCK fires at the join: applied state visible
            assert f.cs.state.last_block_height >= 3
            assert f.store.height >= 3
        finally:
            f.stop()

    def test_env_opt_out_restores_serial(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_PIPELINE", "0")
        f = Fixture(n_vals=1)
        try:
            assert not f.cs.pipeline_enabled
            f.cs.start()
            f.wait_height(2)
            assert not any(
                r.get("pipelined") for r in f.cs.height_ledger.recent()
            )
        finally:
            f.stop()

    def test_endblock_valset_change_rebuilds_speculation(self):
        """EndBlock rotating the validator set mid-pipeline: the join
        barrier must rebuild the speculated H+1 round state (fresh
        HeightVoteSet against the post-EndBlock set) and consensus must
        keep committing under the new set."""
        from tendermint_tpu.abci.types import Validator as ABCIValidator

        f = Fixture(n_vals=1)
        pub = f.cs.validators.validators[0].pub_key.data
        orig_end_block = f.app.end_block

        def end_block(height):
            orig_end_block(height)
            # bump our own power from height 2 on (idempotent after the
            # first application -> exactly one speculation mismatch)
            return [ABCIValidator(pub, 20)] if height >= 2 else []

        f.app.end_block = end_block
        try:
            f.cs.start()
            f.wait_height(4)
            assert f.cs.pipeline_stats["valset_rebuilds"] >= 1
            assert f.cs.validators.validators[0].voting_power == 20
        finally:
            f.stop()


class TestQuorumProgress:
    def test_four_validators_commit_with_injected_votes(self):
        f = Fixture(n_vals=4)
        try:
            f.cs.start()
            # we are one of 4 proposers; wait for OUR proposal at h1 r0
            # (privs[0] proposes round 0 by accum rotation from genesis)
            bid = f.proposal_block_id()
            f.inject_votes(VOTE_TYPE_PREVOTE, bid, [1, 2, 3])
            f.inject_votes(VOTE_TYPE_PRECOMMIT, bid, [1, 2, 3])
            block = f.wait_height(1)
            assert block.header.height == 1
            # seen commit persisted
            assert f.store.load_seen_commit(1).is_commit()
        finally:
            f.stop()

    def test_commit_step_is_announced_with_the_committed_blocks_parts(self):
        """+2/3 precommit a block we do not have while we still hold the
        complete parts of our own proposal: the new-step event of the
        commit step (where the reactor reads the parts header for its
        CommitStep broadcast) must already see the COMMITTED block's
        empty part set. Announced with the stale proposal's header and
        every bit set, no peer ever sends the block and the node stays
        in commit for good."""
        f = Fixture(n_vals=4)
        seen = []

        def parts_at_commit(data):  # runs on the consensus thread
            if data.step == "Commit":
                parts = f.cs.proposal_block_parts
                seen.append((parts.header, parts.is_complete()))

        f.cs.event_switch.add_listener(
            "parts-at-commit", ev.EVENT_NEW_ROUND_STEP, parts_at_commit
        )
        try:
            f.cs.start()
            own = f.proposal_block_id()
            other = BlockID(b"\x42" * 20, PartSetHeader(1, b"\x43" * 20))
            assert other.parts_header != own.parts_header
            f.inject_votes(VOTE_TYPE_PRECOMMIT, other, [1, 2, 3])
            f.wait_step("Commit")
            assert seen == [(other.parts_header, False)]
            assert f.cs.get_round_state().proposal_block is None
        finally:
            f.stop()

    def test_nil_precommits_go_to_next_round(self):
        f = Fixture(n_vals=4, real_ticker=True)
        try:
            f.cs.start()
            f.proposal_block_id()
            nil = BlockID(b"", PartSetHeader.zero())
            # everyone prevotes+precommits nil -> next round, same height
            f.inject_votes(VOTE_TYPE_PREVOTE, nil, [1, 2, 3])
            f.inject_votes(VOTE_TYPE_PRECOMMIT, nil, [1, 2, 3])
            deadline = time.time() + 10
            while time.time() < deadline:
                rs = f.cs.get_round_state()
                if rs.round >= 1:
                    break
                time.sleep(0.01)
            assert f.cs.get_round_state().round >= 1
            assert f.cs.get_round_state().height == 1
        finally:
            f.stop()


class TestLocking:
    def test_lock_held_against_different_block_next_round(self):
        """Once locked by a polka, we must keep prevoting the locked
        block in later rounds (reference TestLockNoPOL essence)."""
        f = Fixture(n_vals=4, real_ticker=True)
        try:
            f.cs.start()
            bid = f.proposal_block_id()
            # polka for our block at round 0 -> we lock
            f.inject_votes(VOTE_TYPE_PREVOTE, bid, [1, 2, 3])
            f.wait_event(ev.EVENT_LOCK)
            rs = f.cs.get_round_state()
            assert rs.locked_round == 0
            assert rs.locked_block.hash() == bid.hash
            # our own precommit is for the locked block
            pc = f.cs.votes.precommits(0).get_by_address(f.privs[0].address)
            assert pc is not None and pc.block_id.hash == bid.hash
            # drive to round 1 with nil precommits from others
            nil = BlockID(b"", PartSetHeader.zero())
            f.inject_votes(VOTE_TYPE_PRECOMMIT, nil, [1, 2, 3])
            deadline = time.time() + 10
            while time.time() < deadline and f.cs.get_round_state().round < 1:
                time.sleep(0.01)
            # in round 1 we must have prevoted the LOCKED block again
            deadline = time.time() + 10
            pv = None
            while time.time() < deadline:
                pvs = f.cs.votes.prevotes(1)
                pv = pvs.get_by_address(f.privs[0].address) if pvs else None
                if pv is not None:
                    break
                time.sleep(0.01)
            assert pv is not None, "no round-1 prevote from locked validator"
            assert pv.block_id.hash == bid.hash
        finally:
            f.stop()

    def test_unlock_on_nil_polka(self):
        """A +2/3 nil-prevote polka in a later round releases the lock
        (reference TestLockPOLUnlock essence)."""
        f = Fixture(n_vals=4, real_ticker=True)
        try:
            f.cs.start()
            bid = f.proposal_block_id()
            f.inject_votes(VOTE_TYPE_PREVOTE, bid, [1, 2, 3])
            f.wait_event(ev.EVENT_LOCK)
            nil = BlockID(b"", PartSetHeader.zero())
            f.inject_votes(VOTE_TYPE_PRECOMMIT, nil, [1, 2, 3])
            deadline = time.time() + 10
            while time.time() < deadline and f.cs.get_round_state().round < 1:
                time.sleep(0.01)
            # round 1: others polka nil -> we must unlock and precommit nil
            f.inject_votes(VOTE_TYPE_PREVOTE, nil, [1, 2, 3], round_=1)
            f.wait_event(ev.EVENT_UNLOCK)
            rs = f.cs.get_round_state()
            assert rs.locked_block is None and rs.locked_round == -1
        finally:
            f.stop()


class TestProposalHeartbeat:
    def test_heartbeats_fire_while_waiting_for_txs(self):
        """No-empty-blocks mode: the validator emits signed heartbeats
        while the chain idles, sequence increments, signature verifies
        (reference consensus/state.go:686,707-738)."""
        cfg = ConsensusConfig.test_config()
        cfg.create_empty_blocks = False
        cfg.proposal_heartbeat_interval = 0.05
        f = Fixture(n_vals=1, config=cfg)
        hbs: "queue.Queue" = queue.Queue()
        f.cs.event_switch.add_listener(
            "hb-test", ev.EVENT_PROPOSAL_HEARTBEAT, hbs.put
        )
        f.cs.start()
        try:
            first = hbs.get(timeout=5)
            second = hbs.get(timeout=5)
            assert second.sequence > first.sequence
            assert first.validator_address == f.privs[0].address
            assert first.validator_index == 0
            assert f.privs[0].pub_key.verify(
                first.sign_bytes(CHAIN), first.signature
            )
            # consensus is genuinely idle: no block was created
            assert f.cs.height == 1
            assert f.cs.step == RoundStepType.NEW_ROUND
        finally:
            f.cs.stop()

    def test_heartbeat_ws_event_json(self):
        """WS subscribers see heartbeats: the event payload serializes
        to the compact JSON view (height/round/sequence/validator)."""
        from tendermint_tpu.rpc.websocket import event_to_json
        from tendermint_tpu.types.heartbeat import Heartbeat

        hb = Heartbeat(
            validator_address=b"\xab" * 20,
            validator_index=1,
            height=5,
            round=0,
            sequence=3,
            signature=b"\x01" * 64,
        )
        out = event_to_json(ev.EVENT_PROPOSAL_HEARTBEAT, hb)
        assert out == {
            "event": ev.EVENT_PROPOSAL_HEARTBEAT,
            "height": 5,
            "round": 0,
            "sequence": 3,
            "validator": (b"\xab" * 20).hex(),
        }

    def test_heartbeat_message_round_trip(self):
        from tendermint_tpu.consensus.reactor import (
            ProposalHeartbeatMessage,
            decode_message,
        )
        from tendermint_tpu.types.heartbeat import Heartbeat

        hb = Heartbeat(
            validator_address=b"\x11" * 20,
            validator_index=3,
            height=7,
            round=1,
            sequence=42,
            signature=b"\x22" * 64,
        )
        msg = decode_message(ProposalHeartbeatMessage(hb).encode())
        assert isinstance(msg, ProposalHeartbeatMessage)
        assert msg.heartbeat == hb


class TestWALRecovery:
    def test_wal_records_and_endheight(self, tmp_path):
        wal_path = str(tmp_path / "cs.wal")
        f = Fixture(n_vals=1, wal_path=wal_path)
        try:
            f.cs.start()
            f.wait_height(2)
        finally:
            f.stop()
        recs = list(WAL.iter_records(wal_path))
        heights = [r.height for r in recs if isinstance(r, EndHeightMessage)]
        assert 1 in heights and 2 in heights
        votes = [r for r in recs if isinstance(r, MsgRecord) and isinstance(r.msg, Vote)]
        assert votes, "own votes must be WAL'd"

    def test_poisoned_wal_does_not_brick_restart(self, tmp_path):
        """Inputs are WAL'd BEFORE validation, so an invalid peer vote can
        be on disk; replay must tolerate it like the live loop does
        (reference replay.go logs-and-continues) instead of raising out of
        start() on every restart."""
        wal_path = str(tmp_path / "cs.wal")
        db, store_db = MemDB(), MemDB()
        f = Fixture(n_vals=1, wal_path=wal_path, db=db, store_db=store_db)
        try:
            f.cs.start()
            f.wait_height(2)
        finally:
            f.stop()
        from tendermint_tpu.state import load_state

        state = load_state(db)
        h0 = state.last_block_height
        # poison: garbage-signature vote for the in-progress height,
        # appended as if a peer sent it just before the crash
        bad = Vote(
            validator_address=f.privs[0].address,
            validator_index=0,
            height=h0 + 1,
            round=0,
            timestamp=time.time_ns(),
            type=VOTE_TYPE_PREVOTE,
            block_id=BlockID(b"", PartSetHeader.zero()),
            signature=b"\x01" * 64,
        )
        w = WAL(wal_path)
        w.save(MsgRecord(bad, "badpeer"))
        w.close()
        conns = local_client_creator(KVStoreApp())()
        from tendermint_tpu.state.execution import exec_commit_block

        store = BlockStore(store_db)
        for h in range(1, h0 + 1):
            exec_commit_block(conns.consensus, store.load_block(h))
        cs2 = ConsensusState(
            config=ConsensusConfig.test_config(),
            state=state,
            app_conn=conns.consensus,
            block_store=store,
            priv_validator=f.privs[0],
            wal_path=wal_path,
            ticker=TimeoutTicker(),
        )
        got = queue.Queue()
        cs2.event_switch.add_listener("t", ev.EVENT_NEW_BLOCK, lambda d: got.put(d))
        cs2.start()  # must NOT raise on the poisoned record
        try:
            data = got.get(timeout=10)
            assert data.block.header.height == h0 + 1
        finally:
            cs2.stop()

    def test_restart_resumes_from_wal_and_store(self, tmp_path):
        wal_path = str(tmp_path / "cs.wal")
        db, store_db = MemDB(), MemDB()
        f = Fixture(n_vals=1, wal_path=wal_path, db=db, store_db=store_db)
        try:
            f.cs.start()
            f.wait_height(2)
        finally:
            f.stop()
        # restart on the same dbs + WAL; must pick up after last ENDHEIGHT
        from tendermint_tpu.state import load_state

        state = load_state(db)
        h0 = state.last_block_height
        f2 = Fixture.__new__(Fixture)
        Fixture.__init__(f2, n_vals=1, wal_path=wal_path, db=db, store_db=store_db)
        # __init__ created a fresh genesis state; rebuild cs from saved state
        f2.stop()
        conns = local_client_creator(KVStoreApp())()
        # replay chain into the fresh app (handshake's job; done manually here)
        from tendermint_tpu.state.execution import exec_commit_block

        store = BlockStore(store_db)
        for h in range(1, h0 + 1):
            exec_commit_block(conns.consensus, store.load_block(h))
        # real ticker: if the pre-crash node signed a proposal that never
        # hit the WAL, the privval refuses to re-sign it (reference
        # `types/priv_validator.go:249-251` — proposals include time and
        # can be lost); the node then recovers via the round-1 timeout
        # path, which needs real timeouts to fire.
        cs2 = ConsensusState(
            config=ConsensusConfig.test_config(),
            state=state,
            app_conn=conns.consensus,
            block_store=store,
            priv_validator=f.privs[0],
            wal_path=wal_path,
            ticker=TimeoutTicker(),
        )
        got = queue.Queue()
        cs2.event_switch.add_listener(
            "t", ev.EVENT_NEW_BLOCK, lambda d: got.put(d)
        )
        cs2.start()
        try:
            data = got.get(timeout=10)
            assert data.block.header.height == h0 + 1
        finally:
            cs2.stop()


class CountingVerifier:
    """Host verifier that records every verify_batch size."""

    def __init__(self):
        from tendermint_tpu.services import HostBatchVerifier

        self._inner = HostBatchVerifier()
        self.calls = []

    def verify_batch(self, triples):
        self.calls.append(len(triples))
        return self._inner.verify_batch(triples)


class TestVoteStormBatchDrain:
    def test_storm_verifies_as_one_batch(self):
        """A backlog of same-(height, round, type) votes must be verified
        as one device batch through the accumulate->flush seam instead of
        N batch-of-one calls (VERDICT r4 weak #8, SURVEY §7 hard part 3);
        per-vote attribution is preserved — a planted bad signature still
        only rejects its own vote."""
        n = 1000
        v = CountingVerifier()
        f = Fixture(n_vals=n, verifier=v)
        try:
            # enqueue the full storm BEFORE the loop starts so it is one
            # consecutive backlog run (prevote nil, height 1, round 0)
            bad_index = None
            for i in range(1, n):  # privs[0] is the node itself
                vote = Vote(
                    validator_address=f.privs[i].address,
                    validator_index=i,
                    height=1,
                    round=0,
                    timestamp=time.time_ns(),
                    type=VOTE_TYPE_PREVOTE,
                    block_id=BlockID.zero(),
                )
                vote = f.privs[i].sign_vote(CHAIN, vote)
                if bad_index is None:
                    # corrupt the FIRST storm vote's signature
                    import dataclasses

                    bad_index = i
                    vote = dataclasses.replace(
                        vote,
                        signature=vote.signature[:8]
                        + bytes([vote.signature[8] ^ 1])
                        + vote.signature[9:],
                    )
                f.cs.add_vote(vote, peer_id=f"peer{i}")
            f.cs.start()
            deadline = time.time() + 30
            while time.time() < deadline:
                pv = f.cs.votes.prevotes(0) if f.cs.votes else None
                if pv is not None and pv.bit_array().count() >= n - 2:
                    break
                time.sleep(0.05)
            pv = f.cs.votes.prevotes(0)
            # every good vote tallied; the corrupted one rejected
            assert pv.bit_array().count() >= n - 2
            assert pv.get_by_index(bad_index) is None
            assert pv.get_by_index(bad_index + 1) is not None
            # ONE big batched verify replaced ~n singles: the storm may
            # split across a few drains (loop races the enqueue tail, the
            # bad lane re-verifies solo) but must not degrade to singles
            big = [c for c in v.calls if c >= f.cs.VOTE_DRAIN_MIN]
            assert sum(big) >= (n - 1) * 0.9, (len(v.calls), v.calls[:10])
            assert len(v.calls) <= 20, f"{len(v.calls)} verify calls"
        finally:
            f.stop()
