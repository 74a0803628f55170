"""Adversarial-input hardening at the p2p layer: misbehavior scoring,
bans, reader-thread resilience to malformed frames, and the bounded
per-peer claim tracking in the vote sets (ISSUE 9 satellites).
"""

from __future__ import annotations

import time

import pytest

from tendermint_tpu.p2p.connection import MAX_FRAME_SIZE, ChannelDescriptor, build_frame
from tendermint_tpu.p2p.peer import NodeInfo
from tendermint_tpu.p2p.score import MISBEHAVIOR_WEIGHTS, PeerScorer
from tendermint_tpu.p2p.switch import Reactor, Switch, connect_switches
from tendermint_tpu.p2p.transport import pipe_pair
from tendermint_tpu.telemetry import REGISTRY

CHAIN = "score-chain"


def wait_until(pred, timeout=10.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


class EchoReactor(Reactor):
    def __init__(self, chan=0x10):
        super().__init__()
        self.chan = chan
        self.received: list[bytes] = []

    def get_channels(self):
        return [ChannelDescriptor(self.chan)]

    def receive(self, chan_id, peer, payload):
        if payload == b"explode":
            raise ValueError("bad payload")
        self.received.append(payload)


def make_switch(n, reactor=None):
    sw = Switch(NodeInfo(node_id=f"peer{n}", moniker=f"p{n}", chain_id=CHAIN))
    sw.add_reactor("echo", reactor if reactor is not None else EchoReactor())
    sw.start()
    return sw


class TestPeerScorer:
    def test_accumulates_and_bans_at_threshold(self):
        clock = [0.0]
        s = PeerScorer(threshold=100, half_life_s=60, clock=lambda: clock[0])
        assert not s.debit("p", "bad_sig")  # 10
        for _ in range(8):
            s.debit("p", "bad_sig")
        assert not s.is_banned("p")
        assert s.debit("p", "bad_sig")  # crosses 100
        assert s.is_banned("p")

    def test_score_decays_with_half_life(self):
        clock = [0.0]
        s = PeerScorer(threshold=100, half_life_s=10, clock=lambda: clock[0])
        s.debit("p", "bad_frame")  # 25
        clock[0] = 10.0
        assert s.score("p") == pytest.approx(12.5)
        clock[0] = 1000.0
        assert s.score("p") < 0.01  # honest noise is forgiven

    def test_ban_expires(self):
        clock = [0.0]
        s = PeerScorer(ban_duration_s=30, clock=lambda: clock[0])
        s.ban("p")
        assert s.is_banned("p")
        clock[0] = 31.0
        assert not s.is_banned("p")

    def test_severe_kinds_ban_fast(self):
        s = PeerScorer(threshold=100)
        # a forged block cannot be produced honestly: one offense bans
        assert s.debit("liar", "forged_block")
        assert s.is_banned("liar")

    def test_weights_cover_the_registered_kinds(self):
        for kind in (
            "bad_frame",
            "oversize_frame",
            "bad_msg",
            "bad_sig",
            "bad_vote",
            "forged_block",
            "bad_evidence",
            "flood",
        ):
            assert MISBEHAVIOR_WEIGHTS[kind] > 0


class TestSwitchMisbehavior:
    def test_threshold_ban_disconnects_and_refuses_reconnect(self):
        a, b = make_switch(1), make_switch(2)
        try:
            connect_switches(a, b)
            assert a.n_peers() == 1
            for _ in range(20):
                a.report_misbehavior("peer2", "bad_sig")
            wait_until(lambda: a.n_peers() == 0, msg="banned peer dropped")
            assert a.scorer.is_banned("peer2")
            with pytest.raises(ValueError, match="banned"):
                connect_switches(a, b)
        finally:
            a.stop()
            b.stop()

    def test_reactor_exception_scores_and_drops_peer(self):
        bans_before = REGISTRY.counter_value(
            "tendermint_p2p_peer_misbehavior_total", kind="bad_msg"
        )
        a, b = make_switch(3), make_switch(4)
        try:
            connect_switches(a, b)
            pb = b.peers()[0]
            pb.try_send(0x10, b"explode")
            wait_until(lambda: a.n_peers() == 0, msg="offender dropped")
            assert (
                REGISTRY.counter_value(
                    "tendermint_p2p_peer_misbehavior_total", kind="bad_msg"
                )
                > bans_before
            )
            assert not a.scorer.is_banned("peer4")  # one offense != ban
        finally:
            a.stop()
            b.stop()


class TestReaderResilience:
    """Satellite regression: a malformed/truncated/oversized frame from
    a peer must disconnect THAT peer (debiting its score) — never crash
    or wedge the recv loop."""

    def _victim_with_raw_peer(self, reactor=None, node_id="raw-peer"):
        victim = make_switch(5, reactor)
        ea, eb = pipe_pair()
        victim.add_peer_endpoint(
            NodeInfo(node_id=node_id, moniker="raw", chain_id=CHAIN),
            ea,
            outbound=False,
        )
        return victim, eb

    def test_malformed_frame_drops_only_offender(self):
        reactor = EchoReactor()
        victim, raw = self._victim_with_raw_peer(reactor)
        honest = make_switch(6)
        before = REGISTRY.counter_value(
            "tendermint_p2p_peer_misbehavior_total", kind="bad_frame"
        )
        try:
            connect_switches(victim, honest)
            assert victim.n_peers() == 2
            # length-field lie: declares a huge payload that isn't there
            raw.send(b"\x10\xff\xff\xff\xff\x7f")
            wait_until(lambda: victim.n_peers() == 1, msg="offender dropped")
            assert (
                REGISTRY.counter_value(
                    "tendermint_p2p_peer_misbehavior_total", kind="bad_frame"
                )
                > before
            )
            # the switch (and the honest peer's reader) still works
            honest.peers()[0].try_send(0x10, b"still-alive")
            wait_until(
                lambda: b"still-alive" in reactor.received, msg="honest traffic flows"
            )
        finally:
            victim.stop()
            honest.stop()

    def test_oversize_frame_drops_peer(self):
        victim, raw = self._victim_with_raw_peer(node_id="raw-big")
        before = REGISTRY.counter_value(
            "tendermint_p2p_peer_misbehavior_total", kind="oversize_frame"
        )
        try:
            assert victim.n_peers() == 1
            raw.send(b"\x00" * (MAX_FRAME_SIZE + 1))
            wait_until(lambda: victim.n_peers() == 0, msg="oversize sender dropped")
            assert (
                REGISTRY.counter_value(
                    "tendermint_p2p_peer_misbehavior_total", kind="oversize_frame"
                )
                > before
            )
        finally:
            victim.stop()

    def test_repeat_bad_frame_offender_gets_banned(self):
        """Reconnect-and-garbage cycling is not free: frame offenses
        accumulate on the node id and end in a ban."""
        victim = make_switch(7)
        try:
            for i in range(6):
                ea, eb = pipe_pair()
                try:
                    victim.add_peer_endpoint(
                        NodeInfo(node_id="cycler", moniker="c", chain_id=CHAIN),
                        ea,
                        outbound=False,
                    )
                except ValueError:
                    break  # banned mid-cycle: exactly the point
                eb.send(b"\x10\xff\xff\xff\xff\x7f")
                wait_until(lambda: victim.n_peers() == 0, msg="dropped")
            assert victim.scorer.is_banned("cycler")
        finally:
            victim.stop()


class TestVoteSetClaimBounds:
    """Satellite regression: peer maj23 claims cannot grow unbounded
    per-round/per-height state."""

    def _vote_set(self):
        from tendermint_tpu.testing.nemesis import make_genesis
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.db.kv import MemDB
        from tendermint_tpu.types.vote import VOTE_TYPE_PREVOTE
        from tendermint_tpu.types.vote_set import VoteSet

        genesis, privs = make_genesis(4, chain_id=CHAIN)
        state = make_genesis_state(MemDB(), genesis)
        return (
            VoteSet(CHAIN, 1, 0, VOTE_TYPE_PREVOTE, state.validators),
            state.validators,
        )

    def test_claim_created_tallies_are_capped(self):
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.part_set import PartSetHeader
        from tendermint_tpu.types.vote_set import VoteSet

        vs, _vals = self._vote_set()
        for i in range(200):
            vs.set_peer_maj23(
                f"flooder{i}",
                BlockID(i.to_bytes(20, "big"), PartSetHeader.zero()),
            )
        # empty claim-tallies evicted past the cap (+1 for the newest)
        assert len(vs.votes_by_block) <= VoteSet.MAX_PEER_CLAIMS + 1

    def test_height_vote_set_refuses_round_claim_flood(self):
        from tendermint_tpu.consensus.round_state import HeightVoteSet
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.part_set import PartSetHeader
        from tendermint_tpu.types.vote import VOTE_TYPE_PREVOTE

        _, vals = self._vote_set()
        hvs = HeightVoteSet(CHAIN, 1, vals)
        bid = BlockID(b"\x01" * 20, PartSetHeader.zero())
        for r in range(2, 500):
            hvs.set_peer_maj23(r, VOTE_TYPE_PREVOTE, "flooder", bid)
        # 1 base round pair + round 1 (catchup window) + 2 per-peer
        # catchup rounds: far below the 500 a flood asked for
        assert len(hvs._round_vote_sets) <= 6
