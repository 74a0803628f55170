"""The BFT consensus state machine (reference `consensus/state.go`).

Architecture: an event-sourced core — ONE thread (`_receive_loop`) owns
the RoundState and serializes every input (peer message, internal
message, timeout tock) exactly like the reference's `receiveRoutine`
(`consensus/state.go:497-547`). Every input is WAL'd before processing.
Public methods only enqueue; reads take a snapshot under the state lock.

Transitions follow `consensus/state.go`: NewHeight → NewRound → Propose
→ Prevote → PrevoteWait → Precommit → PrecommitWait → Commit, with the
POL lock/unlock safety rules (`:963-1053`) and commit finalization
(`:1078-1243`). Signature verification inside VoteSet/verify_commit
routes through the BatchVerifier seam (TPU batch when available).

Test seams (reference `consensus/state.go:107-110` + common_test.go):
`decide_proposal_fn` / `do_prevote_fn` / `set_proposal_fn` are
overridable, and any ticker implementing schedule/set_on_timeout/stop
can be injected (MockTicker drives deterministic tests).
"""

from __future__ import annotations

import os
import queue
import threading
import time as time_mod
from dataclasses import dataclass

from tendermint_tpu.consensus.config import ConsensusConfig
from tendermint_tpu.consensus.round_state import HeightVoteSet, RoundState, RoundStepType
from tendermint_tpu.consensus.ticker import AdaptiveTimeouts, TimeoutInfo, TimeoutTicker
from tendermint_tpu.consensus.wal import (
    WAL,
    EndHeightMessage,
    MsgRecord,
    RoundStateRecord,
    TimeoutRecord,
)
from tendermint_tpu.state import apply_block
from tendermint_tpu.state.state import State
from tendermint_tpu.types import events as ev
from tendermint_tpu.types.block import Block, Commit
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.heartbeat import Heartbeat
from tendermint_tpu.types.errors import (
    ErrDoubleSign,
    ErrVoteConflictingVotes,
    ErrVoteInvalidSignature,
    ErrVoteInvalidValidatorAddress,
    ErrVoteInvalidValidatorIndex,
    ErrVoteNonDeterministicSignature,
    ErrVoteUnexpectedStep,
    FatalConsensusError,
    ValidationError,
)
from tendermint_tpu.types.part_set import Part, PartSet, PartSetHeader
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.services import NopMempool
from tendermint_tpu.types.tx import Txs
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE, Vote
from tendermint_tpu.types.vote_set import VoteSet
from tendermint_tpu.telemetry import TRACER
from tendermint_tpu.telemetry import heightlog as _heightlog
from tendermint_tpu.telemetry import metrics as _metrics
from tendermint_tpu.telemetry import tracectx as _trace
from tendermint_tpu.telemetry.flightrec import FLIGHT
from tendermint_tpu.utils.fail import fail_point
from tendermint_tpu.utils.lockrank import ranked_rlock
from tendermint_tpu.utils import log as _log_mod
import logging as _logging

_SENTINEL = object()
# receive-loop-internal marker: "no new input — join the oldest
# in-flight vote-batch preverify instead"
_JOIN = object()
# receive-loop-internal marker: "queue idle — join the pending
# pipelined apply now" (an idle loop delays nothing by joining, and
# the height's ledger record / commit events land promptly instead of
# waiting for the next height's first barrier)
_JOIN_APPLY = object()


@dataclass
class _TxsAvailable:
    height: int


class ConsensusState:
    def __init__(
        self,
        config: ConsensusConfig,
        state: State,
        app_conn,
        block_store,
        mempool=None,
        priv_validator=None,
        event_switch=None,
        wal_path: str | None = None,
        ticker=None,
        verifier=None,
        tx_indexer=None,
        hasher=None,
        evidence_pool=None,
        heightlog=None,
    ) -> None:
        self.config = config
        self.app_conn = app_conn
        self.block_store = block_store
        self.mempool = mempool if mempool is not None else NopMempool()
        self.priv_validator = priv_validator
        self.event_switch = event_switch if event_switch is not None else ev.EventSwitch()
        self.verifier = verifier
        self.tx_indexer = tx_indexer
        # Byzantine accountability: ErrVoteConflictingVotes sites feed
        # DuplicateVoteEvidence here; proposals reap it; commits retire
        # it. None = detection still logs/records, proof is dropped
        # (the pre-evidence behavior).
        self.evidence_pool = evidence_pool
        if evidence_pool is not None:
            evidence_pool.chain_id = state.chain_id
            if evidence_pool.verifier is None:
                evidence_pool.verifier = verifier
            evidence_pool.val_set_fn = self._evidence_val_set
            evidence_pool.best_height_fn = lambda: self.height
        # reactor-wired hook: fn(peer_id, kind, detail) — classified
        # adversarial vote input debits the sending peer's p2p
        # misbehavior score (equivocation is VALIDATOR fault and goes to
        # the evidence pool instead; the relaying peer did nothing wrong)
        self.on_peer_misbehavior = None
        # TreeHasher for proposal-block data_hash/part-set builds; None = host
        # merkle (reference SimpleHash call sites `types/block.go:177`).
        self.hasher = hasher
        self.wal = WAL(wal_path, light=config.wal_light) if wal_path else None

        self._queue: "queue.Queue" = queue.Queue()
        self._vote_dispatch = None  # lazy DispatchQueue for vote preverify
        # The lowest-ranked lock in the process (lockrank
        # "consensus.state"): held across mempool update/lock, evidence
        # admission, and verify-spine joins, so everything it reaches
        # must rank above it.
        self._mtx = ranked_rlock("consensus.state")
        self._thread: threading.Thread | None = None
        self._running = False
        # Set when an internal invariant/persistence failure halts the
        # loop (the reference panics instead; see FatalConsensusError).
        self.fatal_error: BaseException | None = None

        self.ticker = ticker if ticker is not None else TimeoutTicker()
        self.ticker.set_on_timeout(self._enqueue_timeout)

        # test hooks (reference overridable fields `consensus/state.go:107-110`)
        self.decide_proposal_fn = self._default_decide_proposal
        self.do_prevote_fn = self._default_do_prevote
        self.set_proposal_fn = self._default_set_proposal

        # RoundState
        self.state: State = None  # type: ignore  # set by _update_to_state
        self.height = 0
        self.round = 0
        self.step = RoundStepType.NEW_HEIGHT
        self.start_time = 0.0
        self.commit_time = 0.0
        self.validators = None
        self.proposal: Proposal | None = None
        self.proposal_block: Block | None = None
        self.proposal_block_parts: PartSet | None = None
        self.locked_round = -1
        self.locked_block: Block | None = None
        self.locked_block_parts: PartSet | None = None
        self.votes: HeightVoteSet | None = None
        self.commit_round = -1
        self.last_commit: VoteSet | None = None

        # telemetry: the open round-phase span and the height stopwatch
        # (observed into tendermint_consensus_phase_seconds /
        # _height_seconds and the span tracer on every transition)
        self._phase_name: str | None = None
        self._phase_started = time_mod.monotonic()
        self._height_started = time_mod.monotonic()
        # finality observatory: one ledger record per committed height
        # (phase durations, wait-vs-work split, critical-path label,
        # laggard validator) — an injected ledger persists under the
        # node's data dir; the default is an in-memory ring.
        self.height_ledger = (
            heightlog if heightlog is not None else _heightlog.HeightLedger()
        )
        self.vote_arrivals = _heightlog.VoteArrivalRollup()
        # gossip observatory: the switch-owned GossipRollup, wired by
        # the consensus reactor's on_start (None standalone) — vote/part
        # duplicate adds and first-seen propagation stamps land here
        self.gossip = None
        self._last_commit_wall: float | None = None
        self._phase_acc: dict[str, list] = {}  # phase -> [dur_s, work_s]
        self._height_work0 = _heightlog.work_totals()
        self._phase_work0 = self._height_work0
        self._val_arrivals: dict[int, tuple[str, float]] = {}
        self._apply_s = 0.0
        # measured-latency timeout policy (falls back to the fixed
        # config ladder while cold or opted out)
        self.timeouts = AdaptiveTimeouts(
            config, rollup=self.vote_arrivals, ledger=self.height_ledger
        )
        # Cross-height pipeline: while height H's apply flies on the
        # apply dispatch queue, H+1 runs on a speculated state. All
        # fields are owned by the receive-loop thread (finalize, joins,
        # and the batch drain all run there); `stop()` drains after the
        # loop exits.
        self.pipeline_enabled = bool(
            getattr(config, "pipeline_commit", False)
        ) and os.environ.get("TENDERMINT_TPU_PIPELINE", "1") != "0"
        self._pending_apply: dict | None = None
        self._apply_dispatch = None  # lazy depth-1 DispatchQueue("apply")
        # bumped when the join barrier rebuilds the valset (EndBlock
        # changed it): preverify verdicts minted under an older gen are
        # discarded (their sigs bound validator indexes to stale keys)
        self._valset_gen = 0
        # node-local pipeline counters for GET /health (reported, never
        # folded into routing status — same discipline as the SLO)
        self.pipeline_stats = {
            "joins": 0,
            "stalls": 0,
            "valset_rebuilds": 0,
            "overlap_s_total": 0.0,
            "last_overlap_s": 0.0,
        }

        self._update_to_state(state)
        if hasattr(self.mempool, "set_on_txs_available"):
            self.mempool.set_on_txs_available(self._on_txs_available)
        # A brand-new WAL gets an ENDHEIGHT marker for the last committed
        # height so crash recovery of the FIRST in-progress height finds
        # its replay anchor (the reference seeds "#ENDHEIGHT: 0" likewise).
        if self.wal is not None and os.path.getsize(self.wal.path) == 0:
            self.wal.save(EndHeightMessage(state.last_block_height))

    # ------------------------------------------------------------------ API

    def start(self) -> None:
        self._catchup_replay()
        self._running = True
        # named for the contention profiler's subsystem classification
        # (telemetry/profiler.py) — this is THE consensus hot thread
        self._thread = threading.Thread(
            target=self._receive_loop, name="consensus-recv", daemon=True
        )
        self._thread.start()
        self._schedule_round0()

    def update_to_state(self, state: State) -> None:
        """Adopt an externally-advanced state BEFORE start() — the
        fast-sync handoff (reference `SwitchToConsensus
        consensus/reactor.go:79-96` calls updateToState with the synced
        state). Must not be called while the receive loop runs."""
        if self._running:
            raise ValidationError("update_to_state on a running consensus")
        with self._mtx:
            self._update_to_state(state)

    def stop(self) -> None:
        self._running = False
        self.ticker.stop()
        self._queue.put(_SENTINEL)
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._pending_apply is not None:
            # drain the pipeline: the in-flight apply persisted (or
            # failed) on its worker — join so shutdown state on disk is
            # the applied one, never a half-landed height
            with self._mtx:
                try:
                    self._join_apply("shutdown")
                except Exception as e:
                    self.fatal_error = e
                    import traceback

                    traceback.print_exc()
        if self._vote_dispatch is not None:
            self._vote_dispatch.close()
        if self._apply_dispatch is not None:
            self._apply_dispatch.close()
        if self.wal is not None:
            self.wal.close()

    def add_vote(self, vote: Vote, peer_id: str = "") -> None:
        if self.fatal_error is not None:
            return  # halted: nothing drains the queue anymore
        self._queue.put(self._record(vote, peer_id))

    def set_proposal(self, proposal: Proposal, peer_id: str = "") -> None:
        if self.fatal_error is not None:
            return
        self._queue.put(self._record(proposal, peer_id))

    def add_proposal_block_part(
        self, height: int, round_: int, part: Part, peer_id: str = ""
    ) -> None:
        if self.fatal_error is not None:
            return
        self._queue.put(self._record((height, round_, part), peer_id))

    @staticmethod
    def _record(msg, peer_id: str) -> MsgRecord:
        """Stamp the enqueued input with the caller thread's ambient
        trace context + arrival time (the p2p recv loop installs the
        wire context before reactor dispatch) so the receive loop can
        re-establish the context while processing — trace propagation
        survives the thread hop through the queue."""
        return MsgRecord(msg, peer_id, ctx=_trace.current(), arrived=time_mod.time())

    def get_round_state(self) -> RoundState:
        with self._mtx:
            return RoundState(
                height=self.height,
                round=self.round,
                step=self.step,
                start_time=self.start_time,
                commit_time=self.commit_time,
                validators=self.validators,
                proposal=self.proposal,
                proposal_block=self.proposal_block,
                proposal_block_parts=self.proposal_block_parts,
                locked_round=self.locked_round,
                locked_block=self.locked_block,
                locked_block_parts=self.locked_block_parts,
                votes=self.votes,
                commit_round=self.commit_round,
                last_commit=self.last_commit,
                last_validators=self.state.last_validators,
            )

    def is_proposer(self) -> bool:
        if self.priv_validator is None:
            return False
        return self.validators.proposer.address == self.priv_validator.address

    # ----------------------------------------------------------- the loop

    # A backlog of this many same-(height, round, type) votes switches the
    # loop to one batched device verify instead of per-vote singles
    # (SURVEY §7 hard part 3: a 10k-validator vote storm must not verify
    # 10k sigs one at a time on host while the TPU idles).
    # While a pipelined apply is in flight the gate drops to ANY run —
    # votes preverify through the coalescer instead of their tally
    # queuing behind the apply join (measured: the extra thread hops of
    # universal async singles COST latency on an idle loop, so the
    # always-async variant was reverted).
    VOTE_DRAIN_MIN = 8
    VOTE_DRAIN_MAX = 4096
    # Vote-batch preverifies kept in flight: while batch K's signatures
    # fly on device, the loop keeps pulling the queue and drains batch
    # K+1 — verdicts join in drain order before ANY state mutation, so
    # consensus input order is exactly the synchronous loop's.
    VOTE_PIPELINE_DEPTH = 2

    def _receive_loop(self) -> None:
        from collections import deque

        stashed = None
        pending: "deque" = deque()  # (records, handle) batches, drain order
        while self._running:
            if stashed is not None:
                item, stashed = stashed, None
            elif pending:
                # a preverify is in flight: don't block on the queue —
                # either drain more input behind it or join its verdict
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    item = _JOIN
            elif self._pending_apply is not None:
                # no input queued and nothing else in flight: join the
                # pipelined apply rather than sleeping on the queue —
                # the overlap already ran its course
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    item = _JOIN_APPLY
            else:
                item = self._queue.get()
            if item is _SENTINEL:
                # shutting down: join in-flight preverifies so their
                # dispatch slots release; the votes are WAL'd and replay
                # on restart, no state is mutated past this point
                for entry in pending:
                    try:
                        entry[1].result()
                    # tmlint: disable=T001 -- shutdown slot-release join: verdicts are discarded by design, votes replay from the WAL
                    except Exception:
                        pass
                return
            # Opportunistic vote-storm drain: batch the CONSECUTIVE run of
            # queued votes for the same (height, round, type). Consensus
            # message order is otherwise preserved — the drain stops at
            # the first non-matching item and stashes it for next turn.
            batch = None
            if (
                item is not _JOIN
                and isinstance(item, MsgRecord)
                and isinstance(item.msg, Vote)
                and (not self._queue.empty() or self._pending_apply is not None)
            ):
                key = (item.msg.height, item.msg.round, item.msg.type)
                batch = [item]
                while len(batch) < self.VOTE_DRAIN_MAX:
                    try:
                        nxt = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is _SENTINEL:
                        stashed = nxt
                        break
                    if (
                        isinstance(nxt, MsgRecord)
                        and isinstance(nxt.msg, Vote)
                        and (nxt.msg.height, nxt.msg.round, nxt.msg.type) == key
                    ):
                        batch.append(nxt)
                    else:
                        stashed = nxt
                        break
            if batch is not None:
                _metrics.VOTE_DRAIN_BATCH.observe(len(batch))
            try:
                if item is _JOIN:
                    self._join_vote_batch(*pending.popleft())
                elif item is _JOIN_APPLY:
                    with self._mtx:
                        self._join_apply("idle")
                elif batch is not None and (
                    len(batch) >= self.VOTE_DRAIN_MIN
                    # while an apply is in flight, runs of 2+ preverify
                    # asynchronously instead of tallying (and joining)
                    # behind it; singles stay on the cheap sync path —
                    # their tally join costs at most the apply remainder
                    or (self._pending_apply is not None and len(batch) >= 2)
                ):
                    # submit this run's preverify and keep pulling; the
                    # depth bound joins the oldest batch first so state
                    # mutation stays in drain order
                    while len(pending) >= self.VOTE_PIPELINE_DEPTH:
                        self._join_vote_batch(*pending.popleft())
                    pending.append(self._submit_vote_batch(batch))
                else:
                    # ORDER BARRIER: anything that isn't a same-key vote
                    # run (proposals, parts, timeouts, small runs) must
                    # observe every earlier vote's effect — join all
                    # in-flight batches before touching state
                    while pending:
                        self._join_vote_batch(*pending.popleft())
                    if batch is not None:
                        # runs too small to amortize a batch preverify
                        # take the single-vote path, in drain order
                        for rec in batch:
                            self._process_item(rec)
                    else:
                        self._process_item(item)
            except (ErrDoubleSign, FatalConsensusError) as e:
                # Internal failure: halt consensus rather than keep voting
                # from a half-advanced state (reference PanicConsensus —
                # crash recovery takes over on restart). The flight
                # recorder dumps its ring NOW: the events leading into
                # the halt are exactly what the post-mortem needs.
                import traceback

                traceback.print_exc()
                self.fatal_error = e
                self._running = False
                self.ticker.stop()
                FLIGHT.record(
                    "fatal",
                    error=type(e).__name__,
                    height=self.height,
                    round=self.round,
                )
                FLIGHT.dump(reason="consensus-fatal")
                raise
            except Exception:  # a bad peer message must not kill consensus
                import traceback

                traceback.print_exc()

    def _process_item(self, item) -> None:
        with self._mtx:
            # _TxsAvailable is a local wakeup hint, not a consensus
            # input — it is not WAL'd (matches the reference, where
            # txsAvailable arrives on a separate non-WAL'd channel)
            if self.wal is not None and not isinstance(item, _TxsAvailable):
                try:
                    self.wal.save(item)
                except Exception as e:
                    raise FatalConsensusError("WAL write failed") from e
            self._dispatch(item)

    def _process_vote_batch(self, records: list) -> None:
        """One batched verify for a drained same-key vote run, then
        per-vote tallying — the submit+join pipeline stages run
        back-to-back (kept for replay/tests; the receive loop overlaps
        them)."""
        self._join_vote_batch(*self._submit_vote_batch(records))

    def _submit_vote_batch(self, records: list):
        """Pipeline stage 1: WAL the drained run (drain order == WAL
        order == eventual processing order), prep the signature triples
        under the state lock, and launch their batch verify through the
        dispatch queue. No round state is mutated here.

        Trace attribution: the launch runs with the height's block
        context (the proposal's, which the proposer adopted from its
        first traced tx) — or a drained vote's own context — ambient,
        so the coalescer request and the device launch downstream join
        the trace of the block being decided."""
        submitted = time_mod.time()
        exemplar = self._proposal_ctx
        traced = [rec for rec in records if rec.ctx is not None]
        if exemplar is None and traced:
            exemplar = traced[0].ctx
        for rec in traced:
            if rec.arrived:
                _metrics.VOTE_STAGE.labels(stage="drain").observe(
                    submitted - rec.arrived, exemplar=rec.ctx.trace
                )
        FLIGHT.record(
            "vote_batch",
            n=len(records),
            height=self.height,
            round=self.round,
        )
        with self._mtx:
            if self.wal is not None:
                for rec in records:
                    try:
                        self.wal.save(rec)
                    except Exception as e:
                        raise FatalConsensusError("WAL write failed") from e
            with _trace.use(exemplar):
                handle = self._preverify_votes_async(
                    [rec.msg for rec in records],
                    skip={i for i, rec in enumerate(records) if rec.self_signed},
                )
            gen = self._valset_gen
        return records, handle, submitted, exemplar, gen

    def _join_vote_batch(
        self, records: list, handle, submitted: float = 0.0, exemplar=None, gen=None
    ) -> None:
        """Pipeline stage 2: join the verdict mask, then tally each vote
        with the mask deciding which skip the in-set signature check
        (failed lanes re-verify individually so error attribution matches
        the single-vote path exactly). A dispatch-layer failure degrades
        to all-False — every vote just re-verifies in-set. Verdicts
        minted under a stale valset generation (the cross-height join
        barrier rebuilt the set after EndBlock changes) degrade the same
        way — the speculative preverify bound indexes to superseded
        keys, so those votes re-verify against the real set."""
        try:
            verdicts = handle.result()
        except Exception:
            import traceback

            traceback.print_exc()
            verdicts = [False] * len(records)
        joined = time_mod.time()
        if exemplar is not None and submitted:
            _metrics.VOTE_STAGE.labels(stage="verify").observe(
                joined - submitted, exemplar=exemplar.trace
            )
        with self._mtx:
            for rec, ok in zip(records, verdicts):
                self._observe_vote_arrival(rec)
                # re-checked per vote: the first tally's join can rebuild
                # the valset mid-batch. Self-signed votes stay trusted
                # across rebuilds — the signature is this node's own.
                ok = rec.self_signed or (
                    bool(ok) and (gen is None or gen == self._valset_gen)
                )
                try:
                    with _trace.use(rec.ctx):
                        self._handle_vote(
                            rec.msg, rec.peer_id, preverified=ok
                        )
                    if rec.ctx is not None:
                        self._observe_vote_e2e(rec, joined)
                except (ErrDoubleSign, FatalConsensusError):
                    raise
                except Exception:  # per-vote fault isolation, as singles
                    import traceback

                    traceback.print_exc()

    def _observe_vote_e2e(self, rec, done: float) -> None:
        """One traced vote's gossip-arrival → verdict-applied span +
        e2e histogram slice (sampled votes only)."""
        if not rec.arrived:
            return
        v = rec.msg
        _metrics.VOTE_STAGE.labels(stage="e2e").observe(
            done - rec.arrived, exemplar=rec.ctx.trace
        )
        TRACER.add(
            "vote.e2e",
            rec.arrived,
            done,
            trace=rec.ctx.trace,
            origin=rec.ctx.origin,
            height=v.height,
            round=v.round,
            type=v.type,
        )

    def _observe_vote_arrival(self, rec) -> None:
        """Per-peer vote-arrival latency (vote timestamp → local
        arrival) for the rollup + the current height's laggard-validator
        attribution. Replayed WAL records (arrived == 0) are skipped;
        delays are clamped — a byzantine validator controls its own
        timestamps and must not poison the attribution."""
        v = rec.msg
        if not rec.arrived or not isinstance(v, Vote) or v.height != self.height:
            return
        delay = rec.arrived - v.timestamp / 1e9
        if delay < 0.0:
            delay = 0.0  # clock skew / future-stamped vote
        elif delay > _heightlog.MAX_ARRIVAL_S:
            delay = _heightlog.MAX_ARRIVAL_S
        self.vote_arrivals.observe(rec.peer_id or "self", delay)
        _metrics.VOTE_ARRIVAL_SECONDS.observe(delay)
        cur = self._val_arrivals.get(v.validator_index)
        if cur is None or delay > cur[1]:
            self._val_arrivals[v.validator_index] = (
                v.validator_address.hex()[:12],
                delay,
            )

    def _vote_queue(self):
        if self._vote_dispatch is None:
            from tendermint_tpu.services.dispatch import DispatchQueue

            self._vote_dispatch = DispatchQueue(
                depth=max(2, self.VOTE_PIPELINE_DEPTH), name="consensus"
            )
        return self._vote_dispatch

    def _preverify_votes_async(self, votes: list, skip=None):
        """Launch the batch preverify of current-height votes against
        the current validator set; returns a handle resolving to the
        per-vote bool list (False = re-verify individually in-set).
        Triples are prepped NOW — the verdict stays valid however far
        the loop advances before joining, because it binds the votes'
        height to the valset current at that height."""
        verifier = self.verifier
        if verifier is None:
            from tendermint_tpu.services.verifier import default_verifier

            verifier = default_verifier()
        idxs, triples = [], []
        for i, v in enumerate(votes):
            if skip is not None and i in skip:
                continue  # self-signed: trusted without a launch lane
            if v.height != self.height or self.validators is None:
                continue
            val = self.validators.get_by_index(v.validator_index)
            if val is None or val.address != v.validator_address:
                continue
            triples.append(
                (val.pub_key.data, v.sign_bytes(self.state.chain_id), v.signature)
            )
            idxs.append(i)
        out = [False] * len(votes)
        from tendermint_tpu.services.dispatch import CompletedHandle

        if not triples:
            return CompletedHandle(out)

        def _scatter(verdicts):
            for i, ok in zip(idxs, verdicts):
                out[i] = bool(ok)
            return out

        if hasattr(verifier, "verify_batch_async"):
            from tendermint_tpu.services.batcher import consumer_kwargs

            return verifier.verify_batch_async(
                triples,
                queue=self._vote_queue(),
                **consumer_kwargs(verifier, "consensus"),
            ).then(_scatter)
        return CompletedHandle(_scatter(verifier.verify_batch(triples)))

    def _preverify_votes(self, votes: list) -> list[bool]:
        """Synchronous preverify (replay/test seam): submit + join."""
        return self._preverify_votes_async(votes).result()

    def _dispatch(self, item) -> None:
        if isinstance(item, MsgRecord):
            m = item.msg
            # Re-establish the record's trace context for the whole
            # handling scope: events fired from here (EVENT_VOTE /
            # EVENT_COMPLETE_PROPOSAL push-gossip) re-attach it to
            # outbound frames without any reactor plumbing.
            with _trace.use(getattr(item, "ctx", None)):
                if isinstance(m, Vote):
                    self._observe_vote_arrival(item)
                    self._handle_vote(
                        m, item.peer_id, preverified=getattr(item, "self_signed", False)
                    )
                    if item.ctx is not None:
                        self._observe_vote_e2e(item, time_mod.time())
                elif isinstance(m, Proposal):
                    self.set_proposal_fn(m)
                else:
                    height, round_, part = m
                    self._handle_block_part(height, round_, part, item.peer_id)
        elif isinstance(item, TimeoutRecord):
            self._handle_timeout(
                TimeoutInfo(item.duration, item.height, item.round, item.step)
            )
        elif isinstance(item, _TxsAvailable):
            if item.height == self.height and self.step == RoundStepType.NEW_ROUND:
                self._enter_propose(self.height, self.round)

    def _enqueue_timeout(self, ti: TimeoutInfo) -> None:
        self._queue.put(TimeoutRecord(ti.duration, ti.height, ti.round, ti.step))

    def _on_txs_available(self) -> None:
        self._queue.put(_TxsAvailable(self.height))

    # ----------------------------------------------------------- telemetry

    def _observe_phase(self, next_name: str | None) -> None:
        """Close the open round-phase span (histogram + tracer) and open
        `next_name`. Called on every phase transition under the state
        lock; None closes without opening (height finalized).

        Ledger bookkeeping rides the same transitions: per-height phase
        durations accumulate (a phase can repeat across rounds) with a
        wait-vs-work split stitched from the exported verify/hash
        stopwatches, and the gap from height start to the first opened
        phase is the NewHeight wait."""
        now = time_mod.monotonic()
        work = _heightlog.work_totals()
        if self._phase_name is not None:
            dur = now - self._phase_started
            _metrics.CONSENSUS_PHASE_SECONDS.labels(
                phase=self._phase_name
            ).observe(dur)
            wall_end = time_mod.time()
            TRACER.add(
                f"consensus.{self._phase_name}",
                wall_end - dur,
                wall_end,
                height=self.height,
                round=self.round,
            )
            work_s = max(
                0.0,
                (work["verify"] + work["hash"])
                - (self._phase_work0["verify"] + self._phase_work0["hash"]),
            )
            acc = self._phase_acc.setdefault(self._phase_name, [0.0, 0.0])
            acc[0] += dur
            acc[1] += min(work_s, dur)
        elif next_name is not None:
            # first phase of the height opening: everything since the
            # height started (commit timeout + waiting for round 0)
            self._phase_acc.setdefault("new_height", [0.0, 0.0])[0] += max(
                0.0, now - self._height_started
            )
        self._phase_name = next_name
        self._phase_started = now
        self._phase_work0 = work

    # ------------------------------------------------------ state plumbing

    def _update_to_state(self, state: State) -> None:
        """Reset the round state for state.last_block_height+1
        (reference `updateToState consensus/state.go:415-477`)."""
        if self.commit_round > -1 and 0 < self.height != state.last_block_height:
            raise ValidationError(
                f"updateToState expected height {self.height}, got {state.last_block_height}"
            )
        # last_commit: the precommits that committed the last block
        last_commit = None
        if state.last_block_height > 0:
            if self.commit_round > -1 and self.votes is not None:
                precommits = self.votes.precommits(self.commit_round)
                if precommits is None or not precommits.has_two_thirds_majority():
                    raise ValidationError("updateToState called with unfinished commit")
                last_commit = precommits
            else:
                last_commit = self._reconstruct_last_commit(state)

        self.state = state
        self.height = state.last_block_height + 1
        self.round = 0
        self.step = RoundStepType.NEW_HEIGHT
        now = time_mod.time()
        if self.commit_time:
            self.start_time = self.commit_time + self.timeouts.commit_timeout()
        else:
            self.start_time = now + self.timeouts.commit_timeout()
        validators = state.validators.copy()
        self.validators = validators
        self.proposal = None
        self.proposal_block = None
        self.proposal_block_parts = None
        self.locked_round = -1
        self.locked_block = None
        self.locked_block_parts = None
        self.votes = HeightVoteSet(state.chain_id, self.height, validators)
        self.commit_round = -1
        self.last_commit = last_commit
        self._phase_name = None
        self._height_started = time_mod.monotonic()
        # fresh per-height ledger accumulators (phase durations,
        # work-stopwatch baseline, per-validator vote arrivals)
        self._phase_acc = {}
        self._height_work0 = _heightlog.work_totals()
        self._phase_work0 = self._height_work0
        self._val_arrivals = {}
        self._apply_s = 0.0
        # the height's block trace context: adopted from the proposal
        # (proposer: its first traced tx; receivers: the proposal
        # frame's context) — vote-batch verifies for this height are
        # attributed to it
        self._proposal_ctx = None
        _metrics.CONSENSUS_HEIGHT.set(self.height)
        _metrics.CONSENSUS_ROUND.set(0)

    def _reconstruct_last_commit(self, state: State) -> VoteSet | None:
        """Rebuild the precommit VoteSet from the stored seen-commit
        (reference `consensus/state.go:392-411`)."""
        if state.last_block_height == 0 or self.block_store is None:
            return None
        seen = self.block_store.load_seen_commit(state.last_block_height)
        if seen is None:
            raise ValidationError(
                f"no seen commit for height {state.last_block_height}"
            )
        vs = VoteSet(
            state.chain_id,
            state.last_block_height,
            seen.round(),
            VOTE_TYPE_PRECOMMIT,
            state.last_validators,
        )
        for v in seen.precommits:
            if v is not None:
                vs.add_vote(v, verifier=self.verifier)
        if not vs.has_two_thirds_majority():
            raise ValidationError("reconstructed last commit lacks +2/3")
        return vs

    def _catchup_replay(self) -> None:
        """Replay WAL records for the in-progress height
        (reference `consensus/replay.go:93-143`)."""
        if self.wal is None:
            return
        records = WAL.records_since_last_end_height(self.wal.path, self.height)
        if records is None:
            return
        saved_wal, self.wal = self.wal, None  # don't re-WAL replayed inputs
        try:
            for rec in records:
                if isinstance(rec, (EndHeightMessage, RoundStateRecord)):
                    continue
                try:
                    with self._mtx:
                        self._dispatch(rec)
                except (ErrDoubleSign, FatalConsensusError):
                    raise
                except Exception:
                    # Inputs are WAL'd BEFORE validation, so a bad peer
                    # message (invalid sig, conflicting vote) can be on
                    # disk; tolerate it here exactly like the live loop
                    # does, or the node can never restart (reference
                    # replay.go logs-and-continues the same way).
                    import traceback

                    traceback.print_exc()
        finally:
            self.wal = saved_wal

    # --------------------------------------------------------- scheduling

    def _schedule_round0(self) -> None:
        sleep = max(0.0, self.start_time - time_mod.time())
        self.ticker.schedule(
            TimeoutInfo(sleep, self.height, 0, RoundStepType.NEW_HEIGHT)
        )

    def _schedule_timeout(self, duration: float, height: int, round_: int, step: int) -> None:
        self.ticker.schedule(TimeoutInfo(duration, height, round_, step))

    def _handle_timeout(self, ti: TimeoutInfo) -> None:
        """Reference `handleTimeout consensus/state.go:589-622`."""
        if ti.height != self.height or ti.round < self.round or (
            ti.round == self.round and ti.step < self.step
        ):
            return
        if ti.step == RoundStepType.NEW_HEIGHT:
            self._enter_new_round(ti.height, 0)
        elif ti.step == RoundStepType.NEW_ROUND:
            # create_empty_blocks_interval expired while waiting for txs
            self._enter_propose(ti.height, 0)
        elif ti.step == RoundStepType.PROPOSE:
            self.event_switch.fire(ev.EVENT_TIMEOUT_PROPOSE, self._rs_event())
            self._enter_prevote(ti.height, ti.round)
        elif ti.step == RoundStepType.PREVOTE:
            # Round-skip (ROADMAP liveness gap): starved at PREVOTE with
            # no +2/3-any to arm PrevoteWait — precommit nil and move on,
            # later Tendermint's OnTimeoutPrevote. The guard above
            # filtered this tock out if the round advanced on its own.
            _metrics.CONSENSUS_ROUND_SKIPS.labels(phase="prevote").inc()
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == RoundStepType.PRECOMMIT:
            # OnTimeoutPrecommit: starved at PRECOMMIT — next round.
            _metrics.CONSENSUS_ROUND_SKIPS.labels(phase="precommit").inc()
            self._enter_new_round(ti.height, ti.round + 1)
        elif ti.step == RoundStepType.PREVOTE_WAIT:
            self.event_switch.fire(ev.EVENT_TIMEOUT_WAIT, self._rs_event())
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == RoundStepType.PRECOMMIT_WAIT:
            self.event_switch.fire(ev.EVENT_TIMEOUT_WAIT, self._rs_event())
            self._enter_new_round(ti.height, ti.round + 1)

    # -------------------------------------------------------- transitions

    def _rs_event(self):
        return ev.EventDataRoundState(
            height=self.height, round=self.round, step=RoundStepType.name(self.step)
        )

    def _new_step(self) -> None:
        if self.wal is not None:
            self.wal.save(RoundStateRecord(self.height, self.round, self.step))
        FLIGHT.record(
            "round_step",
            height=self.height,
            round=self.round,
            step=RoundStepType.name(self.step),
        )
        self.event_switch.fire(ev.EVENT_NEW_ROUND_STEP, self._rs_event())

    def _enter_new_round(self, height: int, round_: int) -> None:
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step != RoundStepType.NEW_HEIGHT
        ):
            return
        validators = self.validators
        if round_ > self.round:
            validators = validators.copy()
            validators.increment_accum(round_ - self.round)
        self.validators = validators
        self.round = round_
        self.step = RoundStepType.NEW_ROUND
        if round_ != 0:
            # round 0 fields were reset by _update_to_state
            self.proposal = None
            self.proposal_block = None
            self.proposal_block_parts = None
        self.votes.set_round(round_ + 1)  # track next round for skipping
        _metrics.CONSENSUS_ROUND.set(round_)
        self.event_switch.fire(ev.EVENT_NEW_ROUND, self._rs_event())

        wait_for_txs = (
            not self.config.create_empty_blocks
            and round_ == 0
            and not self.mempool.tx_available()
        )
        if wait_for_txs:
            self.step = RoundStepType.NEW_ROUND  # wait; _TxsAvailable resumes
            if self.config.create_empty_blocks_interval > 0:
                self._schedule_timeout(
                    self.config.create_empty_blocks_interval,
                    height,
                    round_,
                    RoundStepType.NEW_ROUND,
                )
            if self.priv_validator is not None:
                # signed liveness pings while the chain idles (reference
                # `proposalHeartbeat consensus/state.go:707-738`); the
                # reactor gossips them, WS subscribers observe them
                threading.Thread(
                    target=self._proposal_heartbeat,
                    args=(height, round_),
                    name="consensus-heartbeat",
                    daemon=True,
                ).start()
            return
        self._enter_propose(height, round_)

    def _proposal_heartbeat(self, height: int, round_: int) -> None:
        addr = self.priv_validator.address
        idx = -1
        with self._mtx:
            for i, v in enumerate(self.validators):
                if v.address == addr:
                    idx = i
                    break
            chain_id = self.state.chain_id
        if idx < 0:
            # not in the validator set: nothing to prove liveness for,
            # and the wire encoding (uvarint index) can't carry -1
            return
        sequence = 0
        while self._running:
            rs = self.get_round_state()
            if (
                rs.height > height
                or rs.round > round_
                or rs.step > RoundStepType.NEW_ROUND
            ):
                return
            hb = Heartbeat(
                validator_address=addr,
                validator_index=idx,
                height=rs.height,
                round=rs.round,
                sequence=sequence,
            )
            hb = self.priv_validator.sign_heartbeat(chain_id, hb)
            self.event_switch.fire(ev.EVENT_PROPOSAL_HEARTBEAT, hb)
            sequence += 1
            time_mod.sleep(self.config.proposal_heartbeat_interval)

    def _enter_propose(self, height: int, round_: int) -> None:
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step >= RoundStepType.PROPOSE
        ):
            return
        self.round = round_
        self.step = RoundStepType.PROPOSE
        self._observe_phase("propose")
        self._new_step()
        self._schedule_timeout(
            self.timeouts.propose_timeout(round_), height, round_, RoundStepType.PROPOSE
        )
        if self.priv_validator is not None and self.is_proposer():
            # JOIN BARRIER: the proposal header carries the applied
            # app_hash/validators_hash and reaps the updated mempool +
            # evidence pool. (A stale-valset is_proposer() miss above
            # costs at worst one proposer slot on a rotation height —
            # liveness the next round recovers, never safety.)
            self._join_apply("propose")
            if self.is_proposer():
                self.decide_proposal_fn(height, round_)
        if self._is_proposal_complete():
            self._enter_prevote(height, round_)

    def _default_decide_proposal(self, height: int, round_: int) -> None:
        """Reference `defaultDecideProposal :787-827`."""
        if self.locked_block is not None:
            block, parts = self.locked_block, self.locked_block_parts
        else:
            made = self._create_proposal_block()
            if made is None:
                return
            block, parts = made
        pol_round, pol_block_id = self.votes.pol_info()
        proposal = Proposal(
            height=height,
            round=round_,
            block_parts_header=parts.header,
            pol_round=pol_round,
            pol_block_id=pol_block_id if pol_block_id is not None else BlockID.zero(),
            timestamp=time_mod.time_ns(),
        )
        try:
            proposal = self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except ErrDoubleSign:
            return
        # Proposal creation is a trace edge: adopt the first traced
        # tx's context as the BLOCK's context (the verify work this
        # height does is causally that tx's), minting fresh otherwise.
        # The internal sends below run with it ambient, so the records
        # — and the push-gossiped proposal/parts frames they trigger —
        # carry it to every peer.
        ctx = None
        trace_for = getattr(self.mempool, "trace_for", None)
        if trace_for is not None:
            for tx in block.data.txs:
                ctx = trace_for(bytes(tx))
                if ctx is not None:
                    break
        if ctx is None:
            origin = (
                self.priv_validator.address.hex()[:12]
                if self.priv_validator is not None
                else ""
            )
            ctx = _trace.mint(origin)
        self._proposal_ctx = ctx
        # send to ourselves (internal queue, no peer id)
        with _trace.use(ctx):
            self.set_proposal(proposal, "")
            for i in range(parts.total):
                self.add_proposal_block_part(height, round_, parts.get_part(i), "")

    def _create_proposal_block(self) -> tuple[Block, PartSet] | None:
        """Reference `createProposalBlock :848-868`."""
        if self.height == 1:
            last_commit = Commit.empty()
        elif self.last_commit is not None and self.last_commit.has_two_thirds_majority():
            last_commit = self.last_commit.make_commit()
        else:
            return None  # can't propose without the last commit
        txs = self.mempool.reap(self.config.max_block_size_txs)
        # commit pending misbehavior proofs alongside the txs (reference
        # `createProposalBlock` reaps the evidence pool); expired proofs
        # would fail every honest validator's validate_block, so filter
        # here rather than waste the proposal
        evidence = []
        if self.evidence_pool is not None:
            params = self.state.consensus_params.evidence
            evidence = [
                e
                for e in self.evidence_pool.pending_evidence(params.max_evidence)
                if self.height - e.height <= params.max_age
            ]
        block = Block.make_block(
            height=self.height,
            chain_id=self.state.chain_id,
            txs=Txs(txs),
            last_commit=last_commit,
            last_block_id=self.state.last_block_id,
            time=time_mod.time_ns(),
            validators_hash=self.state.validators.hash(),
            app_hash=self.state.app_hash,
            hasher=self.hasher,
            evidence=evidence,
        )
        return block, block.make_part_set(
            self.state.consensus_params.block_gossip.block_part_size_bytes,
            hasher=self.hasher,
        )

    def _default_set_proposal(self, proposal: Proposal) -> None:
        """Reference `defaultSetProposal :1247-1278`."""
        if self.proposal is not None:
            return
        if proposal.height != self.height or proposal.round != self.round:
            return
        if not (-1 <= proposal.pol_round < proposal.round):
            raise ValidationError("proposal POLRound out of range")
        proposer = self.validators.proposer
        if not proposer.pub_key.verify(
            proposal.sign_bytes(self.state.chain_id), proposal.signature
        ):
            raise ValidationError("invalid proposal signature")
        self.proposal = proposal
        if self._proposal_ctx is None:
            # receiver side of block-context adoption: the proposal
            # frame's trace context is ambient here (via the record)
            self._proposal_ctx = _trace.current()
        if self.proposal_block_parts is None:
            self.proposal_block_parts = PartSet.from_header(proposal.block_parts_header)

    def _handle_block_part(
        self, height: int, round_: int, part: Part, peer_id: str = ""
    ) -> None:
        """Reference `addProposalBlockPart :1282-1315`."""
        if height != self.height or self.proposal_block_parts is None:
            return
        if self._proposal_ctx is None:
            # block parts carry the block's context too (push gossip);
            # adopt when the proposal itself arrived uncontexted
            self._proposal_ctx = _trace.current()
        try:
            added = self.proposal_block_parts.add_part(part)
        except ValidationError:
            return
        if self.gossip is not None:
            if added:
                self.gossip.first_seen("block_part", height, round_, part.index)
            elif peer_id:
                # PartSet already-have part: a peer re-shipped a part we
                # hold — redundant wire bytes (own enqueues gate out on
                # the empty peer_id, same rule as votes)
                self.gossip.redundant("block_part", len(part.encode()))
        if not added or not self.proposal_block_parts.is_complete():
            return
        buf = b"".join(
            self.proposal_block_parts.get_part(i).bytes_
            for i in range(self.proposal_block_parts.total)
        )
        self.proposal_block = Block.decode(buf)
        self.event_switch.fire(ev.EVENT_COMPLETE_PROPOSAL, self._rs_event())
        prevotes = self.votes.prevotes(self.round)
        bid = prevotes.two_thirds_majority() if prevotes is not None else None
        if bid is not None and not bid.is_zero() and self.step <= RoundStepType.PREVOTE:
            # +2/3 already prevoted this block before we had it
            self._enter_prevote(height, self.round)
        elif self.step == RoundStepType.PROPOSE and self._is_proposal_complete():
            self._enter_prevote(height, self.round)
        elif self.step == RoundStepType.COMMIT:
            self._try_finalize_commit(height)

    def _is_proposal_complete(self) -> bool:
        if self.proposal is None or self.proposal_block is None:
            return False
        if self.proposal.pol_round < 0:
            return True
        prevotes = self.votes.prevotes(self.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def _enter_prevote(self, height: int, round_: int) -> None:
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step >= RoundStepType.PREVOTE
        ):
            return
        # JOIN BARRIER: prevoting validates the proposal against
        # applied state (app_hash, EndBlock valset) — never vote for a
        # block judged on speculation.
        self._join_apply("prevote")
        self.round = round_
        self.step = RoundStepType.PREVOTE
        self._observe_phase("prevote")
        self._new_step()
        skip = self.config.round_skip_timeout(round_)
        if skip > 0:
            # round-skip deadline: replaced by PrevoteWait/Precommit
            # scheduling when votes actually flow (ticker keys order by
            # step), fires only if this round truly starves here
            self._schedule_timeout(skip, height, round_, RoundStepType.PREVOTE)
        self.do_prevote_fn(height, round_)

    def _default_do_prevote(self, height: int, round_: int) -> None:
        """Reference `defaultDoPrevote :875-908`."""
        if self.locked_block is not None:
            self._sign_add_vote(
                VOTE_TYPE_PREVOTE,
                self.locked_block.hash(),
                self.locked_block_parts.header,
            )
            return
        if self.proposal_block is None:
            self._sign_add_vote(VOTE_TYPE_PREVOTE, b"", PartSetHeader.zero())
            return
        try:
            from tendermint_tpu.state import validate_block

            validate_block(
                self.state,
                self.proposal_block,
                verifier=self.verifier,
                hasher=self.hasher,
            )
        except ValidationError:
            self._sign_add_vote(VOTE_TYPE_PREVOTE, b"", PartSetHeader.zero())
            return
        self._sign_add_vote(
            VOTE_TYPE_PREVOTE,
            self.proposal_block.hash(),
            self.proposal_block_parts.header,
        )

    def _enter_prevote_wait(self, height: int, round_: int) -> None:
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step >= RoundStepType.PREVOTE_WAIT
        ):
            return
        self.round = round_
        self.step = RoundStepType.PREVOTE_WAIT
        self._new_step()
        self._schedule_timeout(
            self.timeouts.prevote_timeout(round_), height, round_, RoundStepType.PREVOTE_WAIT
        )

    def _enter_precommit(self, height: int, round_: int) -> None:
        """Reference `enterPrecommit :963-1053` — the POL lock logic."""
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step >= RoundStepType.PRECOMMIT
        ):
            return
        self.round = round_
        self.step = RoundStepType.PRECOMMIT
        self._observe_phase("precommit")
        self._new_step()
        skip = self.config.round_skip_timeout(round_)
        if skip > 0:
            self._schedule_timeout(skip, height, round_, RoundStepType.PRECOMMIT)

        prevotes = self.votes.prevotes(round_)
        block_id = prevotes.two_thirds_majority() if prevotes is not None else None

        if block_id is None:
            # no polka: precommit nil
            self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", PartSetHeader.zero())
            return

        self.event_switch.fire(ev.EVENT_POLKA, self._rs_event())

        if block_id.is_zero():
            # polka for nil: unlock if locked
            if self.locked_block is not None:
                self.locked_round = -1
                self.locked_block = None
                self.locked_block_parts = None
                self.event_switch.fire(ev.EVENT_UNLOCK, self._rs_event())
            self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", PartSetHeader.zero())
            return

        if self.locked_block is not None and self.locked_block.hash_to(block_id.hash):
            # relock
            self.locked_round = round_
            self.event_switch.fire(ev.EVENT_RELOCK, self._rs_event())
            self._sign_add_vote(VOTE_TYPE_PRECOMMIT, block_id.hash, block_id.parts_header)
            return

        if self.proposal_block is not None and self.proposal_block.hash_to(block_id.hash):
            # lock the polka block (it must validate)
            from tendermint_tpu.state import validate_block

            try:
                validate_block(
                    self.state,
                    self.proposal_block,
                    verifier=self.verifier,
                    hasher=self.hasher,
                )
            except ValidationError as e:
                raise ValidationError(f"+2/3 prevoted an invalid block: {e}") from e
            self.locked_round = round_
            self.locked_block = self.proposal_block
            self.locked_block_parts = self.proposal_block_parts
            self.event_switch.fire(ev.EVENT_LOCK, self._rs_event())
            self._sign_add_vote(VOTE_TYPE_PRECOMMIT, block_id.hash, block_id.parts_header)
            return

        # polka for a block we don't have: unlock, fetch it, precommit nil
        self.locked_round = -1
        self.locked_block = None
        self.locked_block_parts = None
        if self.proposal_block_parts is None or not self.proposal_block_parts.has_header(
            block_id.parts_header
        ):
            self.proposal_block = None
            self.proposal_block_parts = PartSet.from_header(block_id.parts_header)
        self.event_switch.fire(ev.EVENT_UNLOCK, self._rs_event())
        self._sign_add_vote(VOTE_TYPE_PRECOMMIT, b"", PartSetHeader.zero())

    def _enter_precommit_wait(self, height: int, round_: int) -> None:
        if height != self.height or round_ < self.round or (
            round_ == self.round and self.step >= RoundStepType.PRECOMMIT_WAIT
        ):
            return
        self.round = round_
        self.step = RoundStepType.PRECOMMIT_WAIT
        self._new_step()
        self._schedule_timeout(
            self.timeouts.precommit_timeout(round_),
            height,
            round_,
            RoundStepType.PRECOMMIT_WAIT,
        )

    def _enter_commit(self, height: int, commit_round: int) -> None:
        """Reference `enterCommit :1078-1143`."""
        if height != self.height or self.step >= RoundStepType.COMMIT:
            return
        self.commit_round = commit_round
        self.commit_time = time_mod.time()
        self.step = RoundStepType.COMMIT
        self._observe_phase("commit")

        block_id = self.votes.precommits(commit_round).two_thirds_majority()
        if block_id is None or block_id.is_zero():
            raise ValidationError("enterCommit without +2/3 precommits")
        if self.locked_block is not None and self.locked_block.hash_to(block_id.hash):
            self.proposal_block = self.locked_block
            self.proposal_block_parts = self.locked_block_parts
        if self.proposal_block is None or not self.proposal_block.hash_to(block_id.hash):
            if self.proposal_block_parts is None or not self.proposal_block_parts.has_header(
                block_id.parts_header
            ):
                # we don't have the committed block: fetch via gossip
                self.proposal_block = None
                self.proposal_block_parts = PartSet.from_header(block_id.parts_header)
        # announced only now, as the reference defers newStep to
        # enterCommit's end: the reactor's CommitStep broadcast reads the
        # parts header inside this event, and it must be the committed
        # block's. A stale proposal's header with its bits set tells
        # every peer this node holds a block nobody committed, and none
        # sends it the real one.
        self._new_step()
        self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        block_id = self.votes.precommits(self.commit_round).two_thirds_majority()
        if block_id is None or block_id.is_zero():
            return
        if self.proposal_block is None or not self.proposal_block.hash_to(block_id.hash):
            return  # wait for gossip to complete the block
        self._finalize_commit(height)

    def _pipeline_on(self) -> bool:
        """Pipelined finalize only on the live receive loop — WAL replay
        and pre-start harness drives keep the strictly serial ladder."""
        return self.pipeline_enabled and self._running

    def _finalize_commit(self, height: int) -> None:
        """Reference `finalizeCommit :1146-1243` with fail points
        bracketing every persistence step.

        Two tails share the persistence prefix (block save + WAL
        ENDHEIGHT): the serial tail applies the block inline before
        entering H+1, the pipelined tail (`_finalize_pipelined`)
        launches the apply as a dispatch handle and enters H+1's
        NewHeight immediately on a speculated state."""
        block = self.proposal_block
        parts = self.proposal_block_parts
        block_id = self.votes.precommits(self.commit_round).two_thirds_majority()
        assert block is not None and block.hash_to(block_id.hash)

        # Any failure from here on is an internal invariant/persistence
        # error, not bad peer input: once the block is saved / ENDHEIGHT is
        # WAL'd, a swallowed exception would leave a live node half-advanced
        # (store=H, WAL done, state=H-1) but still voting. Escalate so the
        # receive loop halts and crash recovery handles it on restart
        # (reference panics via PanicConsensus in finalizeCommit/ApplyBlock).
        try:
            fail_point()  # before block save
            if self.block_store is not None and self.block_store.height < height:
                seen_commit = self.votes.precommits(self.commit_round).make_commit()
                self.block_store.save_block(block, parts, seen_commit)

            fail_point()  # block saved, before WAL ENDHEIGHT
            if self.wal is not None:
                self.wal.save(EndHeightMessage(height))

            fail_point()  # ENDHEIGHT written, before ApplyBlock
            state_copy = self.state.copy()
            if self._pipeline_on():
                self._finalize_pipelined(height, block, parts, state_copy)
                return
            tx_results: list[tuple[bytes, object]] = []
            t_apply = time_mod.monotonic()
            apply_block(
                state_copy,
                block,
                parts.header,
                self.app_conn,
                mempool=self.mempool,
                verifier=self.verifier,
                tx_indexer=self.tx_indexer,
                on_tx_result=lambda i, tx, res: tx_results.append((tx, res)),
                hasher=self.hasher,
            )
            self._apply_s = time_mod.monotonic() - t_apply

            fail_point()  # applied, before round-state reset
            if self.evidence_pool is not None:
                # retire committed proofs + prune expired stragglers
                self.evidence_pool.update(height, list(block.evidence))
            self._observe_phase(None)  # closes the "commit" span
            draft = self._close_height_telemetry(height, block)
            self._record_height_ledger(draft, apply_s=self._apply_s)
            self._close_tx_traces(block, draft["wall_end"], height)
            self._update_to_state(state_copy)
        except FatalConsensusError:
            raise
        except Exception as e:
            raise FatalConsensusError(
                f"finalize_commit failed at height {height}"
            ) from e
        # Listener callbacks are external code — a raising subscriber must
        # not be escalated to a consensus halt, so fire outside the scope.
        self._fire_commit_events(height, block, tx_results)
        self._schedule_round0()
        # Announce H+1's NewHeight right away (the pipelined tail does
        # the same): peers that hear we advanced push their H+1
        # proposal/votes immediately instead of rediscovering us on the
        # next gossip poll tick.
        self.event_switch.fire(ev.EVENT_NEW_ROUND_STEP, self._rs_event())

    # ------------------------------------------------ pipelined finalize

    def _apply_queue(self):
        if self._apply_dispatch is None:
            from tendermint_tpu.services.dispatch import DispatchQueue

            # depth 1: at most one height's apply in flight — the next
            # finalize can only be reached through a vote tally, which
            # joins first. launch_ledger off: host work, not a device
            # launch (the device observatory must not count it).
            self._apply_dispatch = DispatchQueue(
                depth=1, name="apply", launch_ledger=False
            )
        return self._apply_dispatch

    def _finalize_pipelined(self, height, block, parts, state_copy) -> None:
        """Overlapped-apply tail of `_finalize_commit`: launch height
        H's `apply_block` (ABCI execute + state-tree hash + persist) on
        the apply dispatch queue, then enter H+1's NewHeight on a
        PROVISIONAL state speculated without the ABCI responses
        (`State.speculate_next`). Everything H+1 does before the join
        barrier (`_join_apply`) is either derivable pre-apply
        (last_commit, heights, block ids) or degrade-safe speculation
        (vote preverify launches); applied fields — app_hash, EndBlock
        valset changes, the updated mempool — are only readable past a
        join. A faulted apply surfaces at the join and halts consensus
        exactly like the serial path, so speculative state can never
        reach a signature on a forged fork."""
        tx_results: list[tuple[bytes, object]] = []

        def _run_apply():
            t0 = time_mod.monotonic()
            apply_block(
                state_copy,
                block,
                parts.header,
                self.app_conn,
                mempool=self.mempool,
                verifier=self.verifier,
                tx_indexer=self.tx_indexer,
                on_tx_result=lambda i, tx, res: tx_results.append((tx, res)),
                hasher=self.hasher,
            )
            return time_mod.monotonic() - t0

        handle = self._apply_queue().submit(_run_apply, kind="apply")
        self._observe_phase(None)  # closes the "commit" span pre-apply
        draft = self._close_height_telemetry(height, block)
        provisional = self.state.speculate_next(block.header, parts.header)
        self._pending_apply = {
            "height": height,
            "block": block,
            "handle": handle,
            "state": state_copy,
            "tx_results": tx_results,
            "draft": draft,
            "spec_val_hash": provisional.validators.hash(),
            "launched": time_mod.monotonic(),
        }
        FLIGHT.record(
            "commit_pipelined",
            height=height,
            round=self.commit_round,
            txs=len(block.data.txs),
        )
        self._update_to_state(provisional)
        self._schedule_round0()
        self.event_switch.fire(ev.EVENT_NEW_ROUND_STEP, self._rs_event())

    def _join_apply(self, reason: str) -> None:
        """The hard join barrier: block until H's in-flight apply lands,
        swap the applied state in for the provisional one, and run the
        post-apply bookkeeping the serial path did inline (evidence
        retirement, ledger record, commit events). Callers sit at every
        point that reads applied state: the proposer's block creation
        (`_enter_propose`), prevote validation (`_enter_prevote`), and
        current-height vote tallies. Idempotent no-op when nothing is
        pending. Raises FatalConsensusError on a faulted apply — the
        receive loop halts, exactly the serial failure mode."""
        pend = self._pending_apply
        if pend is None:
            return
        self._pending_apply = None
        t0 = time_mod.monotonic()
        stalled = not pend["handle"].done()
        try:
            apply_s = pend["handle"].result()
        except FatalConsensusError:
            raise
        except Exception as e:
            _metrics.PIPELINE_STALLS.labels(reason="fault").inc()
            FLIGHT.record(
                "pipeline_fault", height=pend["height"], error=type(e).__name__
            )
            raise FatalConsensusError(
                f"pipelined apply failed at height {pend['height']}"
            ) from e
        stall_s = time_mod.monotonic() - t0
        # an "idle" join blocked nothing — the loop had no input to
        # process; only barrier joins that made H+1 wait count as stalls
        stalled = stalled and reason != "idle"
        if stalled:
            _metrics.PIPELINE_STALLS.labels(reason=reason).inc()
        overlap_s = max(0.0, min(apply_s, apply_s - stall_s))
        self._apply_s = apply_s
        _metrics.APPLY_OVERLAP_SECONDS.observe(overlap_s)
        st = self.pipeline_stats
        st["joins"] += 1
        st["stalls"] += 1 if stalled else 0
        st["overlap_s_total"] += overlap_s
        st["last_overlap_s"] = overlap_s
        applied = pend["state"]
        height = pend["height"]
        block = pend["block"]
        if applied.validators.hash() != pend["spec_val_hash"]:
            # EndBlock rotated the valset: rebuild everything H+1
            # derived from the speculation. Nothing was consumed under
            # it — vote tallies and the proposer's path join first — so
            # a fresh HeightVoteSet and re-derived accum are complete.
            base = applied.validators.copy()
            self.votes = HeightVoteSet(applied.chain_id, self.height, base)
            if self.round > 0:
                vals = base.copy()
                vals.increment_accum(self.round)
                self.validators = vals
            else:
                self.validators = base
            self._valset_gen += 1
            self.pipeline_stats["valset_rebuilds"] += 1
            FLIGHT.record(
                "pipeline_valset_rebuild", height=self.height, round=self.round
            )
        self.state = applied
        if self.evidence_pool is not None:
            self.evidence_pool.update(height, list(block.evidence))
        self._record_height_ledger(
            pend["draft"], apply_s=apply_s, overlap_s=overlap_s, pipelined=True
        )
        self._close_tx_traces(block, pend["draft"]["wall_end"], height)
        self._fire_commit_events(height, block, pend["tx_results"])

    # ------------------------------------------------ commit bookkeeping

    def _close_height_telemetry(self, height: int, block: Block) -> dict:
        """Metrics + tracer spans closed at commit decide time, plus the
        draft snapshot the ledger record is assembled from — captured
        BEFORE `_update_to_state` wipes the per-height accumulators (the
        pipelined tail records at the join, a height later)."""
        height_wall = time_mod.monotonic() - self._height_started
        _metrics.CONSENSUS_HEIGHT_SECONDS.observe(height_wall)
        _metrics.CONSENSUS_COMMITS.inc()
        _metrics.CONSENSUS_TXS_COMMITTED.inc(len(block.data.txs))
        wall_end = time_mod.time()
        TRACER.add(
            "consensus.height",
            wall_end - height_wall,
            wall_end,
            height=height,
            round=self.commit_round,
            txs=len(block.data.txs),
        )
        FLIGHT.record(
            "commit",
            height=height,
            round=self.commit_round,
            txs=len(block.data.txs),
            hash=block.hash().hex()[:12],
        )
        return {
            "height": height,
            "round": self.commit_round,
            "txs": len(block.data.txs),
            "wall_end": wall_end,
            "height_wall": height_wall,
            "phase_acc": dict(self._phase_acc),
            "work0": self._height_work0,
            "work1": _heightlog.work_totals(),
            "val_arrivals": dict(self._val_arrivals),
        }

    def _close_tx_traces(self, block: Block, wall_end: float, height: int) -> None:
        """Close every committed traced tx: first-seen -> committed on
        THIS node's clock, linked back by exemplar trace id."""
        take_trace = getattr(self.mempool, "take_trace", None)
        if take_trace is None:
            return
        for tx in block.data.txs:
            entry = take_trace(bytes(tx))
            if entry is None:
                continue
            tx_ctx, t_seen = entry
            _metrics.TX_E2E.observe(wall_end - t_seen, exemplar=tx_ctx.trace)
            TRACER.add(
                "tx.e2e",
                t_seen,
                wall_end,
                trace=tx_ctx.trace,
                origin=tx_ctx.origin,
                height=height,
            )

    def _fire_commit_events(self, height: int, block: Block, tx_results) -> None:
        self.event_switch.fire(ev.EVENT_NEW_BLOCK, ev.EventDataNewBlock(block))
        self.event_switch.fire(
            ev.EVENT_NEW_BLOCK_HEADER, ev.EventDataNewBlockHeader(block.header)
        )
        _log_mod.kv(
            _log_mod.logger("consensus"),
            _logging.INFO,
            "block committed",
            height=height,
            txs=len(block.data.txs),
            hash=block.hash().hex()[:12],
        )
        # per-tx results: generic stream + hash-keyed (broadcast_tx_commit
        # waits on the keyed event — reference EventDataTx via event cache)
        from tendermint_tpu.types.tx import tx_hash

        for tx, res in tx_results:
            data = ev.EventDataTx(
                height=height, tx=tx, data=res.data, log=res.log, code=res.code
            )
            self.event_switch.fire(ev.EVENT_TX, data)
            self.event_switch.fire(ev.event_tx(tx_hash(tx)), data)

    def _record_height_ledger(
        self,
        draft: dict,
        apply_s: float,
        overlap_s: float = 0.0,
        pipelined: bool = False,
    ) -> None:
        """Assemble the height's ledger record from the finalize-time
        draft: phase durations with their wait-vs-work split, the
        commit-to-commit gap, critical-path attribution over the
        candidate contributors, and the laggard validator from the
        vote-arrival tracking. Pipelined records carry the overlap
        (`apply_overlap_s`) and count only the NON-overlapped apply
        share toward the critical path. Observability must never fail
        the commit — errors are printed, not raised."""
        try:
            height = draft["height"]
            wall_end = draft["wall_end"]
            height_wall = draft["height_wall"]
            work1 = draft["work1"]
            w0 = draft["work0"]
            phase_acc = draft["phase_acc"]
            verify_s = max(0.0, work1["verify"] - w0["verify"])
            hash_s = max(0.0, work1["hash"] - w0["hash"])
            coalescer_s = max(0.0, work1["coalescer"] - w0["coalescer"])
            dispatch_s = max(0.0, work1["dispatch"] - w0["dispatch"])
            phases: dict[str, dict] = {}
            for name in ("new_height", "propose", "prevote", "precommit", "commit"):
                dur, work = phase_acc.get(name, (0.0, 0.0))
                if name == "commit" and not pipelined:
                    # the serial commit phase closes AFTER apply; split
                    # the apply stopwatch out so it reads as its own
                    # phase (the pipelined phase closes pre-launch)
                    dur = max(0.0, dur - apply_s)
                work = min(work, dur)
                phases[name] = {
                    "s": round(dur, 6),
                    "work_s": round(work, 6),
                    "wait_s": round(max(0.0, dur - work), 6),
                }
            phases["apply"] = {
                "s": round(apply_s, 6),
                "work_s": round(apply_s, 6),
                "wait_s": 0.0,
            }
            for name, p in phases.items():
                _metrics.HEIGHT_PHASE_SECONDS.labels(phase=name).observe(p["s"])
            finality_s = None
            if self._last_commit_wall is not None:
                finality_s = max(0.0, wall_end - self._last_commit_wall)
                _metrics.FINALITY_SECONDS.observe(finality_s)
            self._last_commit_wall = wall_end
            # critical-path candidates: wall-clock phase groups plus the
            # registry-stitched device/coalescer stopwatch deltas (the
            # latter are process-global — cross-node sums in multi-node
            # harnesses; the wall-clock groups are per-node exact)
            contributors = {
                "proposal_wait": phases["new_height"]["s"] + phases["propose"]["s"],
                "vote_gather": phases["prevote"]["s"] + phases["precommit"]["s"],
                "commit_wait": phases["commit"]["s"],
                "coalescer_wait": coalescer_s,
                "dispatch_launch": verify_s + dispatch_s,
                "abci_apply": max(0.0, apply_s - overlap_s),
                "merkle_hash": hash_s,
            }
            critical = max(contributors, key=lambda k: contributors[k])
            laggard = None
            if draft["val_arrivals"]:
                idx, (addr, delay) = max(
                    draft["val_arrivals"].items(), key=lambda kv: kv[1][1]
                )
                laggard = {
                    "validator": addr,
                    "index": idx,
                    "delay_s": round(delay, 6),
                }
                _metrics.VOTE_ARRIVAL_MAX.set(delay)
            self.height_ledger.record(
                {
                    "height": height,
                    "round": draft["round"],
                    "txs": draft["txs"],
                    "t_start": round(wall_end - height_wall, 6),
                    "t_commit": round(wall_end, 6),
                    "height_s": round(height_wall, 6),
                    "finality_s": round(finality_s, 6)
                    if finality_s is not None
                    else None,
                    "phases": phases,
                    "path": {k: round(v, 6) for k, v in contributors.items()},
                    "critical_path": critical,
                    "laggard": laggard,
                    "pipelined": pipelined,
                    "apply_overlap_s": round(overlap_s, 6),
                }
            )
        except Exception:
            import traceback

            traceback.print_exc()

    # ---------------------------------------------------------------- votes

    def _evidence_val_set(self, height: int):
        """Validator set evidence at `height` must verify against. Only
        the live and previous sets are retained in memory; older
        evidence verifies best-effort against the current set (a
        validator absent from both is unprovable here and the evidence
        is rejected — the max-age window bounds how far back proofs can
        reach anyway)."""
        if height == self.height - 1 and self.state.last_validators is not None:
            if self.state.last_validators.size():
                return self.state.last_validators
        return self.validators

    def _report_misbehavior(self, peer_id: str, kind: str, detail: str = "") -> None:
        cb = self.on_peer_misbehavior
        if cb is not None and peer_id:
            try:
                cb(peer_id, kind, detail)
            except Exception:
                pass  # scoring must never hurt consensus

    def _found_conflicting_votes(
        self, err: ErrVoteConflictingVotes, peer_id: str
    ) -> None:
        """An equivocation surfaced (reference `tryAddVote`'s
        ErrVoteConflictingVotes branch — which the reference, like the
        seed here, used to throw away). Both votes carry verified
        signatures by construction, so the pair IS the proof: build
        DuplicateVoteEvidence and feed the pool (which WALs, gossips on
        0x38, and surfaces it to the next proposal)."""
        from tendermint_tpu.types.evidence import DuplicateVoteEvidence

        evidence = DuplicateVoteEvidence.make(err.vote_a, err.vote_b)
        FLIGHT.record(
            "evidence_detected",
            validator=evidence.address.hex()[:12],
            height=evidence.height,
            round=evidence.vote_a.round,
            type=evidence.vote_a.type,
            peer=peer_id[:12],
        )
        _log_mod.kv(
            _log_mod.logger("consensus"),
            _logging.WARNING,
            "conflicting votes detected",
            validator=evidence.address.hex()[:12],
            height=evidence.height,
            round=evidence.vote_a.round,
        )
        if self.evidence_pool is None:
            return
        try:
            self.evidence_pool.add_evidence(
                evidence, val_set=self._evidence_val_set(evidence.height)
            )
        except ValidationError:
            # locally detected pairs verified on entry; a failure here
            # means the offender left the retained valsets — drop it
            pass

    def _handle_vote(self, vote: Vote, peer_id: str, preverified: bool = False) -> None:
        """Reference `tryAddVote/addVote :1318-1453`."""
        try:
            self._handle_vote_inner(vote, peer_id, preverified)
        except ErrVoteConflictingVotes as e:
            self._found_conflicting_votes(e, peer_id)
            # The conflict can surface AFTER the tally moved: a vote for
            # a peer-maj23-tracked block is counted first, raises second
            # (`VoteSet._add_verified_vote`), and that count may have
            # JUST tipped +2/3. Swallowing the error without running the
            # post-add transitions wedged the height permanently (no
            # later vote re-triggers them — duplicates don't re-add).
            # The transition handlers are guarded + idempotent, so run
            # them unconditionally for current-height votes.
            if vote.height == self.height:
                if vote.type == VOTE_TYPE_PREVOTE:
                    self._on_prevote_added(vote)
                elif vote.type == VOTE_TYPE_PRECOMMIT:
                    self._on_precommit_added(vote)
        except (
            ErrVoteInvalidSignature,
            ErrVoteNonDeterministicSignature,
        ) as e:
            # forged/malleated signature: adversarial input, not noise —
            # debit the sender (a garbage-sig flood bans it) and move on
            # without letting the error reach the loop's traceback dump
            self._report_misbehavior(peer_id, "bad_sig", str(e))
        except (
            ErrVoteInvalidValidatorAddress,
            ErrVoteInvalidValidatorIndex,
        ) as e:
            self._report_misbehavior(peer_id, "bad_vote", str(e))
        except ErrVoteUnexpectedStep:
            # A vote whose (height, round, type) misses every live
            # tally — a straggler from a round the node has moved past.
            # Routine under WAN delay/reordering; never actionable and
            # must not take the receive loop down with it.
            pass

    def _handle_vote_inner(
        self, vote: Vote, peer_id: str, preverified: bool = False
    ) -> None:
        # LastCommit catchup: precommit for height-1 while in NewHeight step
        if vote.height + 1 == self.height:
            if (
                self.step == RoundStepType.NEW_HEIGHT
                and vote.type == VOTE_TYPE_PRECOMMIT
                and self.last_commit is not None
            ):
                if vote.round != self.last_commit.round:
                    # last_commit only tallies the round the block
                    # actually committed in. Under WAN reordering a
                    # straggler precommit from an earlier round of H-1
                    # is benign gossip noise — drop it instead of
                    # letting VoteSet raise ErrVoteUnexpectedStep,
                    # which would kill the receive loop.
                    return
                if self.last_commit.add_vote(
                    vote, verifier=self.verifier, preverified=preverified
                ):
                    self.event_switch.fire(ev.EVENT_VOTE, ev.EventDataVote(vote))
                    if (
                        self.config.skip_timeout_commit
                        and self.last_commit.has_all()
                    ):
                        # every precommit of H-1 is in: nothing left for
                        # the commit pacing to gather — start round 0 now
                        # (reference `handleMsg`'s skipTimeoutCommit leg)
                        self._enter_new_round(self.height, 0)
                elif self.gossip is not None and peer_id:
                    # catchup precommit we already tallied: the sender
                    # re-gossiped a known vote (own re-queues have
                    # peer_id="" and don't count)
                    self.gossip.redundant("vote", len(vote.encode()))
            return
        if vote.height != self.height:
            return

        # JOIN BARRIER: tallying a current-height vote binds its
        # validator index to a pubkey — that mapping must be the
        # post-EndBlock one. (Height-1 precommits above tally into
        # last_commit, whose valset was final before the pipeline
        # launched — they ride the overlap freely.)
        self._join_apply("vote_tally")
        added = self.votes.add_vote(
            vote, peer_id, verifier=self.verifier, preverified=preverified
        )
        if not added:
            # a VoteSet exact-duplicate add — before the gossip
            # observatory this wasted wire traffic vanished silently
            if self.gossip is not None and peer_id:
                self.gossip.redundant("vote", len(vote.encode()))
            return
        if self.gossip is not None:
            # first delivery of this (height, round, validator) vote on
            # this node: the propagation-map stamp gossip_report merges
            # across nodes (bounded like VoteArrivalRollup)
            self.gossip.first_seen(
                "vote", vote.height, vote.round, vote.validator_index
            )
        self.event_switch.fire(ev.EVENT_VOTE, ev.EventDataVote(vote))

        if vote.type == VOTE_TYPE_PREVOTE:
            self._on_prevote_added(vote)
        elif vote.type == VOTE_TYPE_PRECOMMIT:
            self._on_precommit_added(vote)

    def _on_prevote_added(self, vote: Vote) -> None:
        prevotes = self.votes.prevotes(vote.round)
        block_id = prevotes.two_thirds_majority()

        # POL unlock (reference `:1400-1420`): a newer-round polka for a
        # different block releases our lock.
        if (
            self.locked_block is not None
            and self.locked_round < vote.round <= self.round
            and block_id is not None
            and not self.locked_block.hash_to(block_id.hash)
        ):
            self.locked_round = -1
            self.locked_block = None
            self.locked_block_parts = None
            self.event_switch.fire(ev.EVENT_UNLOCK, self._rs_event())

        if self.round < vote.round and prevotes.has_two_thirds_any():
            # round skip
            self._enter_new_round(self.height, vote.round)
        elif self.round == vote.round:
            if block_id is not None and (
                self._is_proposal_complete() or block_id.is_zero()
            ):
                self._enter_precommit(self.height, vote.round)
            elif prevotes.has_two_thirds_any() and self.step == RoundStepType.PREVOTE:
                self._enter_prevote_wait(self.height, vote.round)
        elif (
            self.proposal is not None
            and 0 <= self.proposal.pol_round == vote.round
            and self._is_proposal_complete()
        ):
            self._enter_prevote(self.height, self.round)

    def _on_precommit_added(self, vote: Vote) -> None:
        precommits = self.votes.precommits(vote.round)
        block_id = precommits.two_thirds_majority()
        if block_id is not None:
            self._enter_new_round(self.height, vote.round)
            self._enter_precommit(self.height, vote.round)
            if not block_id.is_zero():
                self._enter_commit(self.height, vote.round)
                if self.config.skip_timeout_commit and precommits.has_all():
                    self._enter_new_round(self.height, 0)
            else:
                self._enter_precommit_wait(self.height, vote.round)
        elif self.round <= vote.round and precommits.has_two_thirds_any():
            self._enter_new_round(self.height, vote.round)
            self._enter_precommit_wait(self.height, vote.round)

    def _sign_add_vote(self, type_: int, hash_: bytes, header: PartSetHeader) -> None:
        """Reference `signAddVote :1471-1487`."""
        if self.priv_validator is None or not self.validators.has_address(
            self.priv_validator.address
        ):
            return
        idx, _ = self.validators.get_by_address(self.priv_validator.address)
        vote = Vote(
            validator_address=self.priv_validator.address,
            validator_index=idx,
            height=self.height,
            round=self.round,
            timestamp=time_mod.time_ns(),
            type=type_,
            block_id=BlockID(hash_, header),
        )
        try:
            vote = self.priv_validator.sign_vote(self.state.chain_id, vote)
        except ErrDoubleSign:
            return
        # Enqueue our own vote instead of handling it inline (reference
        # `sendInternalMessage :1471-1487`): a synchronous _handle_vote
        # here re-enters the transition functions — _enter_precommit can
        # finalize the height mid-call, after which the CALLER's
        # still-running transition (e.g. _on_precommit_added's
        # `_enter_commit(self.height, ...)`) reads the NEW height's
        # round state and corrupts it (observed as a fatal "enterCommit
        # without +2/3 precommits" under multi-node gossip load). The
        # queue item is WAL'd by the receive loop like any other input.
        # Vote creation is a trace edge: a vote cast FOR this height's
        # block is causally part of that block's trace (which the
        # proposer adopted from its first traced tx), so it re-hops the
        # block context — the whole decision path of a traced tx shares
        # one trace_id, node to node. Votes before any block context
        # exists (nil prevotes, early rounds) mint their own,
        # head-sampled.
        if self._proposal_ctx is not None:
            ctx = self._proposal_ctx.rehop()
        else:
            ctx = _trace.mint(self.priv_validator.address.hex()[:12])
        # self_signed: the tally trusts the signature it just produced —
        # re-verifying our own fresh vote through the device path cost a
        # full batch-of-1 launch per vote for nothing (replay clears the
        # flag: WAL records re-verify)
        self._queue.put(
            MsgRecord(vote, "", ctx=ctx, arrived=time_mod.time(), self_signed=True)
        )
