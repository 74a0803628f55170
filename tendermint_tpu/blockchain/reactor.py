"""Fast-sync reactor: catch a fresh node up by downloading + batch-
verifying blocks (reference `blockchain/reactor.go`, channel 0x40).

TPU-first twist on the reference's serial verify loop
(`reactor.go:242-289`, one `VerifyCommit` per block): the sync loop
gathers a WINDOW of consecutive downloaded blocks and verifies all
their commits in ONE device batch via
`ValidatorSet.verify_commit_batched` (the valset-table kernel path —
BASELINE config 3's 50k-blocks-at-1000-validators shape), then applies
them with the per-block signature pass skipped.

Trust rule per reference: block H is applied only once +2/3 of the
current validators are seen precommitting it — H's commit travels as
block H+1's LastCommit, so the window always holds one more block than
it applies.

Who is blamed when that fails (`_redo`; docs/BYZANTINE.md): a commit
that does not verify was carried by block H+1, so H+1's server is
debited, not H's and not the window's first; the entries of the window
before the one the verdict names are verified and are applied; and the
pool forgets what the debited peer delivered and nothing else.
"""

from __future__ import annotations

import threading
import time
from functools import partial

from tendermint_tpu.codec.binary import Reader, Writer
from tendermint_tpu.p2p.connection import ChannelDescriptor
from tendermint_tpu.p2p.peer import Peer
from tendermint_tpu.p2p.switch import Reactor
from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.state.execution import apply_block
from tendermint_tpu.telemetry import TRACER
from tendermint_tpu.telemetry import launchlog as _launchlog
from tendermint_tpu.telemetry.metrics import (
    FASTSYNC_BLOCKS_APPLIED,
    FASTSYNC_CHILD_STAGES,
    FASTSYNC_PREFIX_BLOCKS_APPLIED,
    FASTSYNC_REDO_BLOCKS_DROPPED,
    FASTSYNC_REDO_RECOVER_SECONDS,
    FASTSYNC_REDOS,
    FASTSYNC_STAGE_CPU_SECONDS,
    FASTSYNC_STAGE_SECONDS,
    FASTSYNC_STAGES,
    FASTSYNC_WINDOWS,
)
from tendermint_tpu.types.block import Block
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.errors import ValidationError
from tendermint_tpu.utils.log import kv, logger
import logging

BLOCKCHAIN_CHANNEL = 0x40

_MSG_BLOCK_REQUEST = 0x01
_MSG_BLOCK_RESPONSE = 0x02
_MSG_NO_BLOCK = 0x03
_MSG_STATUS_REQUEST = 0x04
_MSG_STATUS_RESPONSE = 0x05

_SYNC_TICK_S = 0.01
_STATUS_INTERVAL_S = 2.0  # reference statusUpdateIntervalSeconds=10, scaled
VERIFY_WINDOW = 16  # commits batched per device call

# Verify windows kept in flight on device at once (docs/PERFORMANCE.md).
# 2 is the classic software pipeline: window K's verdict flies while the
# host preps K+1's part sets/lanes and applies K-1's blocks via ABCI.
# 1 degenerates to the synchronous verify->apply loop; >2 only helps
# when launches are slower than applies.
PIPELINE_DEPTH = 2

_STAGE_SECONDS = {
    s: FASTSYNC_STAGE_SECONDS.labels(stage=s)
    for s in FASTSYNC_STAGES + FASTSYNC_CHILD_STAGES
}
_STAGE_CPU_SECONDS = {
    s: FASTSYNC_STAGE_CPU_SECONDS.labels(stage=s) for s in _STAGE_SECONDS
}


def _observe_stage(name: str, seconds: float, cpu_seconds: float) -> None:
    _STAGE_SECONDS[name].observe(seconds)
    _STAGE_CPU_SECONDS[name].inc(cpu_seconds)


def _stage(name: str, window: dict | None = None):
    """Stopwatch around one stage of a block's life (`TRACER.stage`):
    the duration goes to `tendermint_fastsync_stage_seconds{stage}`, the
    CPU time its thread took in it to
    `tendermint_fastsync_stage_cpu_seconds_total{stage}` and, for a
    stage that belongs to a verify window, the duration into that
    window's per-stage seconds (its `fastsync.window` span); the stretch
    shows as `fastsync.<name>` in a profiler trace."""

    def sink(seconds: float, cpu_seconds: float) -> None:
        _observe_stage(name, seconds, cpu_seconds)
        if window is not None:
            window[name] = window.get(name, 0.0) + seconds

    return TRACER.stage("fastsync." + name, sink)


def _enc(tag: int, *fields) -> bytes:
    w = Writer().uvarint(tag)
    for f in fields:
        if isinstance(f, int):
            w.uvarint(f)
        else:
            w.bytes(f)
    return w.build()


def decode_message(payload: bytes):
    r = Reader(payload)
    tag = r.uvarint()
    if tag == _MSG_BLOCK_REQUEST:
        return ("block_request", r.uvarint())
    if tag == _MSG_BLOCK_RESPONSE:
        return ("block_response", Block.decode(r.bytes()))
    if tag == _MSG_NO_BLOCK:
        return ("no_block", r.uvarint())
    if tag == _MSG_STATUS_REQUEST:
        return ("status_request", None)
    if tag == _MSG_STATUS_RESPONSE:
        return ("status_response", r.uvarint())
    raise ValueError(f"unknown blockchain message tag {tag:#x}")


class BlockchainReactor(Reactor):
    """Serves stored blocks to peers; optionally fast-syncs from them.

    `on_caught_up(state)` fires once when the pool has drained to every
    peer's advertised height — the node uses it to start consensus
    (reference `SwitchToConsensus reactor.go:233-241`).
    """

    def __init__(
        self,
        state,
        store,
        app_conn,
        fast_sync: bool = False,
        on_caught_up=None,
        verifier=None,
        tx_indexer=None,
        hasher=None,
        deferred: bool = False,
        pipeline_depth: int | None = None,
        follow: bool = False,
    ) -> None:
        super().__init__()
        self.state = state
        self.store = store
        self.app_conn = app_conn
        self.fast_sync = fast_sync
        self.on_caught_up = on_caught_up
        # follow mode (read replicas): never exit fast-sync — keep
        # tailing the chain as peers advance instead of handing off to
        # consensus. `is_caught_up` then just means "at the tip".
        self.follow = follow
        self.verifier = verifier
        self.tx_indexer = tx_indexer
        self.hasher = hasher
        # `deferred` parks the sync thread until state sync restores a
        # snapshot and calls begin_fast_sync() — the pool's start height
        # is unknowable before the restore lands
        self.deferred = deferred
        self.pool = BlockPool(start_height=store.height + 1)
        self.pipeline_depth = max(
            1, PIPELINE_DEPTH if pipeline_depth is None else pipeline_depth
        )
        self._dispatch_queue = None  # lazy: only fast-syncing nodes need it
        self._running = False
        self._thread: threading.Thread | None = None
        self.blocks_synced = 0
        self._progress_mark = time.monotonic()
        # height a redo was called at -> when: closed by that height's
        # apply (tendermint_fastsync_redo_recover_seconds)
        self._redone: dict[int, float] = {}
        self._redo_pending = False

    # -- reactor interface -------------------------------------------------

    def get_channels(self) -> list[ChannelDescriptor]:
        return [ChannelDescriptor(BLOCKCHAIN_CHANNEL, priority=5)]

    def on_start(self) -> None:
        self._running = True
        if self.fast_sync and not self.deferred:
            self._start_sync_thread()

    def _start_sync_thread(self) -> None:
        self._thread = threading.Thread(
            target=self._sync_routine, name="fastsync", daemon=True
        )
        self._thread.start()

    def begin_fast_sync(self, state=None) -> None:
        """State-sync handoff: adopt the restored state, re-aim the pool
        at the (snapshot-height advanced) store head, and start syncing
        the tail. The node calls this from the statesync reactor's
        `on_synced` (with state=None when state sync gave up and plain
        fast-sync from the current state proceeds)."""
        if not self.deferred:
            return
        self.deferred = False
        if state is not None:
            self.state = state
        self.pool = BlockPool(start_height=self.store.height + 1)
        # re-learn peer heights on the fresh pool (status responses that
        # arrived during state sync went to the old one)
        if self.switch is not None:
            self.switch.broadcast(BLOCKCHAIN_CHANNEL, _enc(_MSG_STATUS_REQUEST))
        if self._running and self.fast_sync:
            self._start_sync_thread()

    def on_stop(self) -> None:
        self._running = False
        if self._dispatch_queue is not None:
            self._dispatch_queue.close()

    def add_peer(self, peer: Peer) -> None:
        # advertise our height + learn theirs (reference `AddPeer`)
        peer.try_send(
            BLOCKCHAIN_CHANNEL, _enc(_MSG_STATUS_RESPONSE, self.store.height)
        )
        peer.try_send(BLOCKCHAIN_CHANNEL, _enc(_MSG_STATUS_REQUEST))

    def remove_peer(self, peer: Peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    def receive(self, chan_id: int, peer: Peer, payload: bytes) -> None:
        with TRACER.stage("fastsync.decode") as decode:
            kind, arg = decode_message(payload)
        if kind == "block_request":
            block = self.store.load_block(arg)
            if block is not None:
                peer.try_send(
                    BLOCKCHAIN_CHANNEL, _enc(_MSG_BLOCK_RESPONSE, block.encode())
                )
            else:
                peer.try_send(BLOCKCHAIN_CHANNEL, _enc(_MSG_NO_BLOCK, arg))
        elif kind == "block_response":
            _observe_stage("decode", decode.seconds, decode.cpu_seconds)
            self.pool.add_block(peer.id, arg, size=len(payload))
        elif kind == "status_request":
            peer.try_send(
                BLOCKCHAIN_CHANNEL, _enc(_MSG_STATUS_RESPONSE, self.store.height)
            )
        elif kind == "status_response":
            self.pool.set_peer_height(peer.id, arg)
        # no_block: ignore (the request will time out and reassign)

    # -- sync loop ---------------------------------------------------------

    def _send_request(self, peer_id: str, height: int) -> None:
        for p in self.switch.peers() if self.switch else []:
            if p.id == peer_id:
                p.try_send(BLOCKCHAIN_CHANNEL, _enc(_MSG_BLOCK_REQUEST, height))
                return

    def _sync_routine(self) -> None:
        last_status = 0.0
        while self._running and self.fast_sync:
            now = time.monotonic()
            if now - last_status > _STATUS_INTERVAL_S:
                last_status = now
                if self.switch is not None:
                    self.switch.broadcast(
                        BLOCKCHAIN_CHANNEL, _enc(_MSG_STATUS_REQUEST)
                    )
            requests, evictions = self.pool.schedule_requests(now)
            for peer_id in evictions:
                self._drop_peer(peer_id, "fast-sync request timeout")
            for peer_id, height in requests:
                self._send_request(peer_id, height)
            try:
                self._try_sync()
            except Exception:
                # _try_sync handles bad blocks via redo; anything else
                # (e.g. app execution failure) must not kill the sync
                # thread silently — log and keep going
                import logging

                logging.getLogger(__name__).exception("fast-sync step failed")
                time.sleep(0.5)
            if not self.follow and self.pool.is_caught_up():
                self.fast_sync = False
                if self.on_caught_up is not None:
                    self.on_caught_up(self.state)
                return
            if self._redo_pending:
                # a redo freed heights: hand them to the remaining peers
                # now, as an evicted peer's are, not a tick later
                self._redo_pending = False
                continue
            with _stage("starved"):
                time.sleep(_SYNC_TICK_S)

    def tip_lag(self) -> int:
        """Heights between the best-known peer tip and our store head
        (0 at the tip). Follow-mode replicas stay in fast-sync forever,
        so health derives their readiness from this instead of the
        `fast_sync` flag."""
        return max(0, self.pool.max_peer_height() - self.store.height)

    def _queue(self):
        """The reactor-owned dispatch queue (one per fast-syncing node,
        so another consumer's unjoined handles can't backpressure us)."""
        if self._dispatch_queue is None:
            from tendermint_tpu.services.dispatch import DispatchQueue

            self._dispatch_queue = DispatchQueue(
                depth=self.pipeline_depth, name="fastsync"
            )
        return self._dispatch_queue

    def _try_sync(self) -> None:
        """Verify + apply as many downloaded blocks as possible, commits
        batched per device call (reference `trySync` loop `:242-289`) —
        run as a SOFTWARE PIPELINE over the async dispatch layer: while
        window K's commit verdict is in flight on device, the host preps
        window K+1's part sets/lanes and applies window K-1's blocks
        through ABCI. Windows join strictly in submission order; a
        verdict failure applies the failed window's verified prefix and
        drains the younger windows in flight WITHOUT applying their
        blocks (they chain off the fault, and are prepared again from
        the pool once the redone heights are back).
        """
        from collections import deque

        pipeline: "deque" = deque()  # submitted windows, oldest first
        try:
            while True:
                cursor = (
                    pipeline[-1]["next_height"]
                    if pipeline
                    else self.pool.height
                )
                entry = None
                if len(pipeline) < self.pipeline_depth:
                    entry = self._prep_window(cursor)
                if isinstance(entry, dict):
                    pipeline.append(entry)
                    continue  # keep filling until depth / no window
                if entry == "redo":
                    # `cursor`'s window began with a refuted block, which
                    # the pool has forgotten with its server's others; the
                    # OLDER windows in flight stand on their own verdicts
                    # — apply them before leaving
                    self._drain(pipeline, apply=True)
                    return
                if pipeline:
                    # depth reached, or no next window yet: join the
                    # oldest verdict and run its ABCI applies — this is
                    # the overlap stage, younger windows are in flight
                    if not self._join_and_apply(pipeline.popleft()):
                        self._drain(pipeline, apply=False)
                        return
                    continue
                if entry == "boundary":
                    # valset changed at the very next block: verify it
                    # alone via its successor's commit the slow way
                    # (pipeline is empty here, so the valset is current)
                    window = self.pool.peek(2)
                    if len(window) < 2:
                        return
                    self._sync_one(window[0], window[1])
                    continue
                return  # nothing downloaded, nothing in flight
        except BaseException:
            # non-verification failure (app execution, dispatch layer):
            # release the in-flight windows' queue slots, then let the
            # sync routine's catch-all log and retry
            self._drain(pipeline, apply=False)
            raise

    def _prep_window(self, cursor: int):
        """Host-prep stage: claim up to VERIFY_WINDOW applyable blocks
        at `cursor`, build part sets + block ids, check commit linkage,
        and submit ONE batched commit verify to the dispatch queue.

        Returns the in-flight window entry, None (no full window there
        yet), "boundary" (valset changes at `cursor` — needs a drained
        pipeline + `_sync_one`), or "redo" (a block of the window was
        refuted and no commit before it is left to verify).

        A block refuted here (its id is not the one its successor's
        commit carries, or the commit it carries is malformed) is redone
        at once, and the window goes on without it: the blocks before it
        and the commits they carry, which the verdict still has to
        pass. The pool has a gap where the block was, so no younger
        window is prepared until it is fetched again."""
        t0 = time.time()
        stages: dict = {}
        with _stage("part_set", stages):
            claimed = self._claim_window(cursor)
        if not isinstance(claimed, tuple):
            return claimed
        blocks, parts, entries, cut, mismatch = claimed
        while True:
            if mismatch is not None:
                refuted = mismatch + self._who_lied(
                    blocks[mismatch + 1].last_commit,
                    blocks[mismatch].header.height,
                    stages,
                )
                cause, mismatch = "block_id", None
            else:
                try:
                    # the launch record names the heights it covers, so the
                    # ledger can say which backend answered for each height
                    with _stage("verify_submit", stages), _launchlog.tag(
                        height_lo=entries[0][1], height_hi=entries[-1][1]
                    ):
                        handle = self.state.validators.verify_commit_batched_async(
                            self.state.chain_id,
                            entries,
                            verifier=self.verifier,
                            queue=self._queue(),
                            consumer="fastsync",
                        )
                    break
                except ValidationError as e:
                    # malformed commit caught during prep: the block that
                    # carried it is the refused entry's successor
                    refuted, cause = getattr(e, "entry", 0) + 1, "prep"
            self._redo(blocks[refuted].header.height, cause)
            blocks, entries = blocks[:refuted], entries[: max(refuted - 1, 0)]
            cut = "pool_gap"
            if not entries:
                return "redo"
        return {
            "blocks": blocks,
            "parts": parts,
            "handle": handle,
            "apply_n": len(entries),
            "start_height": blocks[0].header.height,
            "next_height": blocks[0].header.height + len(entries),
            "t0": t0,
            "stages": stages,
            "cut": cut,
        }

    def _claim_window(self, cursor: int):
        """The `part_set` stage of `_prep_window`: (blocks, part sets,
        verify entries, cut, mismatch) for the window at `cursor`, or
        one of `_prep_window`'s None / "boundary". `mismatch` is None,
        or the index of the first block whose id is not the one its
        successor's commit carries; the entries then stop before it."""
        window = self.pool.peek(VERIFY_WINDOW + 1, from_height=cursor)
        if len(window) < 2:
            return None
        # the batch spans consecutive blocks under ONE valset — windows
        # beyond an EndBlock valset rotation never enter the pipeline,
        # their headers carry a different validators_hash
        val_hash = self.state.validators.hash()
        usable = 0
        for b in window:
            if b.header.validators_hash != val_hash:
                break
            usable += 1
        if usable < 2:
            return "boundary"
        # why the window ends where it does
        if usable < len(window):
            cut = "boundary"
        elif usable == VERIFY_WINDOW + 1:
            cut = "full"
        else:
            cut = "pool_gap"
        blocks = window[:usable]
        # commit for blocks[i] rides in blocks[i+1].last_commit; the
        # final block waits for its successor in a later window, so
        # only the applied prefix needs part sets / ids built
        apply_n = usable - 1
        parts = [b.make_part_set() for b in blocks[:apply_n]]
        block_ids = [
            BlockID(b.hash(), ps.header)
            for b, ps in zip(blocks[:apply_n], parts)
        ]
        entries = []
        for i in range(apply_n):
            commit = blocks[i + 1].last_commit
            if commit.block_id != block_ids[i]:
                return blocks, parts, entries, cut, i
            entries.append((block_ids[i], blocks[i].header.height, commit))
        return blocks, parts, entries, cut, None

    def _who_lied(self, commit, height: int, stages: dict) -> int:
        """`commit`, the last_commit of the block at `height` + 1, carries
        another id than the block's at `height`: 0 if that block's server
        lied, 1 if its successor's did. Decidable: either the commit's
        signatures verify over the id it carries (then more than 2/3
        signed another block at that height, and ours is not it), or
        they do not (the successor made its commit up). One synchronous
        K=1 verify against the current set, which is that height's
        (`_claim_window` holds a window to one set), on the default
        queue: the reactor's own may be full of windows in flight. Only
        this path pays for it."""
        with _stage("verify_wait", stages):
            signed = self._commit_verifies(commit.block_id, height, commit)
        return 0 if signed else 1

    def _commit_verifies(self, block_id, height: int, commit) -> bool:
        """One commit against the current set, synchronously (the K=1
        launch consensus makes too); its launch record names the height,
        as a window's names its own."""
        try:
            with _launchlog.tag(height_lo=height, height_hi=height):
                self.state.validators.verify_commit(
                    self.state.chain_id,
                    block_id,
                    height,
                    commit,
                    verifier=self.verifier,
                    consumer="fastsync",
                )
        except ValidationError:
            return False
        return True

    def _join_and_apply(self, entry) -> bool:
        """Join one window's in-flight verdict, then store + apply its
        blocks. False means the window failed past its verified prefix
        and the refuted block was redone — the caller must discard
        younger in-flight windows."""
        try:
            return self._apply_window(entry)
        finally:
            self._close_window(entry)

    def _apply_window(self, entry) -> bool:
        stages = entry["stages"]
        blocks, parts = entry["blocks"], entry["parts"]
        refused = None
        verified = entry["apply_n"]
        try:
            with _stage("verify_wait", stages):
                entry["handle"].result()
        except ValidationError as e:
            # the verdict names the first entry that failed; the ones
            # before it passed (`ErrCommitRefused`) and are applied
            refused = getattr(e, "entry", 0)
            verified = refused if getattr(e, "prefix_verified", False) else 0
        for i in range(verified):
            commit = blocks[i + 1].last_commit
            try:
                self._store_and_apply(blocks[i], parts[i], commit, stages)
            except ValidationError:
                # commit verified but the block body is inconsistent
                # (possible only past a 2/3-byzantine signer set):
                # redo the block + drop its server rather than spin
                self._redo(blocks[i].header.height, "body")
                return False
            self._log_progress()
        if refused is None:
            return True
        # the commit that failed travelled in the NEXT block: that
        # block's server made it up. The refused entry's own block,
        # whose id the commit does carry, is neither proved nor refuted
        # and stays in the pool
        FASTSYNC_PREFIX_BLOCKS_APPLIED.inc(verified)
        self._redo(
            blocks[refused + 1].header.height, "verdict", prefix_applied=verified
        )
        return False

    def _store_and_apply(self, block, parts, commit, stages: dict) -> None:
        with _stage("store", stages):
            self.store.save_block(block, parts, commit)
        apply_block(
            self.state,
            block,
            parts.header,
            self.app_conn,
            verifier=self.verifier,
            tx_indexer=self.tx_indexer,
            commit_preverified=True,
            hasher=self.hasher,
            stage=partial(_stage, window=stages),
        )
        self.pool.pop()
        self.blocks_synced += 1
        FASTSYNC_BLOCKS_APPLIED.inc()
        if self._redone:
            self._recovered(block.header.height)

    def _close_window(self, entry) -> None:
        """One `fastsync.window` span and one count a window, from its
        prep to its last apply: the launch record of the same window
        carries the same `height_lo`, so span and launch join."""
        FASTSYNC_WINDOWS.labels(cut=entry["cut"]).inc()
        TRACER.add(
            "fastsync.window",
            entry["t0"],
            time.time(),
            height_lo=entry["start_height"],
            height_hi=entry["next_height"] - 1,
            cut=entry["cut"],
            **{f"{k}_s": round(v, 6) for k, v in entry["stages"].items()},
        )

    def _drain(self, pipeline, apply: bool) -> None:
        """Empty the pipeline in submission order. While `apply` holds
        and windows keep verifying, their blocks go through ABCI; after
        the first failure (or when draining a stale suffix) remaining
        verdicts are joined ONLY to release their dispatch-queue slots —
        stale blocks are never applied."""
        while pipeline:
            entry = pipeline.popleft()
            if apply:
                apply = self._join_and_apply(entry)
            else:
                try:
                    with _stage("verify_wait"):
                        entry["handle"].result()
                # tmlint: disable=T001 -- stale-suffix drain: joined only to release the dispatch slot, the failure was already handled upstream
                except Exception:
                    pass

    def _log_progress(self) -> None:
        """blocks/s every 100 blocks (reference `reactor.go:281-286`)."""
        if self.blocks_synced % 100 != 0:
            return
        now = time.monotonic()
        rate = 100.0 / max(now - self._progress_mark, 1e-9)
        self._progress_mark = now
        kv(
            logger("blockchain"),
            logging.INFO,
            "fast-sync progress",
            height=self.pool.height - 1,
            blocks_per_s=round(rate, 1),
        )

    def _sync_one(self, block, successor) -> None:
        if successor is None:
            return
        height = block.header.height
        window = {
            "t0": time.time(),
            "stages": {},
            "cut": "boundary",
            "start_height": height,
            "next_height": height + 1,
        }
        try:
            self._sync_one_window(block, successor.last_commit, window["stages"])
        finally:
            self._close_window(window)

    def _sync_one_window(self, block, commit, stages: dict) -> None:
        with _stage("part_set", stages):
            parts = block.make_part_set()
            block_id = BlockID(block.hash(), parts.header)
        height = block.header.height
        # synchronous: the whole verify is a wait nothing hides
        if commit.block_id != block_id:
            self._redo(height + self._who_lied(commit, height, stages), "block_id")
            return
        with _stage("verify_wait", stages):
            signed = self._commit_verifies(block_id, height, commit)
        if not signed:
            # the commit is the successor's last_commit: its server's lie
            self._redo(height + 1, "verdict")
            return
        try:
            self._store_and_apply(block, parts, commit, stages)
        except ValidationError:
            self._redo(height, "body")

    def _redo(self, height: int, cause: str, prefix_applied: int = 0) -> None:
        """The block at `height` cannot be what the chain committed:
        forget it with whatever else its server delivered, and debit and
        drop that server (reference `RedoRequest` + peer eviction). Such
        a block cannot be produced honestly, so the server's misbehavior
        score is debited and a lying fast-sync peer gets banned, not just
        disconnected-and-redialed. One `fastsync.redo` span and one count
        a call; the time to the apply of `height` is the redo's cost
        (`_recovered`)."""
        t0 = time.time()
        bad_peer, blamed, others = self.pool.redo(height)
        FASTSYNC_REDOS.labels(cause=cause).inc()
        FASTSYNC_REDO_BLOCKS_DROPPED.labels(whose="blamed").inc(blamed)
        FASTSYNC_REDO_BLOCKS_DROPPED.labels(whose="others").inc(others)
        self._redone.setdefault(height, time.monotonic())
        self._redo_pending = True
        if bad_peer:
            if self.switch is not None:
                self.switch.report_misbehavior(
                    bad_peer, "forged_block", detail=f"height {height}"
                )
            self._drop_peer(bad_peer, "bad fast-sync block")
        TRACER.add(
            "fastsync.redo",
            t0,
            time.time(),
            height=height,
            cause=cause,
            peer=(bad_peer or "")[:12],
            dropped_blamed=blamed,
            dropped_others=others,
            prefix_applied=prefix_applied,
        )

    def _recovered(self, height: int) -> None:
        """`height` was applied: close every redo called at or below it."""
        now = time.monotonic()
        for h in [h for h in self._redone if h <= height]:
            FASTSYNC_REDO_RECOVER_SECONDS.observe(now - self._redone.pop(h))

    def _drop_peer(self, peer_id: str, reason: str) -> None:
        self.pool.remove_peer(peer_id)
        if self.switch is not None:
            for p in self.switch.peers():
                if p.id == peer_id:
                    self.switch.stop_peer_for_error(p, reason)
                    return
