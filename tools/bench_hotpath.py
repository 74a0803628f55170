"""Hot-path bench driven THROUGH the telemetry registry.

Exercises the verify/hash service backends and the consensus-WAL fsync
path, then derives `BENCH_hotpath.json` from the same histograms the
node exports on `GET /metrics` — so bench numbers and production
telemetry can never disagree about what was measured.

Backend selection is automatic: on CPU (`JAX_PLATFORMS=cpu`, the CI
shape) only the host backends run — no XLA kernel compiles, finishes in
seconds. On a TPU backend the device verifier, the valset-table
verifier, and the device Merkle tree run too (first run pays compiles
unless the persistent executable cache is warm).

    JAX_PLATFORMS=cpu python tools/bench_hotpath.py          # CI shape
    python tools/bench_hotpath.py --out BENCH_hotpath.json   # device shape

Output: one JSON line on stdout + the JSON file (default
`BENCH_hotpath.json` in the CWD).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.utils.jax_cache import enable_persistent_cache

enable_persistent_cache()


def _make_sigs(n: int):
    from tendermint_tpu.crypto.keys import gen_priv_key

    privs = [gen_priv_key(bytes([i % 256]) * 32) for i in range(min(64, n))]
    msgs = [
        b'{"chain_id":"hotpath","vote":{"height":7,"round":0,"index":%d}}' % i
        for i in range(n)
    ]
    sigs = [privs[i % len(privs)].sign(m) for i, m in enumerate(msgs)]
    pubs = [privs[i % len(privs)].pub_key.data for i in range(n)]
    return pubs, msgs, sigs


def drive_verify_host(sizes, reps) -> None:
    from tendermint_tpu.services.verifier import HostBatchVerifier

    v = HostBatchVerifier()
    for n in sizes:
        pubs, msgs, sigs = _make_sigs(n)
        triples = list(zip(pubs, msgs, sigs))
        for _ in range(reps):
            out = v.verify_batch(triples)
            assert bool(out.all()), "host verify must pass on valid sigs"


def drive_verify_device(sizes, reps) -> None:
    from tendermint_tpu.services.verifier import DeviceBatchVerifier

    v = DeviceBatchVerifier(min_device_batch=1)
    for n in sizes:
        pubs, msgs, sigs = _make_sigs(n)
        triples = list(zip(pubs, msgs, sigs))
        for _ in range(reps):
            v.verify_batch(triples)


def drive_verify_tables(n_vals: int, stack: int, reps: int) -> None:
    from tendermint_tpu.services.verifier import TableBatchVerifier

    v = TableBatchVerifier(min_device_batch=1)
    pubs, msgs, sigs = _make_sigs(n_vals)
    commits = [(list(msgs), list(sigs))] * stack
    for _ in range(reps):
        v.verify_commits(pubs, commits)


def drive_hash(sizes, reps, backend: str) -> None:
    from tendermint_tpu.services.hasher import TreeHasher

    h = TreeHasher(backend=backend, min_device_leaves=2)
    for n in sizes:
        items = [b"leaf-%d" % i for i in range(n)]
        for _ in range(reps):
            h.root_from_items(items)


def drive_statesync(payload_kb: int, chunk_size: int, reps: int) -> None:
    """Snapshot take + full chunk-set verification through the service
    seam — fills tendermint_statesync_snapshot_seconds /
    _chunk_verify_seconds exactly as a serving/restoring node would."""
    from tendermint_tpu.db.kv import MemDB
    from tendermint_tpu.services.hasher import TreeHasher
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.statesync.snapshot import SnapshotStore, verify_chunks
    from tendermint_tpu.testing.nemesis import make_genesis

    genesis, _ = make_genesis(4, chain_id="bench-statesync")
    hasher = TreeHasher(backend="host")
    app_state = os.urandom(payload_kb * 1024)
    for _ in range(reps):
        st = make_genesis_state(MemDB(), genesis)
        st.last_block_height = 5
        st.app_hash = b"\xab" * 20
        store = SnapshotStore(MemDB(), hasher=hasher, chunk_size=chunk_size)
        m = store.take(st, app_state)
        chunks = [store.load_chunk(m.height, m.format, i) for i in range(m.chunks)]
        verify_chunks(m, chunks, hasher)


def statesync_summary() -> dict | None:
    n_snap, t_snap, snap_p50, snap_p99 = _histo(
        "tendermint_statesync_snapshot_seconds"
    )
    n_ver, t_ver, ver_p50, ver_p99 = _histo(
        "tendermint_statesync_chunk_verify_seconds"
    )
    if n_snap == 0 and n_ver == 0:
        return None
    out = {}
    if n_snap:
        out["snapshot"] = {
            "count": n_snap,
            "p50_ms": round(snap_p50 * 1e3, 3),
            "p99_ms": round(snap_p99 * 1e3, 3),
        }
    if n_ver:
        out["chunk_verify"] = {
            "count": n_ver,
            "p50_ms": round(ver_p50 * 1e3, 3),
            "p99_ms": round(ver_p99 * 1e3, 3),
        }
    return out


def _build_chain(n_blocks: int, n_vals: int):
    """A real committed chain (blocks + quorum commits + genesis) via
    the testing chain machinery — what the fast-sync bench replays."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.client import local_client_creator
    from tendermint_tpu.db.kv import MemDB
    from tendermint_tpu.state import apply_block, make_genesis_state
    from tendermint_tpu.testing.nemesis import make_genesis
    from tendermint_tpu.types import BlockID, Commit, Txs
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.types.vote import Vote

    genesis, privs = make_genesis(n_vals, chain_id="bench-fastsync")
    state = make_genesis_state(MemDB(), genesis)
    state.save()
    conns = local_client_creator(KVStoreApp())()
    blocks, commits = [], []
    for _ in range(n_blocks):
        height = state.last_block_height + 1
        last_commit = commits[-1] if commits else Commit.empty()
        block = Block.make_block(
            height=height,
            chain_id=state.chain_id,
            txs=Txs([]),
            last_commit=last_commit,
            last_block_id=state.last_block_id,
            time=genesis.genesis_time + height * 1_000_000_000,
            validators_hash=state.validators.hash(),
            app_hash=state.app_hash,
        )
        part_set = block.make_part_set()
        block_id = BlockID(block.hash(), part_set.header)
        vote_set = VoteSet(
            state.chain_id, height, 0, VOTE_TYPE_PRECOMMIT, state.validators
        )
        for i, priv in enumerate(privs):
            vote = Vote(
                validator_address=priv.address,
                validator_index=i,
                height=height,
                round=0,
                timestamp=genesis.genesis_time + height * 1_000_000_000,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            vote_set.add_vote(priv.sign_vote(state.chain_id, vote))
        apply_block(state, block, part_set.header, conns.consensus)
        blocks.append(block)
        commits.append(vote_set.make_commit())
    return genesis, blocks


class _LaunchLatencyVerifier:
    """CPU stand-in for the device verifier's dispatch shape: real host
    crypto preceded by an assumed fixed launch cost (--launch-ms; not
    measured on v5e) spent OFF the GIL — which
    is exactly what an in-flight kernel looks like to the host. Lets the
    checked-in CPU seed measure what the pipeline hides; on a TPU
    backend the bench uses the real table verifier instead."""

    def __init__(self, launch_s: float):
        from tendermint_tpu.services.verifier import HostBatchVerifier

        self._host = HostBatchVerifier()
        self._launch_s = launch_s

    def verify_batch(self, triples):
        time.sleep(self._launch_s)
        return self._host.verify_batch(triples)

    # async seam: whole call runs on the DispatchQueue worker
    launch_verify_batch = verify_batch

    def finalize_verify_batch(self, launched):
        return launched

    def verify_batch_async(self, triples, queue=None):
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(lambda: self.verify_batch(triples), kind="verify")


def _overlap_stats():
    """(count, sum) of the fastsync queue's overlap-ratio histogram."""
    n, total, _, _ = _histo("tendermint_dispatch_overlap_ratio", queue="fastsync")
    return n, total


def drive_fastsync_pipeline(
    n_blocks: int, n_vals: int, launch_ms: float, on_device: bool
) -> dict:
    """Replay one committed chain through the REAL
    `BlockchainReactor._try_sync` twice — pipeline depth 1 (the
    synchronous verify->apply baseline) vs the default overlapped depth
    — and report blocks/s plus the telemetry-measured overlap ratio."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.client import local_client_creator
    from tendermint_tpu.blockchain.reactor import PIPELINE_DEPTH, BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.db.kv import MemDB
    from tendermint_tpu.state import make_genesis_state

    genesis, blocks = _build_chain(n_blocks, n_vals)
    if on_device:
        from tendermint_tpu.services.resilient import ResilientVerifier
        from tendermint_tpu.services.verifier import TableBatchVerifier

        verifier = ResilientVerifier(TableBatchVerifier(min_device_batch=1))
        launch_ms = 0.0  # real launches, no emulation
    else:
        verifier = _LaunchLatencyVerifier(launch_ms / 1e3)

    def run(depth: int) -> float:
        state = make_genesis_state(MemDB(), genesis)
        state.save()
        store = BlockStore(MemDB())
        conns = local_client_creator(KVStoreApp())()
        reactor = BlockchainReactor(
            state=state,
            store=store,
            app_conn=conns.consensus,
            fast_sync=True,
            verifier=verifier,
            pipeline_depth=depth,
        )
        reactor.pool.set_peer_height("bench", len(blocks))
        for h, b in enumerate(blocks, start=1):
            reactor.pool._blocks[h] = (b, "bench")
        t0 = time.perf_counter()
        reactor._try_sync()
        dt = time.perf_counter() - t0
        assert store.height == len(blocks) - 1, (
            f"bench sync stalled at {store.height}"
        )
        return (len(blocks) - 1) / dt

    depth = max(2, PIPELINE_DEPTH)
    sync_bps = run(1)
    ov_n0, ov_s0 = _overlap_stats()
    pipelined_bps = run(depth)
    ov_n1, ov_s1 = _overlap_stats()
    overlap = (ov_s1 - ov_s0) / (ov_n1 - ov_n0) if ov_n1 > ov_n0 else 0.0
    return {
        "blocks": n_blocks,
        "validators": n_vals,
        "pipeline_depth": depth,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": not on_device,
        "sync_blocks_per_s": round(sync_bps, 1),
        "pipelined_blocks_per_s": round(pipelined_bps, 1),
        "speedup": round(pipelined_bps / sync_bps, 3),
        "overlap_ratio_mean": round(overlap, 3),
    }


def _salted_sigs(n: int, salt: bytes):
    """Like `_make_sigs` but with per-call-unique messages, so replay
    loops control exactly which triples repeat."""
    from tendermint_tpu.crypto.keys import gen_priv_key

    privs = [gen_priv_key(bytes([i % 256]) * 32) for i in range(min(64, n))]
    msgs = [
        b'{"chain_id":"hotpath","salt":"%s","vote":{"index":%d}}' % (salt, i)
        for i in range(n)
    ]
    sigs = [privs[i % len(privs)].sign(m) for i, m in enumerate(msgs)]
    pubs = [privs[i % len(privs)].pub_key.data for i in range(n)]
    return list(zip(pubs, msgs, sigs))


def drive_dedup_steady_state(heights: int, n_vals: int, launch_ms: float) -> dict:
    """Gossip-then-commit height replay through the dedup cache: each
    height's votes are verified once on gossip arrival and again when
    the commit seals the block — the exact redundancy the cache exists
    to remove. Cache-off pays the emulated launch twice per height
    (same CPU method as `fastsync_pipeline`); cache-on serves the
    commit pass from proven triples."""
    from tendermint_tpu.services.batcher import CoalescingVerifier

    height_triples = [
        _salted_sigs(n_vals, b"h%d" % h) for h in range(heights)
    ]

    def run(cache_size: int) -> float:
        v = CoalescingVerifier(
            _LaunchLatencyVerifier(launch_ms / 1e3),
            cache_size=cache_size,
            window_s=0.001,
        )
        try:
            total = 0
            t0 = time.perf_counter()
            for triples in height_triples:
                assert bool(v.verify_batch(triples).all())  # gossip drain
                assert bool(v.verify_batch(triples).all())  # commit seal
                total += 2 * len(triples)
            return total / (time.perf_counter() - t0)
        finally:
            v.close()

    def _cache_hits() -> float:
        from tendermint_tpu.telemetry import REGISTRY

        return REGISTRY.counter_value("tendermint_verify_cache_hits_total")

    off_vps = run(cache_size=0)
    h0 = _cache_hits()
    on_vps = run(cache_size=65536)
    return {
        "heights": heights,
        "validators": n_vals,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "cache_off_verifies_per_s": round(off_vps, 1),
        "cache_on_verifies_per_s": round(on_vps, 1),
        "speedup": round(on_vps / off_vps, 3),
        "cache_hits": int(_cache_hits() - h0),
    }


def drive_tracing_overhead(heights: int, n_vals: int, launch_ms: float) -> dict:
    """Bench guard for the distributed tracer: verifies/s on the
    dedup_steady_state replay with head-based sampling at the
    production default (1-in-64) must sit within 3% of tracing-off.
    The traced run exercises the real costs: the ambient thread-local
    read at every coalescer submit, plus flush/launch spans and flight
    events for the sampled heights."""
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.telemetry import tracectx as _tc

    height_triples = [
        _salted_sigs(n_vals, b"trace-h%d" % h) for h in range(heights)
    ]

    def run(sample: int) -> float:
        prev = os.environ.get(_tc.SAMPLE_ENV)
        os.environ[_tc.SAMPLE_ENV] = str(sample)
        v = CoalescingVerifier(
            _LaunchLatencyVerifier(launch_ms / 1e3),
            cache_size=65536,
            window_s=0.001,
        )
        try:
            total = 0
            t0 = time.perf_counter()
            for triples in height_triples:
                # the admission edge: head-sampled mint, then the whole
                # height's verify work runs with the context ambient
                # (exactly the consensus vote-drain shape)
                with _tc.use(_tc.mint("bench") if sample else None):
                    for consumer in ("consensus", "fastsync"):
                        assert bool(
                            v.verify_batch_async(triples, consumer=consumer)
                            .result(timeout=60)
                            .all()
                        )
                total += 2 * len(triples)
            return total / (time.perf_counter() - t0)
        finally:
            v.close()
            if prev is None:
                os.environ.pop(_tc.SAMPLE_ENV, None)
            else:
                os.environ[_tc.SAMPLE_ENV] = prev

    run(0)  # warmup: host-crypto/thread spin-up would bias the first run
    off_vps = run(0)
    on_vps = run(64)
    overhead_pct = 100.0 * (1.0 - on_vps / off_vps)
    return {
        "heights": heights,
        "validators": n_vals,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "sample_rate": 64,
        "tracing_off_verifies_per_s": round(off_vps, 1),
        "tracing_on_verifies_per_s": round(on_vps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "within_3pct": overhead_pct <= 3.0,
    }


def drive_profiler_overhead(heights: int, n_vals: int, launch_ms: float) -> dict:
    """Bench guard for the contention observatory (PR 12): verifies/s
    on the dedup_steady_state replay with the profiler OFF vs armed at
    the default 29 Hz WITH ranked-lock contention timing — the
    always-on-capable configuration — must sit within 3% of off. The
    armed run pays the real costs: the sampler walking every thread's
    stack ~29x/s, the per-acquire perf_counter pair + stat update on
    every instrumented lock (cache shards, coalescer window, dispatch
    locks), and the wait/hold histogram observes."""
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.telemetry.profiler import PROFILER
    from tendermint_tpu.utils import lockrank

    # locks must be *instrumentable* for the armed half: make this
    # process timing-capable before the verifier stack constructs them
    # (no-op under the tier-1 suite, which runs with the sanitizer on)
    os.environ.setdefault("TENDERMINT_TPU_PROFILE_HZ", "0")

    height_triples = [
        _salted_sigs(n_vals, b"prof-h%d" % h) for h in range(heights)
    ]
    replays = 3  # long enough for 29 Hz to land real samples

    def run() -> float:
        v = CoalescingVerifier(
            _LaunchLatencyVerifier(launch_ms / 1e3),
            cache_size=65536,
            window_s=0.001,
        )
        try:
            total = 0
            t0 = time.perf_counter()
            for _ in range(replays):
                for triples in height_triples:
                    for consumer in ("consensus", "fastsync"):
                        assert bool(
                            v.verify_batch_async(triples, consumer=consumer)
                            .result(timeout=60)
                            .all()
                        )
                    total += 2 * len(triples)
            return total / (time.perf_counter() - t0)
        finally:
            v.close()

    run()  # warmup: thread spin-up / memo fills excluded
    off_vps = run()
    PROFILER.reset()
    lockrank.reset_contention()
    PROFILER.start(hz=29)
    try:
        on_vps = run()
    finally:
        PROFILER.stop()
    snap = PROFILER.snapshot(top_stacks=5)
    locks = lockrank.contention_snapshot(top=3)["locks"]
    overhead_pct = 100.0 * (1.0 - on_vps / off_vps)
    return {
        "heights": heights,
        "validators": n_vals,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "profile_hz": 29,
        "lock_timing": True,
        "profiler_off_verifies_per_s": round(off_vps, 1),
        "profiler_on_verifies_per_s": round(on_vps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "within_3pct": overhead_pct <= 3.0,
        # proof the armed half measured something real, not a no-op
        "samples": snap["samples"],
        "subsystems_seen": sorted(snap["subsystems"]),
        "top_contended_lock": locks[0]["lock"] if locks else None,
    }


def drive_device_efficiency(heights: int, n_vals: int, launch_ms: float) -> dict:
    """`device_efficiency` section (the device observatory, PR 13) —
    two halves:

    * **ledger overhead guard**: the dedup_steady_state coalescer
      replay with `TENDERMINT_TPU_LAUNCHLOG=0` vs on; recording one
      structured record per launch must stay within 3% of off.
    * **occupancy/waste accounting**: real mesh-geometry launches
      through a host-executor `MeshManager` over the virtual CPU mesh
      (the per-chip power-of-two bucket padding of the REAL sharded
      path, no XLA compile), at batch sizes chosen to land on and off
      bucket boundaries — occupancy %, padding waste %, and compile
      amortization read back from the LaunchLedger records the bench
      just produced. CPU seed: compile counters stay zero (the host
      executor compiles nothing); a real-silicon reseed fills them.
    """
    import jax

    from tendermint_tpu.parallel.mesh import MeshManager
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.services.verifier import ShardedBatchVerifier
    from tendermint_tpu.telemetry import launchlog

    height_triples = [
        _salted_sigs(n_vals, b"dev-h%d" % h) for h in range(heights)
    ]

    def run() -> float:
        v = CoalescingVerifier(
            _LaunchLatencyVerifier(launch_ms / 1e3),
            cache_size=65536,
            window_s=0.001,
        )
        try:
            total = 0
            t0 = time.perf_counter()
            for triples in height_triples:
                for consumer in ("consensus", "fastsync"):
                    assert bool(
                        v.verify_batch_async(triples, consumer=consumer)
                        .result(timeout=60)
                        .all()
                    )
                total += 2 * len(triples)
            return total / (time.perf_counter() - t0)
        finally:
            v.close()

    prev = os.environ.get("TENDERMINT_TPU_LAUNCHLOG")
    run()  # warmup (thread spin-up excluded from both halves)
    try:
        os.environ["TENDERMINT_TPU_LAUNCHLOG"] = "0"
        off_vps = run()
        os.environ["TENDERMINT_TPU_LAUNCHLOG"] = "1"
        t_mark = time.time()
        on_vps = run()
    finally:
        if prev is None:
            os.environ.pop("TENDERMINT_TPU_LAUNCHLOG", None)
        else:
            os.environ["TENDERMINT_TPU_LAUNCHLOG"] = prev
    overhead_pct = 100.0 * (1.0 - on_vps / off_vps)
    ledger_records = [
        r for r in launchlog.LAUNCHLOG.recent() if r.get("t", 0) >= t_mark
    ]

    # occupancy half: the REAL mesh pad geometry (per-chip bucket *
    # width) over the virtual-device mesh, host executor = no compiles
    mgr = MeshManager(
        devices=list(jax.devices())[: min(8, len(jax.devices()))],
        executor="host",
    )
    mesh_v = ShardedBatchVerifier(mesh=mgr, min_device_batch=1)
    t_mark2 = time.time()
    sizes = (n_vals, n_vals + 1, 8 * mgr.n_active)  # on/off bucket edges
    for size in sizes:
        triples = _salted_sigs(size, b"dev-occ-%d" % size)
        assert bool(mesh_v.verify_batch(triples).all())
    mesh_records = [
        r
        for r in launchlog.LAUNCHLOG.recent(kind="verify")
        if r.get("t", 0) >= t_mark2 and r.get("mesh_width")
    ]
    summary = launchlog.summarize(mesh_records).get("verify") or {}

    from tendermint_tpu.telemetry import REGISTRY

    hits = REGISTRY.counter_value("tendermint_mesh_compile_total", result="hit")
    misses = REGISTRY.counter_value(
        "tendermint_mesh_compile_total", result="miss"
    )
    return {
        "heights": heights,
        "validators": n_vals,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "ledger_off_verifies_per_s": round(off_vps, 1),
        "ledger_on_verifies_per_s": round(on_vps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "within_3pct": overhead_pct <= 3.0,
        # proof the on half actually recorded (a silently-disabled
        # ledger would pass the overhead guard trivially)
        "records": len(ledger_records),
        "mesh_width": mgr.n_active,
        "mesh_launch_sizes": list(sizes),
        "mesh_launches": len(mesh_records),
        "occupancy_pct": summary.get("occupancy_pct"),
        "padding_waste_pct": summary.get("padding_waste_pct"),
        "compile_amortization": {
            "hits": int(hits),
            "misses": int(misses),
            "hit_rate": round(hits / (hits + misses), 3)
            if hits + misses
            else None,
        },
    }


def _build_fullcommit_chain(heights: int, n_vals: int, rotate_every: int):
    """FullCommits for heights 1..N with one validator replaced every
    `rotate_every` heights (sliding window over deterministic keys), so
    a long jump's old-set overlap decays linearly — the read-path walk
    benches need BOTH regimes: jumps the 2/3 dynamic rule rejects and
    the 1/3 skip rule still accepts."""
    from tendermint_tpu.certifiers.certifier import FullCommit
    from tendermint_tpu.certifiers.provider import MemProvider
    from tendermint_tpu.crypto import PrivKey
    from tendermint_tpu.types import (
        VOTE_TYPE_PRECOMMIT,
        BlockID,
        PartSetHeader,
        PrivValidator,
        Validator,
        ValidatorSet,
        Vote,
        VoteSet,
    )
    from tendermint_tpu.types.block import Header

    chain_id = "reads-bench"
    privs_by_id: dict[int, object] = {}

    def priv(i: int):
        if i not in privs_by_id:
            privs_by_id[i] = PrivValidator(PrivKey(i.to_bytes(32, "little")))
        return privs_by_id[i]

    source = MemProvider()
    fcs = {}
    for h in range(1, heights + 1):
        rot = (h - 1) // max(1, rotate_every)
        privs = [priv(1 + rot + k) for k in range(n_vals)]
        vs = ValidatorSet(
            [
                Validator(address=p.address, pub_key=p.pub_key, voting_power=10)
                for p in privs
            ]
        )
        header = Header(
            chain_id=chain_id,
            height=h,
            time=h * 1_000_000_000,
            num_txs=0,
            last_block_id=BlockID.zero(),
            validators_hash=vs.hash(),
            app_hash=b"app",
        )
        block_id = BlockID(
            header.hash(), PartSetHeader(total=1, hash=header.hash()[:20])
        )
        by_addr = {p.address: p for p in privs}
        vote_set = VoteSet(chain_id, h, 0, VOTE_TYPE_PRECOMMIT, vs)
        for idx, val in enumerate(vs.validators):
            p = by_addr[val.address]
            vote = Vote(
                validator_address=p.address,
                validator_index=idx,
                height=h,
                round=0,
                timestamp=h,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            vote_set.add_vote(p.sign_vote(chain_id, vote))
        fc = FullCommit(
            header=header, commit=vote_set.make_commit(), validators=vs
        )
        source.store_commit(fc)
        fcs[h] = fc
    return chain_id, source, fcs


class _CountingVerifier:
    """Counts launches (submissions) + verifies (triples) flowing
    through an inner consumer-tagged verifier — walk-cost attribution
    for the reads bench."""

    accepts_consumer = True

    def __init__(self, inner):
        self.inner = inner
        self.verifies = 0
        self.launches = 0

    def reset(self):
        self.verifies = 0
        self.launches = 0

    def verify_batch(self, triples):
        self.verifies += len(triples)
        self.launches += 1
        return self.inner.verify_batch(triples)

    def verify_batch_async(self, triples, queue=None, consumer: str = "default"):
        self.verifies += len(triples)
        self.launches += 1
        return self.inner.verify_batch_async(triples, consumer=consumer)


def drive_reads(
    heights: int, n_vals: int, rotate_every: int, launch_ms: float
) -> dict:
    """The read path A/B (ROADMAP item 1): a fresh light client
    verifying to the chain tip through the sequential
    `InquiringCertifier` walk vs the batched-bisection
    `BisectingCertifier`, both over the coalescing stack with the
    emulated per-launch cost — plus the serving half (certified
    FullCommit lookups + encodes per second). Dedup cache OFF so every
    walk pays its honest verification cost (a new client shares no
    proven triples)."""
    from tendermint_tpu.certifiers.certifier import InquiringCertifier
    from tendermint_tpu.certifiers.provider import MemProvider
    from tendermint_tpu.db.fullcommit import FullCommitStore
    from tendermint_tpu.db.kv import MemDB
    from tendermint_tpu.lightclient import BisectingCertifier, CertifiedCommitCache
    from tendermint_tpu.services.batcher import CoalescingVerifier

    chain_id, source, fcs = _build_fullcommit_chain(heights, n_vals, rotate_every)
    target = fcs[heights]

    def run(mode: str, walks: int) -> dict:
        # _DeviceShapeVerifier: emulated fixed launch + tiny per-sig
        # marginal with host spot checks — the device cost shape, so the
        # A/B measures launches saved, not host-crypto throughput
        verifier = _CountingVerifier(
            CoalescingVerifier(
                _DeviceShapeVerifier(launch_ms / 1e3),
                cache_size=0,
                window_s=0.001,
            )
        )
        try:
            t0 = time.perf_counter()
            for _ in range(walks):
                if mode == "bisect":
                    cert = BisectingCertifier(
                        chain_id,
                        seed=fcs[1],
                        trusted=MemProvider(),
                        source=source,
                        verifier=verifier,
                    )
                    cert.verify_to_height(heights)
                    assert cert.last_height == heights
                else:
                    cert = InquiringCertifier(
                        chain_id,
                        fcs[1],
                        MemProvider(),
                        source,
                        verifier=verifier,
                    )
                    cert.certify(target)
            elapsed = time.perf_counter() - t0
        finally:
            verifier.inner.close()
        return {
            "walks": walks,
            "walks_per_s": round(walks / elapsed, 3),
            "verifies_per_walk": round(verifier.verifies / walks, 1),
            "launches_per_walk": round(verifier.launches / walks, 1),
        }

    sequential = run("sequential", walks=2)
    bisect = run("bisect", walks=4)

    # serving half: certified-cache lookups + wire encodes (hot-height
    # skew — what a replica's proof-serving hot loop does per query)
    cache = CertifiedCommitCache(store=FullCommitStore(MemDB()))
    for fc in fcs.values():
        cache.put_certified(fc)
    import random as _random

    rng = _random.Random(5)
    n_queries = 2000
    t0 = time.perf_counter()
    for _ in range(n_queries):
        h = (
            heights - rng.randrange(8)
            if rng.random() < 0.7
            else rng.randrange(1, heights + 1)
        )
        fc = cache.get_exact(max(1, h))
        assert fc is not None
        fc.encode()
    proofs_per_s = n_queries / (time.perf_counter() - t0)

    return {
        "heights": heights,
        "validators": n_vals,
        "rotate_every": rotate_every,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "sequential": sequential,
        "bisect": bisect,
        "bisect_speedup": round(
            bisect["walks_per_s"] / sequential["walks_per_s"], 3
        ),
        "verify_reduction": round(
            sequential["verifies_per_walk"] / max(1.0, bisect["verifies_per_walk"]),
            3,
        ),
        "proofs_served_per_s": round(proofs_per_s, 1),
    }


def drive_coalesce_multiconsumer(rounds: int, batch: int, launch_ms: float) -> dict:
    """All four verify consumers live at once: consensus, fast-sync,
    statesync, and rpc threads submit concurrent async batches through
    one coalescer; the coalesce factor (requests merged per launch) is
    read back from the telemetry the coalescer exports."""
    import threading

    from tendermint_tpu.services.batcher import CoalescingVerifier

    consumers = ("consensus", "fastsync", "statesync", "rpc")
    pre = {
        tag: [
            _salted_sigs(batch, b"%s-r%d" % (tag.encode(), r))
            for r in range(rounds)
        ]
        for tag in consumers
    }
    v = CoalescingVerifier(
        _LaunchLatencyVerifier(launch_ms / 1e3), cache_size=0, window_s=0.005
    )
    n0, s0, _, _ = _histo("tendermint_batcher_coalesce_factor")
    gate = threading.Barrier(len(consumers))
    errors: list = []

    def worker(tag: str) -> None:
        try:
            for triples in pre[tag]:
                gate.wait()  # align the four consumers' submit instants
                if not v.verify_batch_async(triples, consumer=tag).result(
                    timeout=60
                ).all():
                    errors.append(f"{tag}: bad verdict")
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(f"{tag}: {e}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(tag,)) for tag in consumers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    v.close()
    assert not errors, errors
    n1, s1, _, _ = _histo("tendermint_batcher_coalesce_factor")
    launches = n1 - n0
    factor = (s1 - s0) / launches if launches else 0.0
    total = len(consumers) * rounds * batch
    return {
        "consumers": list(consumers),
        "rounds": rounds,
        "batch_per_request": batch,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "verifies_per_s": round(total / dt, 1),
        "coalesced_launches": int(launches),
        "requests": len(consumers) * rounds,
        "coalesce_factor_mean": round(factor, 3),
    }


class _DeviceShapeVerifier:
    """CPU stand-in for the device verifier's INGRESS shape: a fixed
    launch cost plus the measured device marginal per-signature cost,
    both spent OFF the GIL (exactly what an in-flight kernel looks like
    to the host), with a real host-crypto spot check of a sample so the
    emulation can't return verdicts for garbage. The ingress comparison
    is architectural — launch-per-tx vs launch-per-window — and the
    launch is the term the device actually charges (both figures are
    assumptions, not measured on v5e). Flagged `emulated_launch` like
    every CPU-seed section."""

    accepts_consumer = True

    def __init__(self, launch_s: float, per_sig_s: float = 2e-6, sample: int = 2):
        from tendermint_tpu.services.verifier import HostBatchVerifier

        self._host = HostBatchVerifier()
        self._launch_s = launch_s
        self._per_sig_s = per_sig_s
        self._sample = sample

    def verify_batch(self, triples):
        import numpy as np

        time.sleep(self._launch_s + self._per_sig_s * len(triples))
        n = len(triples)
        idx = list(range(0, n, max(1, n // self._sample)))[: self._sample]
        spot = self._host.verify_batch([triples[i] for i in idx])
        return np.full(n, bool(spot.all()), dtype=bool)

    launch_verify_batch = verify_batch

    def finalize_verify_batch(self, launched):
        return launched

    def verify_batch_async(self, triples, queue=None, consumer: str = "default"):
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(lambda: self.verify_batch(triples), kind="verify")


def drive_mempool_ingress(
    n_txs: int, threads: int, launch_ms: float, lanes_list=(1, 4, 8)
) -> dict:
    """`mempool_ingress` section: signed CheckTx traffic through the
    REAL admission paths — legacy one-at-a-time (launch per tx, the
    pre-ingress shape) vs the batched+sharded pipeline (launch per
    verify window through the coalescer) — at 1/4/8 lanes, with p99
    admission latency read from the same histogram a node exports."""
    import threading

    from tendermint_tpu.abci.apps import NilApp
    from tendermint_tpu.abci.client import local_client_creator
    from tendermint_tpu.crypto.keys import gen_priv_key
    from tendermint_tpu.mempool import Mempool
    from tendermint_tpu.mempool.ingress import make_signed_tx
    from tendermint_tpu.services.batcher import CoalescingVerifier

    privs = [gen_priv_key(bytes([i % 256]) * 32) for i in range(16)]
    sys.stderr.write(f"  pre-signing {n_txs} txs...\n")
    tx_sets: dict = {}

    def txs_for(run_key: str) -> list[bytes]:
        # distinct payloads per run so dup caches never cross runs
        if run_key not in tx_sets:
            tx_sets[run_key] = [
                make_signed_tx(
                    privs[i % len(privs)], b"%s/k%d=%d" % (run_key.encode(), i, i)
                )
                for i in range(n_txs)
            ]
        return tx_sets[run_key]

    def run(run_key: str, batch_on: bool, lanes: int) -> dict:
        conns = local_client_creator(NilApp())()
        verifier = CoalescingVerifier(
            _DeviceShapeVerifier(launch_ms / 1e3),
            cache_size=0,
            window_s=0.001,
        )
        mp = Mempool(
            conns.mempool,
            cache_size=4 * n_txs,
            verifier=verifier,
            lanes=lanes,
            ingress_batch=batch_on,
        )
        txs = txs_for(run_key)
        n0, _, _, _ = _histo("tendermint_mempool_admission_seconds")
        errors: list = []
        lat: list[float] = []
        lat_lock = threading.Lock()
        done = threading.Event()

        def worker(k: int) -> None:
            # the RPC-broadcast / gossip-recv shape: non-blocking
            # submits, results via callback — intake threads never
            # stall on a window join, so windows grow with load
            try:
                for tx in txs[k::threads]:
                    t_sub = time.perf_counter()

                    def cb(res, t_sub=t_sub):
                        if not res.is_ok:
                            errors.append(res.log)
                        with lat_lock:
                            lat.append(time.perf_counter() - t_sub)
                            if len(lat) == n_txs:
                                done.set()

                    mp.check_tx_async(tx, cb)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(repr(e))
                done.set()

        ts = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert done.wait(timeout=120), "ingress admissions did not drain"
        dt = time.perf_counter() - t0
        assert not errors, errors[:3]
        assert mp.size() == n_txs
        mp.close()
        verifier.close()
        n1, _, _, _ = _histo("tendermint_mempool_admission_seconds")
        lat.sort()
        p50 = lat[len(lat) // 2]
        p99 = lat[min(len(lat) - 1, int(0.99 * (len(lat) - 1)))]
        return {
            "lanes": lanes,
            "batched": batch_on,
            "checktx_per_s": round(n_txs / dt, 1),
            "p50_admission_ms": round(p50 * 1e3, 3),
            "p99_admission_ms": round(p99 * 1e3, 3),
            # proof the exported histogram saw this run's admissions
            "admissions_observed": int(n1 - n0),
        }

    sys.stderr.write("  legacy one-at-a-time path...\n")
    legacy = run("legacy", batch_on=False, lanes=1)
    rows = []
    for lanes in lanes_list:
        sys.stderr.write(f"  batched ingress, {lanes} lanes...\n")
        rows.append(run(f"b{lanes}", batch_on=True, lanes=lanes))
    best = max(rows, key=lambda r: r["checktx_per_s"])
    return {
        "txs": n_txs,
        "threads": threads,
        "launch_overhead_ms": launch_ms,
        "emulated_launch": True,
        "signed": True,
        "target_device_checktx_per_s": 100_000,
        "legacy": legacy,
        "batched": rows,
        "speedup": round(best["checktx_per_s"] / legacy["checktx_per_s"], 3),
    }


def drive_mesh_scaling(batch: int, reps: int, device_counts=(1, 2, 4, 8)) -> dict | None:
    """`sharded_verify` section: the REAL mesh kernels at mesh widths
    1/2/4/8 — verifies/s, per-launch commit-tally latency, and scaling
    efficiency vs linear from the devices=1 figure. On the CPU CI shape
    the "devices" are XLA virtual host devices (threads over the same
    cores — expect sub-linear; the section exists so a TPU pod reseeds
    it with ICI numbers), flagged `virtual_devices`."""
    import jax
    import numpy as np

    from tendermint_tpu.parallel.mesh import MeshManager
    from tendermint_tpu.services.verifier import ShardedBatchVerifier

    have = len(jax.devices())
    counts = [c for c in device_counts if c <= have]
    if len(counts) < 2:
        return None
    pubs, msgs, sigs = _make_sigs(batch)
    triples = list(zip(pubs, msgs, sigs))
    powers = np.full(batch, 3, dtype=np.int32)
    rows = []
    base_vps = None
    for c in counts:
        sys.stderr.write(f"  mesh width {c}: compiling + timing...\n")
        mgr = MeshManager(devices=list(jax.devices())[:c])
        v = ShardedBatchVerifier(mesh=mgr, min_device_batch=1)
        mask, tally = v.verify_batch_with_powers(triples, powers)  # warm
        assert bool(mask.all()) and tally == 3 * batch, (int(mask.sum()), tally)
        t0 = time.perf_counter()
        for _ in range(reps):
            mask, tally = v.verify_batch_with_powers(triples, powers)
        dt = time.perf_counter() - t0
        vps = batch * reps / dt
        if base_vps is None:
            base_vps = vps
        rows.append(
            {
                "devices": c,
                "verifies_per_s": round(vps, 1),
                "commit_ms": round(dt / reps * 1e3, 3),
                "scaling_efficiency": round(vps / (base_vps * c), 3),
            }
        )
    return {
        "batch": batch,
        "reps": reps,
        "backend": jax.default_backend(),
        "virtual_devices": jax.default_backend() == "cpu",
        "widths": rows,
    }


def _finality_pctls(gaps: list[float]) -> tuple[float | None, float | None]:
    gaps = sorted(gaps)
    if not gaps:
        return None, None
    p50 = gaps[len(gaps) // 2]
    p99 = gaps[min(len(gaps) - 1, int(0.99 * (len(gaps) - 1)))]
    return p50, p99


def drive_finality(
    heights_idle: int, heights_loaded: int, n_vals: int = 4, feeders: int = 2
) -> dict:
    """`finality` section: commit-to-commit p50/p99 on a LIVE
    in-process validator net (full `node.Node` instances: p2p + mempool
    + RPC), idle and under open-loop CheckTx traffic, read back from
    the nodes' HeightLedgers — the exact records `/health`'s SLO window
    and `tools/finality_report.py` consume. The regression floor for
    ROADMAP item 3 (cross-height pipelined consensus): the pipelining
    PR must move these numbers down, and `tools/bench_gate.py` refuses
    a PR that silently moves them up."""
    import tempfile
    import threading

    from tendermint_tpu.consensus.config import ConsensusConfig
    from tendermint_tpu.testing.nemesis import Nemesis

    def fast(cfg):
        # full consensus speed (skip_timeout_commit): measure the
        # machinery's latency, not the production commit pacing.
        # Blocks are capped so the loaded half measures finality under
        # steady traffic instead of degenerating into a max-throughput
        # contest the in-process GIL always loses.
        cfg.consensus = ConsensusConfig.test_config()
        cfg.consensus.max_block_size_txs = 256

    warm = 2
    path_counts: dict[str, int] = {}

    def summarize(recs: list[dict]) -> dict:
        gaps = [
            r["finality_s"]
            for r in recs
            if isinstance(r.get("finality_s"), (int, float))
        ]
        for r in recs:
            label = r.get("critical_path")
            if label:
                path_counts[label] = path_counts.get(label, 0) + 1
        p50, p99 = _finality_pctls(gaps)
        return {
            "heights": len(recs),
            "p50_ms": round(p50 * 1e3, 3) if p50 is not None else None,
            "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
        }

    def measure_idle_leg(mutator, warm_leg, heights, label):
        """One live-net idle run -> p50/p99 + pipeline overlap stats."""
        with tempfile.TemporaryDirectory(prefix=f"hotpath-fin-{label}-") as h:
            with Nemesis(
                n_vals,
                home=h,
                node_factory=Nemesis.full_node_factory(config_mutator=mutator),
            ) as net:
                lead = net.nodes[0]
                net.wait_height(warm_leg + heights, timeout=300)
                recs = [
                    r
                    for r in lead.node.height_ledger.recent()
                    if warm_leg < r["height"] <= warm_leg + heights
                ]
                gaps = [
                    r["finality_s"]
                    for r in recs
                    if isinstance(r.get("finality_s"), (int, float))
                ]
                p50, p99 = _finality_pctls(gaps)
                pipelined = [r for r in recs if r.get("pipelined")]
                out = {
                    "heights": len(recs),
                    "p50_ms": round(p50 * 1e3, 3) if p50 is not None else None,
                    "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
                    "pipelined_heights": len(pipelined),
                }
                if pipelined:
                    out["apply_overlap_ms_mean"] = round(
                        sum(r.get("apply_overlap_s") or 0.0 for r in pipelined)
                        / len(pipelined)
                        * 1e3,
                        3,
                    )
                return out

    def pipeline_ab(heights: int = 6) -> dict:
        """Serial-vs-pipelined on the live net at the PRODUCTION commit
        pacing (timeout_commit=1s, the deployment default): the serial
        leg is the pre-pipeline configuration (strictly serial finalize
        + the fixed timeout ladder), the pipelined leg is this PR
        (overlapped apply + measured-latency timeouts). This is where
        ROADMAP item 3's floors move DOWN — a healthy net stops
        sleeping out the static commit pacing, and the apply rides
        under the next height's voting."""
        from tendermint_tpu.consensus.ticker import AdaptiveTimeouts

        def prod(pipe):
            def mut(cfg):
                c = ConsensusConfig.test_config()
                c.timeout_commit = 1000  # production pacing
                c.skip_timeout_commit = False  # production default
                c.pipeline_commit = pipe
                c.adaptive_timeouts = pipe
                c.max_block_size_txs = 256
                cfg.consensus = c

            return mut

        serial = measure_idle_leg(prod(False), 2, heights, "serial")
        # warm past the derivation gate so measured timeouts engage
        warm_pipe = AdaptiveTimeouts.MIN_HEIGHTS + 1
        pipelined = measure_idle_leg(prod(True), warm_pipe, heights + 2, "pipe")
        speedup = None
        if serial["p50_ms"] and pipelined["p50_ms"]:
            speedup = round(serial["p50_ms"] / pipelined["p50_ms"], 3)
        return {
            "commit_pacing_ms": 1000,
            "serial": serial,
            "pipelined": pipelined,
            "speedup_idle_p50": speedup,
        }

    with tempfile.TemporaryDirectory(prefix="hotpath-finality-") as home:
        with Nemesis(
            n_vals,
            home=home,
            node_factory=Nemesis.full_node_factory(config_mutator=fast),
        ) as net:
            lead = net.nodes[0]
            net.wait_height(warm + heights_idle, timeout=180)
            idle = summarize(
                [
                    r
                    for r in lead.node.height_ledger.recent()
                    if warm < r["height"] <= warm + heights_idle
                ]
            )
            h0 = lead.store.height
            stop = threading.Event()

            def feeder(k: int) -> None:
                # open-loop but depth-bounded: keep a steady backlog in
                # front of the proposer without letting the pool (and
                # the gossip fan-out) grow unboundedly — the bench
                # measures finality under traffic, not pool growth
                i = 0
                while not stop.is_set():
                    if lead.node.mempool.size() < 1024:
                        try:
                            lead.node.mempool.check_tx_async(
                                b"fin%d/k%d=%d" % (k, i, i)
                            )
                        except Exception:
                            return
                        i += 1
                    time.sleep(0.002)

            threads = [
                threading.Thread(target=feeder, args=(k,), daemon=True)
                for k in range(feeders)
            ]
            for t in threads:
                t.start()
            try:
                net.wait_height(h0 + heights_loaded, timeout=240)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=5)
            loaded_recs = [
                r
                for r in lead.node.height_ledger.recent()
                if h0 < r["height"] <= h0 + heights_loaded
            ]
            loaded = summarize(loaded_recs)
            txs = sum(r.get("txs", 0) for r in loaded_recs)
            span = sum(
                r["finality_s"]
                for r in loaded_recs
                if isinstance(r.get("finality_s"), (int, float))
            )
            loaded["txs_committed"] = txs
            loaded["committed_tx_per_s"] = round(txs / span, 1) if span else None
    sys.stderr.write(
        "driving serial-vs-pipelined A/B at production commit pacing...\n"
    )
    return {
        "validators": n_vals,
        "consensus_config": "test (skip_timeout_commit)",
        "feeders": feeders,
        "idle": idle,
        "loaded": loaded,
        "critical_path_counts": dict(
            sorted(path_counts.items(), key=lambda kv: -kv[1])
        ),
        "pipeline": pipeline_ab(),
    }


def drive_scenario_finality(names) -> dict:
    """`scenario_finality` section: the declarative scenario library
    (PR 16) run end-to-end — WAN topology shaping + fault timelines +
    validator churn on live Nemesis nets — with each scenario's
    committed finality floor graded by the runner itself. Includes the
    adaptive-timeout A/B on the slow-WAN topology: the adaptive leg
    must converge its propose timeout above the injected one-way delay
    and stop skipping rounds once warm, while the fixed-short leg
    (same fabric, adaptive off, 10 ms propose) keeps paying round
    skips every time the far validator proposes — the measured, not
    asserted, case for measured-latency timeouts on real WAN RTTs."""
    import copy
    import tempfile

    from tendermint_tpu.testing.scenario import SCENARIO_LIBRARY, ScenarioRunner

    out: dict = {"scenarios": {}, "all_pass": True}
    for name in names:
        spec = copy.deepcopy(SCENARIO_LIBRARY[name])
        sys.stderr.write(f"  scenario {name}...\n")
        report = ScenarioRunner(
            home=tempfile.mkdtemp(prefix=f"hotpath-scn-{name}-")
        ).run(spec)
        entry = {
            "ok": report["ok"],
            "elapsed_s": report["elapsed_s"],
            "min_height": min(report["heights"], default=0),
            "finality": report["finality"],
            "round_skips_post_warm": report["round_skips_post_warm"],
        }
        for key in ("epochs", "valset_rebuilds"):
            if key in report:
                entry[key] = report[key]
        if report["failures"]:
            entry["failures"] = report["failures"]
        out["scenarios"][name] = entry
        out["all_pass"] = bool(out["all_pass"] and report["ok"])

    legs: dict = {}
    for label in ("adaptive", "fixed_short"):
        spec = copy.deepcopy(SCENARIO_LIBRARY["slow_wan_validator"])
        spec["name"] = f"slow_wan_{label}"
        if label == "fixed_short":
            spec["config"]["adaptive_timeouts"] = False
            spec["config"]["timeout_propose_ms"] = 10  # < one-way delay
            spec["expect"].pop("adaptive_above_max_delay", None)
            spec["expect"].pop("max_round_skips_post_warm", None)
        sys.stderr.write(f"  A/B leg {label}...\n")
        report = ScenarioRunner(
            home=tempfile.mkdtemp(prefix=f"hotpath-ab-{label}-")
        ).run(spec)
        leg = {
            "ok": report["ok"],
            "round_skips_post_warm": report["round_skips_post_warm"],
            "finality_p50_s": report["finality"].get("p50_s"),
        }
        if "propose_timeout_s" in report:
            leg["propose_timeout_s"] = report["propose_timeout_s"]
            leg["max_one_way_delay_s"] = report["max_one_way_delay_s"]
        legs[label] = leg
        out["all_pass"] = bool(out["all_pass"] and report["ok"])
    # The headline A/B number: how much slower finality gets when the
    # propose timeout is pinned below the one-way WAN delay instead of
    # adapting to it. Round-skip counters only see skip-ahead jumps, so
    # the latency ratio is the robust degradation signal.
    adaptive_p50 = legs["adaptive"].get("finality_p50_s")
    fixed_p50 = legs["fixed_short"].get("finality_p50_s")
    if adaptive_p50 and fixed_p50:
        legs["finality_p50_ratio"] = round(fixed_p50 / adaptive_p50, 3)
    out["adaptive_ab"] = legs
    return out


def drive_gossip_efficiency(n_msgs: int) -> dict:
    """`gossip_efficiency` section (the gossip observatory, PR 17) —
    two halves:

    * **accounting overhead guard**: vote-tagged frames pumped through
      a connected switch pair with `TENDERMINT_TPU_GOSSIPLOG=0` vs on;
      classifying + rolling up every frame (channel name, kind tag,
      per-peer table row, two counter incs) must stay within 3% of
      off. Best-of-3 per half — pipe throughput is scheduler-noisy.
    * **redundancy factor on the 4-node loadgen net**: a short live
      Nemesis run on the flash-crowd WAN fabric under steady load; the
      per-kind delivered/useful factors from the fleet rollup are the
      measured over-gossip numbers (vote > 1.0 = the HasVote race is
      real, the before-number for the ROADMAP item 3 aggregation lane).
    """
    import copy
    import threading as _threading

    from tendermint_tpu.p2p.connection import ChannelDescriptor
    from tendermint_tpu.p2p.peer import NodeInfo
    from tendermint_tpu.p2p.switch import Reactor, Switch, connect_switches
    from tendermint_tpu.testing.scenario import ScenarioRunner

    vote_chan = 0x22
    payload = b"\x06" + b"v" * 160  # vote-tagged, vote-sized

    class _Sink(Reactor):
        def __init__(self) -> None:
            super().__init__()
            self.count = 0
            self.target = 0
            self.done = _threading.Event()

        def get_channels(self):
            return [
                ChannelDescriptor(
                    vote_chan, priority=5, send_queue_capacity=1024
                )
            ]

        def receive(self, chan_id, peer, data) -> None:
            self.count += 1
            if self.count >= self.target:
                self.done.set()

    def run_half() -> tuple[float, int]:
        a = Switch(NodeInfo("a" * 40, "bench-a", "bench-gossip"))
        b = Switch(NodeInfo("b" * 40, "bench-b", "bench-gossip"))
        a.ping_interval = b.ping_interval = 0
        a.add_reactor("sink", _Sink())
        sink = b.add_reactor("sink", _Sink())
        sink.target = n_msgs
        a.start()
        b.start()
        pa, _pb = connect_switches(a, b)
        try:
            t0 = time.perf_counter()
            for _ in range(n_msgs):
                assert pa.send(vote_chan, payload, ctx=None)
            assert sink.done.wait(timeout=60)
            mps = n_msgs / (time.perf_counter() - t0)
            snap = b.gossip.snapshot()
            counted = (
                snap["kinds"].get("vote", {}).get("recv_msgs", 0)
                if snap["enabled"]
                else 0
            )
            return mps, counted
        finally:
            a.stop()
            b.stop()

    prev = os.environ.get("TENDERMINT_TPU_GOSSIPLOG")
    try:
        os.environ["TENDERMINT_TPU_GOSSIPLOG"] = "0"
        run_half()  # warmup: thread spin-up excluded from both halves
        off_mps = max(run_half()[0] for _ in range(3))
        os.environ["TENDERMINT_TPU_GOSSIPLOG"] = "1"
        on_runs = [run_half() for _ in range(3)]
        on_mps = max(r[0] for r in on_runs)
        msgs_counted = max(r[1] for r in on_runs)
    finally:
        if prev is None:
            os.environ.pop("TENDERMINT_TPU_GOSSIPLOG", None)
        else:
            os.environ["TENDERMINT_TPU_GOSSIPLOG"] = prev
    overhead_pct = 100.0 * (1.0 - on_mps / off_mps)

    # redundancy half: 4 full nodes on the flash-crowd WAN fabric under
    # steady load — a real consensus run, so vote/part/tx dedup sites
    # see genuine gossip races
    spec = {
        "name": "gossip_probe",
        "description": "bench probe: 4-node WAN loadgen redundancy",
        "nodes": 4,
        "kind": "full",
        "topology": {
            "placement": ["us-east", "us-west", "eu-west", "us-east"],
            "scale": 0.1,
        },
        "config": {
            "timeout_propose_ms": 1000,
            "timeout_prevote_ms": 300,
            "timeout_precommit_ms": 300,
        },
        "load": {"rate": 25.0, "payload": 64},
        "run": {"target_height": 8, "timeout_s": 120.0},
        "expect": {
            "min_height": 8,
            "gossip": {"require_counted": True},
        },
    }
    sys.stderr.write("  gossip redundancy probe (4-node WAN loadgen)...\n")
    report = ScenarioRunner(
        home=tempfile.mkdtemp(prefix="hotpath-gossip-")
    ).run(copy.deepcopy(spec))
    g = report.get("gossip") or {}
    factors = dict(g.get("redundancy_factor") or {})
    # vote traffic with zero recorded duplicates is a 1.0x factor, not
    # a missing measurement (the floor guards presence + sanity)
    if "vote" not in factors and (g.get("channel_bytes") or {}).get("cns_vote"):
        factors["vote"] = 1.0
    return {
        "messages": n_msgs,
        "accounting_off_msgs_per_s": round(off_mps, 1),
        "accounting_on_msgs_per_s": round(on_mps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "within_3pct": overhead_pct <= 3.0,
        # proof the on half classified + rolled up real frames, not a
        # silently-disabled no-op
        "msgs_counted": msgs_counted,
        "probe_ok": report["ok"],
        "probe_total_bytes": g.get("total_bytes"),
        "probe_channel_bytes": g.get("channel_bytes"),
        "redundancy_factor": factors,
        "redundancy_factor_vote": factors.get("vote"),
        "top_redundant_kind": g.get("top_redundant_kind"),
    }


def drive_wal(n_records: int) -> None:
    from tendermint_tpu.consensus.wal import WAL, EndHeightMessage

    with tempfile.TemporaryDirectory(prefix="hotpath-wal-") as d:
        wal = WAL(os.path.join(d, "cs.wal"))
        for i in range(n_records):
            wal.save(EndHeightMessage(i))
        wal.close()


def _histo(name: str, **labels):
    """(count, sum, p50, p99) of an exported histogram series."""
    from tendermint_tpu.telemetry import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return 0, 0.0, None, None
    child = fam.labels(**labels) if fam.labelnames else fam._child0()
    snap = child.value
    if snap["count"] == 0:
        return 0, 0.0, None, None
    return (
        snap["count"],
        snap["sum"],
        child.quantile(0.5),
        child.quantile(0.99),
    )


def _histo_snap(name: str, **labels):
    """Raw bucket snapshot of a histogram series (None if the family is
    unregistered) — the baseline half of `_histo_delta`."""
    from tendermint_tpu.telemetry import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return None
    child = fam.labels(**labels) if fam.labelnames else fam._child0()
    return child.value


def _histo_delta(base, snap):
    """(count, sum, p50, p99) of the observations BETWEEN two snapshots
    — how the bench excludes warmup/compile calls from its percentiles:
    the first (cold) call otherwise lands in the pool and a p99 of two
    seconds gets reported for a sub-millisecond path. Quantiles use the
    registry's interpolation over the diffed cumulative buckets."""
    import math

    if snap is None:
        return 0, 0.0, None, None
    if base is None:
        buckets = snap["buckets"]
        count = snap["count"]
        total = snap["sum"]
    else:
        buckets = [
            (ub, c1 - c0)
            for (ub, c1), (_ub, c0) in zip(snap["buckets"], base["buckets"])
        ]
        count = snap["count"] - base["count"]
        total = snap["sum"] - base["sum"]
    if count <= 0:
        return 0, 0.0, None, None

    def q(qv: float) -> float:
        rank = qv * count
        prev_ub, prev_cum = 0.0, 0
        for ub, cum in buckets:
            if cum >= rank:
                if ub == math.inf:
                    return prev_ub
                width = ub - prev_ub
                in_bucket = cum - prev_cum
                if in_bucket == 0:
                    return ub
                return prev_ub + width * (rank - prev_cum) / in_bucket
            prev_ub, prev_cum = ub, cum
        return prev_ub

    return count, total, q(0.5), q(0.99)


_VERIFY_BACKENDS = ("host", "device", "tables", "mesh")
_HASH_BACKENDS = ("host", "device", "mesh")


def snapshot_baselines() -> dict:
    """Per-backend verify/hash histogram snapshots taken AFTER the
    warmup pass — the summaries report only what happened since."""
    base: dict = {}
    for b in _VERIFY_BACKENDS:
        base[("verify_seconds", b)] = _histo_snap(
            "tendermint_verify_seconds", backend=b
        )
        base[("verify_batch_size", b)] = _histo_snap(
            "tendermint_verify_batch_size", backend=b
        )
    for b in _HASH_BACKENDS:
        base[("hash_seconds", b)] = _histo_snap(
            "tendermint_hash_seconds", backend=b
        )
        base[("hash_batch_leaves", b)] = _histo_snap(
            "tendermint_hash_batch_leaves", backend=b
        )
    return base


def backend_summary(backend: str, base: dict | None = None) -> dict | None:
    b = base or {}
    n_calls, t_total, p50, p99 = _histo_delta(
        b.get(("verify_seconds", backend)),
        _histo_snap("tendermint_verify_seconds", backend=backend),
    )
    _n, sig_total, _, _ = _histo_delta(
        b.get(("verify_batch_size", backend)),
        _histo_snap("tendermint_verify_batch_size", backend=backend),
    )
    if n_calls == 0 or t_total <= 0:
        return None
    return {
        "calls": n_calls,
        "signatures": sig_total,
        "verifies_per_s": round(sig_total / t_total, 1),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
    }


def hash_summary(backend: str, base: dict | None = None) -> dict | None:
    b = base or {}
    n_calls, t_total, p50, p99 = _histo_delta(
        b.get(("hash_seconds", backend)),
        _histo_snap("tendermint_hash_seconds", backend=backend),
    )
    _n, leaves, _, _ = _histo_delta(
        b.get(("hash_batch_leaves", backend)),
        _histo_snap("tendermint_hash_batch_leaves", backend=backend),
    )
    if n_calls == 0 or t_total <= 0:
        return None
    return {
        "calls": n_calls,
        "leaves": leaves,
        "leaves_per_s": round(leaves / t_total, 1),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_hotpath.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument(
        "--sizes", default="64,256,1024", help="comma-separated batch sizes"
    )
    ap.add_argument(
        "--wal-records", type=int, default=256, dest="wal_records"
    )
    ap.add_argument(
        "--statesync-kb",
        type=int,
        default=256,
        dest="statesync_kb",
        help="snapshot payload size driven through take+verify (0 skips)",
    )
    ap.add_argument(
        "--fastsync-blocks",
        type=int,
        default=96,
        dest="fastsync_blocks",
        help="chain length replayed through the fast-sync pipeline (0 skips)",
    )
    ap.add_argument(
        "--fastsync-vals",
        type=int,
        default=8,
        dest="fastsync_vals",
        help="validators signing each bench commit",
    )
    ap.add_argument(
        "--dedup-heights",
        type=int,
        default=4,
        dest="dedup_heights",
        help="heights replayed through the gossip-then-commit dedup bench (0 skips)",
    )
    ap.add_argument(
        "--dedup-vals",
        type=int,
        default=64,
        dest="dedup_vals",
        help="validators signing each dedup-bench height",
    )
    ap.add_argument(
        "--coalesce-rounds",
        type=int,
        default=6,
        dest="coalesce_rounds",
        help="rounds each of the four consumers drives through the coalescer (0 skips)",
    )
    ap.add_argument(
        "--coalesce-batch",
        type=int,
        default=32,
        dest="coalesce_batch",
        help="signatures per consumer request in the coalesce bench",
    )
    ap.add_argument(
        "--launch-ms",
        type=float,
        default=86.0,
        dest="launch_ms",
        help="emulated device launch cost on CPU (an assumption, not "
        "measured on v5e); ignored on a real device backend",
    )
    ap.add_argument(
        "--no-device",
        action="store_true",
        help="skip device backends even on TPU",
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="run the sharded_verify mesh-scaling section (devices="
        "1/2/4/8; pays one kernel compile per mesh width — minutes on "
        "XLA:CPU, cached-fast on TPU)",
    )
    ap.add_argument(
        "--mesh-batch",
        type=int,
        default=256,
        dest="mesh_batch",
        help="signatures per launch in the mesh-scaling section",
    )
    ap.add_argument(
        "--ingress",
        action="store_true",
        help="run the mempool_ingress section (batched+sharded CheckTx "
        "admission vs the legacy one-at-a-time path at 1/4/8 lanes)",
    )
    ap.add_argument(
        "--ingress-txs",
        type=int,
        default=1024,
        dest="ingress_txs",
        help="signed txs per ingress run",
    )
    ap.add_argument(
        "--ingress-threads",
        type=int,
        default=8,
        dest="ingress_threads",
        help="concurrent CheckTx submitter threads",
    )
    ap.add_argument(
        "--ingress-launch-ms",
        type=float,
        default=5.0,
        dest="ingress_launch_ms",
        help="emulated device launch cost per ingress verify call "
        "(kept small so the legacy run finishes)",
    )
    ap.add_argument(
        "--reads",
        action="store_true",
        help="run the reads section (light-client walks: sequential "
        "InquiringCertifier vs batched bisection over a 256-height "
        "rotating chain, + proofs-served/s)",
    )
    ap.add_argument(
        "--reads-heights",
        type=int,
        default=256,
        dest="reads_heights",
        help="chain length the read-path walks bridge",
    )
    ap.add_argument(
        "--reads-vals",
        type=int,
        default=8,
        dest="reads_vals",
        help="validators signing each reads-bench height",
    )
    ap.add_argument(
        "--reads-rotate-every",
        type=int,
        default=8,
        dest="reads_rotate_every",
        help="heights between single-validator rotations in the reads "
        "chain (controls how far each trust jump can skip)",
    )
    ap.add_argument(
        "--reads-launch-ms",
        type=float,
        default=86.0,
        dest="reads_launch_ms",
        help="emulated launch cost per read-path verify call (the "
        "same assumption as --launch-ms: the walk A/B is launch-count "
        "bound, so the launch cost is the weighting)",
    )
    ap.add_argument(
        "--finality-heights",
        type=int,
        default=12,
        dest="finality_heights",
        help="idle heights measured in the finality section (0 skips it)",
    )
    ap.add_argument(
        "--finality-loaded",
        type=int,
        default=10,
        dest="finality_loaded",
        help="heights measured under open-loop CheckTx traffic",
    )
    ap.add_argument(
        "--scenarios",
        default="churn_small,flash_crowd",
        help="comma-separated scenario library entries for the "
        "scenario_finality section (empty skips the section; the "
        "adaptive-timeout A/B on the slow-WAN topology always rides "
        "with it)",
    )
    ap.add_argument(
        "--gossip-msgs",
        type=int,
        default=4000,
        help="frames for the gossip-accounting overhead guard "
        "(0 skips the gossip_efficiency section)",
    )
    args = ap.parse_args(argv)
    sizes = [int(s) for s in args.sizes.split(",") if s]

    import jax

    on_device = jax.default_backend() != "cpu" and not args.no_device
    t0 = time.time()
    # Warmup pass over the SAME shapes, EXCLUDED from the percentile
    # pool (the tracing_overhead section's discipline applied to every
    # backend summary): the first call per shape pays imports/compiles/
    # memo fills — seconds against a sub-ms steady state — and would
    # own the reported p99 forever.
    sys.stderr.write("warmup pass (cold-start excluded from percentiles)...\n")
    drive_verify_host(sizes, 1)
    drive_hash(sizes, 1, "host")
    if on_device:
        drive_verify_device(sizes, 1)
        drive_verify_tables(n_vals=max(sizes), stack=1, reps=1)
        drive_hash(sizes, 1, "device")
    baselines = snapshot_baselines()
    sys.stderr.write(f"driving host verify {sizes} x{args.reps}...\n")
    drive_verify_host(sizes, args.reps)
    sys.stderr.write(f"driving host merkle {sizes} x{args.reps}...\n")
    drive_hash(sizes, args.reps, "host")
    sys.stderr.write(f"driving WAL fsync x{args.wal_records}...\n")
    drive_wal(args.wal_records)
    if args.statesync_kb > 0:
        sys.stderr.write(
            f"driving statesync snapshot+verify {args.statesync_kb}KB x{args.reps}...\n"
        )
        drive_statesync(args.statesync_kb, chunk_size=16 * 1024, reps=args.reps)
    if on_device:
        sys.stderr.write("driving device verify/tables/merkle...\n")
        drive_verify_device(sizes, args.reps)
        drive_verify_tables(n_vals=max(sizes), stack=8, reps=args.reps)
        drive_hash(sizes, args.reps, "device")
    # snapshot the backend summaries BEFORE the fast-sync replay: its
    # chain build + window verifies would otherwise pollute the
    # per-backend verifies/s with small consensus-shaped batches
    verify_summaries = {
        b: s
        for b in _VERIFY_BACKENDS
        if (s := backend_summary(b, baselines)) is not None
    }
    hash_summaries = {
        b: s
        for b in _HASH_BACKENDS
        if (s := hash_summary(b, baselines)) is not None
    }
    fastsync_pipeline = None
    if args.fastsync_blocks > 0:
        sys.stderr.write(
            f"driving fast-sync pipeline {args.fastsync_blocks} blocks x "
            f"{args.fastsync_vals} vals (sync vs overlapped)...\n"
        )
        fastsync_pipeline = drive_fastsync_pipeline(
            args.fastsync_blocks, args.fastsync_vals, args.launch_ms, on_device
        )
    dedup_steady_state = None
    if args.dedup_heights > 0:
        sys.stderr.write(
            f"driving dedup steady-state {args.dedup_heights} heights x "
            f"{args.dedup_vals} vals (cache off vs on)...\n"
        )
        dedup_steady_state = drive_dedup_steady_state(
            args.dedup_heights, args.dedup_vals, args.launch_ms
        )
    coalesce_multiconsumer = None
    if args.coalesce_rounds > 0:
        sys.stderr.write(
            f"driving 4-consumer coalescer {args.coalesce_rounds} rounds x "
            f"{args.coalesce_batch} sigs...\n"
        )
        coalesce_multiconsumer = drive_coalesce_multiconsumer(
            args.coalesce_rounds, args.coalesce_batch, args.launch_ms
        )
    tracing_overhead = None
    if args.dedup_heights > 0:
        sys.stderr.write(
            f"driving tracing overhead guard {args.dedup_heights} heights x "
            f"{args.dedup_vals} vals (sampling off vs 1/64)...\n"
        )
        tracing_overhead = drive_tracing_overhead(
            args.dedup_heights, args.dedup_vals, args.launch_ms
        )
    profiler_overhead = None
    if args.dedup_heights > 0:
        sys.stderr.write(
            f"driving profiler overhead guard {args.dedup_heights} heights x "
            f"{args.dedup_vals} vals (off vs 29 Hz + lock timing)...\n"
        )
        profiler_overhead = drive_profiler_overhead(
            args.dedup_heights, args.dedup_vals, args.launch_ms
        )
    device_efficiency = None
    if args.dedup_heights > 0:
        sys.stderr.write(
            f"driving device-efficiency guard {args.dedup_heights} heights x "
            f"{args.dedup_vals} vals (ledger off vs on + mesh occupancy)...\n"
        )
        device_efficiency = drive_device_efficiency(
            args.dedup_heights, args.dedup_vals, args.launch_ms
        )
    mempool_ingress = None
    if args.ingress:
        sys.stderr.write(
            f"driving mempool ingress {args.ingress_txs} signed txs x "
            f"{args.ingress_threads} threads (legacy vs batched @ 1/4/8 lanes)...\n"
        )
        mempool_ingress = drive_mempool_ingress(
            args.ingress_txs, args.ingress_threads, args.ingress_launch_ms
        )
    reads = None
    if args.reads:
        sys.stderr.write(
            f"driving read-path walks: {args.reads_heights} heights x "
            f"{args.reads_vals} vals, rotate every "
            f"{args.reads_rotate_every} (sequential vs bisect)...\n"
        )
        reads = drive_reads(
            args.reads_heights,
            args.reads_vals,
            args.reads_rotate_every,
            args.reads_launch_ms,
        )
    sharded_verify = None
    if args.mesh:
        sys.stderr.write(
            f"driving mesh scaling, batch {args.mesh_batch} at widths 1/2/4/8...\n"
        )
        sharded_verify = drive_mesh_scaling(args.mesh_batch, args.reps)

    # WAL stats are captured BEFORE the finality net runs: its four
    # live nodes fsync their own consensus WALs into the same histogram
    wal_count, wal_sum, wal_p50, wal_p99 = _histo("tendermint_wal_fsync_seconds")
    finality = None
    if args.finality_heights > 0:
        sys.stderr.write(
            f"driving live-net finality: {args.finality_heights} idle + "
            f"{args.finality_loaded} loaded heights x 4 validators...\n"
        )
        finality = drive_finality(args.finality_heights, args.finality_loaded)
    scenario_finality = None
    scenario_names = [s for s in args.scenarios.split(",") if s]
    if scenario_names:
        sys.stderr.write(
            f"driving scenario library: {', '.join(scenario_names)} "
            "+ adaptive-timeout A/B...\n"
        )
        scenario_finality = drive_scenario_finality(scenario_names)
    gossip_efficiency = None
    if args.gossip_msgs > 0:
        sys.stderr.write(
            f"driving gossip-accounting guard: {args.gossip_msgs} frames "
            "(off vs on) + 4-node WAN redundancy probe...\n"
        )
        gossip_efficiency = drive_gossip_efficiency(args.gossip_msgs)
    detail = {
        "wall_s": round(time.time() - t0, 2),
        "backend": jax.default_backend(),
        "verify": verify_summaries,
        "hash": hash_summaries,
        "statesync": statesync_summary(),
        "fastsync_pipeline": fastsync_pipeline,
        "dedup_steady_state": dedup_steady_state,
        "coalesce_multiconsumer": coalesce_multiconsumer,
        "tracing_overhead": tracing_overhead,
        "profiler_overhead": profiler_overhead,
        "device_efficiency": device_efficiency,
        "mempool_ingress": mempool_ingress,
        "reads": reads,
        "sharded_verify": sharded_verify,
        "finality": finality,
        "scenario_finality": scenario_finality,
        "gossip_efficiency": gossip_efficiency,
        "wal_fsync": {
            "count": wal_count,
            "fsyncs_per_s": round(wal_count / wal_sum, 1) if wal_sum else None,
            "p50_ms": round(wal_p50 * 1e3, 3) if wal_p50 is not None else None,
            "p99_ms": round(wal_p99 * 1e3, 3) if wal_p99 is not None else None,
        },
    }
    # headline: the fastest verify backend exercised this run
    best_backend, best = max(
        detail["verify"].items(), key=lambda kv: kv[1]["verifies_per_s"]
    )
    out = {
        "metric": f"hotpath_{best_backend}_verifies_per_s",
        "value": best["verifies_per_s"],
        "unit": "verifies/s",
        "detail": detail,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
