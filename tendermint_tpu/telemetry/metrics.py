"""The metric catalog: every exported series, registered at import.

One module owns all names so the exposition stays consistent and
greppable (docs/OBSERVABILITY.md is generated from this list by hand —
keep them in sync). Naming follows the reference's Prometheus
conventions (`tendermint_consensus_height`, ...); label values are
low-cardinality by construction: `backend` ∈ {host, device, tables,
mesh}, `kind` ∈ {verify, hash, tables}, `phase` ∈ round phases, never
peer ids or heights.

Process-global like the registry: a production process runs ONE node,
so node-scoped gauges (mempool depth, p2p rates) are process gauges.
Multi-node-in-process harnesses (testing/nemesis.py) see sums across
nodes for counters — exactly what their invariants want — and
last-writer-wins for gauges, which they avoid asserting on.
"""

from __future__ import annotations

from tendermint_tpu.telemetry.registry import (
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    CallbackCounter,
    Counter,
    Gauge,
    Histogram,
)

# -- consensus ----------------------------------------------------------------

CONSENSUS_HEIGHT = Gauge(
    "tendermint_consensus_height", "Current consensus height"
)
CONSENSUS_ROUND = Gauge(
    "tendermint_consensus_round", "Current consensus round"
)
CONSENSUS_PHASE_SECONDS = Histogram(
    "tendermint_consensus_phase_seconds",
    "Wall time spent in each round phase (propose/prevote/precommit/commit)",
    labelnames=("phase",),
    buckets=LATENCY_BUCKETS,
)
CONSENSUS_HEIGHT_SECONDS = Histogram(
    "tendermint_consensus_height_seconds",
    "Wall time from entering a height to finalizing its commit",
    buckets=LATENCY_BUCKETS,
)
CONSENSUS_COMMITS = Counter(
    "tendermint_consensus_commits_total", "Blocks finalized by this node"
)
CONSENSUS_TXS_COMMITTED = Counter(
    "tendermint_consensus_txs_committed_total", "Txs in blocks finalized by this node"
)
CONSENSUS_ROUND_SKIPS = Counter(
    "tendermint_consensus_round_skips_total",
    "Round-skip timeouts fired while starved at PREVOTE/PRECOMMIT",
    labelnames=("phase",),
)
VOTE_DRAIN_BATCH = Histogram(
    "tendermint_consensus_vote_drain_batch_size",
    "Consecutive same-(height,round,type) votes drained per receive-loop turn",
    buckets=SIZE_BUCKETS,
)

# -- finality observatory (telemetry/heightlog.py, consensus/state.py) --------
#
# `phase` is the fixed height lifecycle: new_height (commit timeout +
# waiting for round 0), propose, prevote, precommit, commit (waiting
# for the committed block, pre-apply), apply (ABCI + state update) —
# summed across rounds of one height, so the per-height phase set
# always sums to ~the commit-to-commit gap.

FINALITY_SECONDS = Histogram(
    "tendermint_finality_seconds",
    "Commit-to-commit gap: wall time between consecutive finalized "
    "commits on this node (the user-facing finality latency)",
    buckets=LATENCY_BUCKETS,
)
HEIGHT_PHASE_SECONDS = Histogram(
    "tendermint_height_phase_seconds",
    "Per-height time in each lifecycle phase (summed across rounds), "
    "from the HeightLedger record assembled at finalize",
    labelnames=("phase",),
    buckets=LATENCY_BUCKETS,
)
VOTE_ARRIVAL_SECONDS = Histogram(
    "tendermint_consensus_vote_arrival_seconds",
    "Vote timestamp to local arrival, aggregated over all peers "
    "(per-peer rollup lives in dump_telemetry; clock-skew clamped)",
    buckets=LATENCY_BUCKETS,
)
VOTE_ARRIVAL_MAX = Gauge(
    "tendermint_consensus_vote_arrival_max_seconds",
    "Worst single vote-arrival delay observed in the last finalized "
    "height (the laggard-validator signal)",
)

# -- cross-height pipeline (consensus/state.py pipelined finalize) ------------
#
# `reason` is the fixed join-barrier vocabulary: propose (proposer
# needed the applied app_hash/mempool), prevote (validate_block against
# applied state), vote_tally (H+1 vote needed the post-EndBlock
# valset), shutdown (stop() drained the pipeline), fault (the apply
# itself failed — the pipeline drains and consensus halts). A stall is
# a join that actually blocked H+1 progress; instant joins and the
# receive loop's opportunistic idle-joins (nothing queued — blocking
# delays nothing) don't count.

APPLY_OVERLAP_SECONDS = Histogram(
    "tendermint_consensus_apply_overlap_seconds",
    "Share of height H's ABCI apply + state advance that ran "
    "concurrently with H+1's NewHeight/Propose (pipelined finalize; "
    "0 on the serial path)",
    buckets=LATENCY_BUCKETS,
)
PIPELINE_STALLS = Counter(
    "tendermint_consensus_pipeline_stalls_total",
    "Join-barrier waits that actually blocked H+1 progress on H's "
    "in-flight apply, by the barrier that stalled",
    labelnames=("reason",),
)
CONSENSUS_TIMEOUT_DERIVED = Gauge(
    "tendermint_consensus_timeout_derived_seconds",
    "Current measured-latency-derived timeout per phase (clamped to "
    "the configured fixed value; absent while cold-starting on the "
    "fixed ladder)",
    labelnames=("phase",),
)

# -- device dispatch (verify / hash hot paths) --------------------------------

VERIFY_BATCH_SIZE = Histogram(
    "tendermint_verify_batch_size",
    "ed25519 signatures per verify call, by executing backend",
    labelnames=("backend",),
    buckets=SIZE_BUCKETS,
)
VERIFY_SECONDS = Histogram(
    "tendermint_verify_seconds",
    "ed25519 verify call latency, by executing backend",
    labelnames=("backend",),
    buckets=LATENCY_BUCKETS,
)
HASH_BATCH_LEAVES = Histogram(
    "tendermint_hash_batch_leaves",
    "Merkle leaves per root build, by executing backend",
    labelnames=("backend",),
    buckets=SIZE_BUCKETS,
)
HASH_SECONDS = Histogram(
    "tendermint_hash_seconds",
    "Merkle root build latency, by executing backend",
    labelnames=("backend",),
    buckets=LATENCY_BUCKETS,
)
TABLE_CACHE = Counter(
    "tendermint_verify_table_cache_total",
    "Valset comb-table cache outcomes. A lookup is one of hit, miss (it "
    "builds the table) and joined (it waited for a build of the same set "
    "in flight on another thread and built nothing); a miss is also "
    "counted incremental (new keys' columns joined to a cached set's) or "
    "host_build (on the host behind an open breaker) by how it was built",
    labelnames=("event",),
)
TABLE_KEYS_BUILT = Counter(
    "tendermint_verify_table_keys_built_total",
    "Keys whose comb table was computed, by the builder: host (Python "
    "integers, the few keys an incremental build lacks) or device (the "
    "build kernel: every column of a full build, pad columns with them)",
    labelnames=("how",),
)
TABLE_BUILD_KINDS = ("full", "incremental", "host_build", "prebuild")
TABLE_BUILD_SECONDS = Histogram(
    "tendermint_verify_table_build_seconds",
    "One build of a validator set's comb table (a table-cache miss, "
    "the `tables.build` stage of services/verifier.py), by kind: full "
    "(every column on the device), incremental (the new keys' columns "
    "joined to a cached set's), host_build (on the host behind an open "
    "breaker), prebuild (any of those on the thread state/execution.py "
    "starts when a block changes the set, beside the launches)",
    labelnames=("kind",),
    buckets=LATENCY_BUCKETS,
)
for _kind in TABLE_BUILD_KINDS:
    TABLE_BUILD_SECONDS.labels(kind=_kind)
for _event in ("hit", "miss", "joined", "incremental", "host_build"):
    TABLE_CACHE.labels(event=_event).inc(0)
for _how in ("host", "device"):
    TABLE_KEYS_BUILT.labels(how=_how).inc(0)
XLA_CACHE_ENABLED = Gauge(
    "tendermint_xla_persistent_cache_enabled",
    "1 when the persistent XLA executable cache is active",
)
XLA_CACHE_EVENTS = Counter(
    "tendermint_xla_persistent_cache_events_total",
    "Persistent executable cache lookups by outcome, as JAX reports "
    "them: a restarted process that finds its executables shows hits",
    labelnames=("event",),
)
# `fun` is the jitted function's name — bounded by the code, not by
# traffic; each compiled shape of one function is one observation
XLA_COMPILE_SECONDS = Histogram(
    "tendermint_xla_compile_seconds",
    "Backend compile (or cache retrieval) seconds per jitted function, "
    "as JAX reports them — apart from any launch's run time",
    labelnames=("fun",),
    buckets=LATENCY_BUCKETS,
)

# -- device observatory (telemetry/launchlog.py, tools/device_report.py) ------
#
# `kind` is the launch vocabulary (verify / hash / tables /
# leaf_hashes); `state` splits a launch's shipped rows into useful
# (requested work), padded (bucket/mesh geometry zeros — pure waste on
# device), and cached (rows the VerifiedSigCache withheld from the
# launch entirely); `stage` is the handle-lifecycle split (queue_wait /
# host_prep / in_flight / finalize). Per-launch detail (consumer mix,
# mesh width, compile attribution, exemplar trace) lives in the
# LaunchLedger records (`dump_telemetry?launches=N`), never as labels.

LAUNCH_ROWS = Counter(
    "tendermint_launch_rows",
    "Rows per device launch by disposition: useful (requested), padded "
    "(shape-bucket zeros shipped to device), cached (withheld by the "
    "verified-signature cache) — occupancy = useful / (useful + padded)",
    labelnames=("kind", "state"),
)
LAUNCH_STAGE_SECONDS = Histogram(
    "tendermint_launch_stage_seconds",
    "Per-launch stage durations from the dispatch-handle lifecycle: "
    "queue_wait (submit -> launch start), host_prep (lane prep + kernel "
    "dispatch), in_flight (enqueued on device -> consumer join), "
    "finalize (materialization blocking the consumer)",
    labelnames=("stage",),
    buckets=LATENCY_BUCKETS,
)
# byte-sized buckets: 1 KiB floor (a small lane batch) to 2 GiB (the
# 10k-valset sharded comb tables), x4 per step
TRANSFER_BUCKETS = tuple(float(1024 * 4**i) for i in range(11))
LAUNCH_TRANSFER_BYTES = Histogram(
    "tendermint_launch_transfer_bytes",
    "Host->device bytes shipped per launch (lane arrays, padded hash "
    "blocks, sharded-table device_put on placement-cache misses)",
    buckets=TRANSFER_BUCKETS,
)

# -- multi-chip verify mesh (parallel/mesh.py) --------------------------------
#
# `direction` is the re-mesh kind: "shrink" (shard fault -> survivors)
# or "restore" (re-probe brought the full mesh back) — a fixed pair.

MESH_DEVICES = Gauge(
    "tendermint_mesh_devices",
    "Devices currently active in the sharded verify/hash mesh",
)
MESH_SHARD_FAULTS = Counter(
    "tendermint_mesh_shard_faults_total",
    "Per-shard device faults observed by mesh launches",
)
MESH_REMESH = Counter(
    "tendermint_mesh_remesh_total",
    "Mesh rebuilds (shrink = onto survivors after a shard fault, "
    "restore = full mesh back after a successful re-probe)",
    labelnames=("direction",),
)
MESH_COMPILE = Counter(
    "tendermint_mesh_compile_total",
    "Compiled-step cache (_STEP_CACHE) lookups by outcome: a miss "
    "means a launch paid an XLA compile (survivor re-mesh, new "
    "program, fresh process)",
    labelnames=("result",),
)
MESH_COMPILE_SECONDS = Histogram(
    "tendermint_mesh_compile_seconds",
    "Wall time one compiled-step cache miss spent building/compiling "
    "the sharded step (the launch that pays it stalls for the duration)",
    buckets=LATENCY_BUCKETS,
)
TABLE_DEVICE_CACHE = Counter(
    "tendermint_table_device_cache_total",
    "Per-(valset, device-set) sharded-table placement cache outcomes; "
    "a miss re-ships the comb tables to device (device_put, GB-scale "
    "at large valsets)",
    labelnames=("result",),
)

# -- resilient dispatch / circuit breaker -------------------------------------

BREAKER_STATE = Gauge(
    "tendermint_breaker_state",
    "Circuit breaker state (0=closed, 1=half_open, 2=open)",
    labelnames=("kind",),
)
BREAKER_TRANSITIONS = Counter(
    "tendermint_breaker_transitions_total",
    "Breaker state transitions; to=open counts trips, to=closed recoveries",
    labelnames=("kind", "to"),
)
DISPATCH_PRIMARY = Counter(
    "tendermint_device_primary_calls_total",
    "Calls answered by the primary (device) backend",
    labelnames=("kind",),
)
DISPATCH_FALLBACK = Counter(
    "tendermint_device_fallback_calls_total",
    "Calls degraded to the host fallback",
    labelnames=("kind",),
)
DISPATCH_FAILURES = Counter(
    "tendermint_device_dispatch_failures_total",
    "Primary dispatch attempts that raised (pre-retry granularity)",
    labelnames=("kind",),
)

# -- async dispatch pipeline (services/dispatch.py) ---------------------------
#
# `queue` labels are the pipeline owners ("fastsync", "consensus",
# "default") — a fixed small set, never per-peer/per-height.

DISPATCH_INFLIGHT = Gauge(
    "tendermint_dispatch_inflight",
    "Launches submitted to a dispatch queue and not yet joined",
    labelnames=("queue",),
)
DISPATCH_QUEUE_WAIT = Histogram(
    "tendermint_dispatch_queue_wait_seconds",
    "Time a launch waited in the dispatch queue before starting",
    labelnames=("queue",),
    buckets=LATENCY_BUCKETS,
)
# Per-handle share of submit->join wall time the consumer spent doing
# other work (host prep, ABCI applies) instead of blocked in result().
# 0 = fully synchronous behavior; anything > 0 proves the overlap
# pipeline engaged.
DISPATCH_OVERLAP = Histogram(
    "tendermint_dispatch_overlap_ratio",
    "Fraction of a dispatch handle's lifetime overlapped with host work",
    labelnames=("queue",),
    buckets=(0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 1.0),
)

# -- verify coalescer + dedup cache (services/batcher.py) ---------------------
#
# `consumer` labels are the verify-request owners ("consensus",
# "fastsync", "statesync", "rpc", "mempool", "lightclient",
# "default") — a fixed small set.

VERIFY_CACHE_HITS = Counter(
    "tendermint_verify_cache_hits_total",
    "Signature triples answered from the verified-signature dedup cache",
)
VERIFY_CACHE_MISSES = Counter(
    "tendermint_verify_cache_misses_total",
    "Signature triples not in the dedup cache (dispatched for verification)",
)
VERIFY_CACHE_EVICTIONS = Counter(
    "tendermint_verify_cache_evictions_total",
    "Proven triples evicted from the dedup cache by LRU pressure",
)
BATCHER_COALESCE = Histogram(
    "tendermint_batcher_coalesce_factor",
    "Verify requests merged into one coalesced device launch",
    buckets=SIZE_BUCKETS,
)
BATCHER_FLUSH = Counter(
    "tendermint_batcher_flush_total",
    "Coalescer flushes by trigger (window/size/barrier)",
    labelnames=("reason",),
)
BATCHER_WAIT = Histogram(
    "tendermint_batcher_wait_seconds",
    "Time a verify request waited in the coalescer before its launch",
    labelnames=("consumer",),
    buckets=LATENCY_BUCKETS,
)

# -- distributed tracing (telemetry/tracectx.py, tools/trace_timeline.py) -----
#
# `stage` is a fixed per-vote lifecycle slice: "drain" (gossip arrival
# -> drained into a batch), "verify" (batch submit -> verdict join),
# "e2e" (arrival -> verdict applied). Histograms carry exemplar trace
# ids (JSON dump only) so an aggregate links back to one traced message.

TX_E2E = Histogram(
    "tendermint_tx_e2e_seconds",
    "Tx first-seen (CheckTx admission) to committed in a finalized block",
    buckets=LATENCY_BUCKETS,
)
VOTE_STAGE = Histogram(
    "tendermint_vote_stage_seconds",
    "Traced-vote lifecycle slices (drain/verify/e2e) on this node",
    labelnames=("stage",),
    buckets=LATENCY_BUCKETS,
)
TRACE_SAMPLED = Counter(
    "tendermint_trace_sampled_total",
    "Trace contexts minted (head-based sampling said yes)",
)
TRACE_PROPAGATED = Counter(
    "tendermint_trace_propagated_total",
    "p2p frames sent carrying a trace context",
)
TRACE_DROPPED = Counter(
    "tendermint_trace_dropped_total",
    "Trace contexts lost (wire decode failures, trace-table evictions)",
)

# The span-name catalog: every literal passed to TRACER.span()/.add()
# in the package must appear here (collection-time lint in
# tests/conftest.py, same discipline as the tendermint_* metric lint) —
# an uncataloged span name means a timeline query that silently matches
# nothing. The consensus round phases are recorded via an f-string over
# the fixed phase set; they are cataloged for the tooling regardless.
SPAN_CATALOG = frozenset(
    {
        "consensus.propose",
        "consensus.prevote",
        "consensus.precommit",
        "consensus.commit",
        "consensus.height",
        "lightclient.walk",
        "mempool.admission",
        "mempool.window",
        "p2p.hop",
        "scenario.run",
        "batcher.flush",
        "dispatch.launch",
        "fastsync.redo",
        "fastsync.window",
        "tables.build",
        "tx.e2e",
        "valset.change",
        "vote.e2e",
    }
)

# Pre-seed the known breaker kinds, round-skip phases, and flush reasons
# so scrapes see zero-valued series before (or without) any
# instance/event — Prometheus convention: known label values start at 0,
# absence means "unknown".
for _kind in ("verify", "hash", "tables"):
    BREAKER_STATE.labels(kind=_kind).set(0)
for _phase in ("prevote", "precommit"):
    CONSENSUS_ROUND_SKIPS.labels(phase=_phase).inc(0)
for _reason in ("window", "size", "barrier"):
    BATCHER_FLUSH.labels(reason=_reason).inc(0)
for _direction in ("shrink", "restore"):
    MESH_REMESH.labels(direction=_direction).inc(0)
for _result in ("hit", "miss"):
    MESH_COMPILE.labels(result=_result).inc(0)
    TABLE_DEVICE_CACHE.labels(result=_result).inc(0)
    XLA_CACHE_EVENTS.labels(event=_result).inc(0)
for _kind in ("verify", "hash", "tables", "leaf_hashes"):
    for _state in ("useful", "padded", "cached"):
        LAUNCH_ROWS.labels(kind=_kind, state=_state).inc(0)
for _stage in ("queue_wait", "host_prep", "in_flight", "finalize"):
    LAUNCH_STAGE_SECONDS.labels(stage=_stage)
for _stage in ("drain", "verify", "e2e"):
    VOTE_STAGE.labels(stage=_stage)
for _phase in ("new_height", "propose", "prevote", "precommit", "commit", "apply"):
    HEIGHT_PHASE_SECONDS.labels(phase=_phase)
for _reason in ("propose", "prevote", "vote_tally", "shutdown", "fault"):
    PIPELINE_STALLS.labels(reason=_reason).inc(0)
for _phase in ("propose", "prevote", "precommit", "commit"):
    CONSENSUS_TIMEOUT_DERIVED.labels(phase=_phase)

# -- contention observatory (telemetry/profiler.py, utils/lockrank.py) --------
#
# `subsystem` is the fixed classification vocabulary the profiler maps
# thread names/stacks into (consensus, ingress, coalescer, dispatch,
# p2p_recv, p2p_send, statesync, rpc, abci, main, other); `state` is
# on_cpu or blocked; `wait` is the blocked-reason split (lock/io/sleep/
# other — "other" includes runnable-but-GIL-starved). `lock` label
# values come from the bounded utils/lockrank.py annotation vocabulary.
# All series only advance while profiling is armed
# (TENDERMINT_TPU_PROFILE_HZ > 0 or a profiler boost window).

PROFILE_SAMPLES = Counter(
    "tendermint_profile_samples_total",
    "Profiler stack samples by subsystem and on-CPU/blocked state "
    "(state=blocked carries the wait-reason split)",
    labelnames=("subsystem", "state", "wait"),
)
PROFILE_TICK_SECONDS = Histogram(
    "tendermint_profile_tick_seconds",
    "Wall time one profiler sampling pass took (self-overhead guard)",
    buckets=LATENCY_BUCKETS,
)
LOCK_WAIT_SECONDS = Histogram(
    "tendermint_lock_wait_seconds",
    "Blocking acquire-wait per annotated ranked lock (armed profiling "
    "only; per-site attribution in dump_telemetry?profile=1)",
    labelnames=("lock",),
    buckets=LATENCY_BUCKETS,
)
LOCK_HOLD_SECONDS = Histogram(
    "tendermint_lock_hold_seconds",
    "Hold duration per annotated ranked lock (armed profiling only)",
    labelnames=("lock",),
    buckets=LATENCY_BUCKETS,
)

# -- process resources (telemetry/process.py) ---------------------------------

PROCESS_RSS = Gauge(
    "tendermint_process_rss_bytes", "Resident set size of this process"
)
PROCESS_FDS = Gauge(
    "tendermint_process_open_fds", "Open file descriptors in this process"
)
PROCESS_THREADS = Gauge(
    "tendermint_process_threads", "Live Python threads in this process"
)
PROCESS_GC_PAUSE = Histogram(
    "tendermint_process_gc_pause_seconds",
    "Stop-the-world GC collection pauses by generation (gc.callbacks "
    "timing; installed by telemetry/process.py install_gc_telemetry): "
    "gen=2 walks everything that is not frozen",
    labelnames=("gen",),
    buckets=LATENCY_BUCKETS,
)
PROCESS_GC_COLLECTIONS = Counter(
    "tendermint_process_gc_collections_total",
    "GC collections by generation",
    labelnames=("gen",),
)

# the hook never takes a family lock, so it finds its children here
for _gen in ("0", "1", "2"):
    PROCESS_GC_COLLECTIONS.labels(gen=_gen).inc(0)
    PROCESS_GC_PAUSE.labels(gen=_gen)

# live views cost nothing between scrapes (same discipline as the
# node-bound gauges below, but process-scoped so no node is needed)
from tendermint_tpu.telemetry import process as _process  # noqa: E402

PROCESS_RSS.set_function(_process.rss_bytes)
PROCESS_FDS.set_function(_process.open_fds)
PROCESS_THREADS.set_function(_process.thread_count)
PROCESS_GC_FROZEN = Gauge(
    "tendermint_process_gc_frozen_objects",
    "Objects the collector no longer walks (gc.get_freeze_count, read at "
    "scrape time): what telemetry/process.py settle_heap froze, JAX's "
    "traced programs before all; hundreds of thousands on a node that has "
    "met its executables, 0 on one whose collector policy never engaged",
)
PROCESS_GC_FROZEN.set_function(_process.frozen_objects)
PROCESS_HEAP_SETTLES = CallbackCounter(
    "tendermint_process_heap_settles_total",
    "Times the heap was frozen (telemetry/process.py settle_heap): once "
    "at node start and once more after each executable met later",
    _process.heap_settles,
)
PROCESS_CPU_SECONDS = CallbackCounter(
    "tendermint_process_cpu_seconds_total",
    "CPU time of this process, every thread of it (time.process_time, "
    "read at scrape time): a rise of one second a second is one core; "
    "more only by C code that let the interpreter lock go",
    _process.cpu_seconds,
)
PROCESS_THREAD_CPU_SECONDS = CallbackCounter(
    "tendermint_process_thread_cpu_seconds",
    "CPU time of the interpreter's threads (each thread's own CPU "
    "clock, read at scrape time), summed by the contention profiler's "
    "thread classes (telemetry/profiler.py classify_thread, by thread "
    "name). A thread that exited keeps the last value read for it: what "
    "it ran between its last scrape and its exit is in the process's "
    "total alone",
    _process.thread_cpu_seconds,
    labelnames=("thread",),
)

# -- fast sync (blockchain/reactor.py) ----------------------------------------
#
# Where a catching-up node's time goes, measured where the work happens
# (`TRACER.stage`): `decode` runs on the p2p receive thread, every other
# stage on the one sync thread, so the sync-thread sums over wall time
# say how much of that thread the stages cover. `cut` is why a verify
# window carried fewer than VERIFY_WINDOW commits.

FASTSYNC_STAGES = (
    "decode", "part_set", "verify_submit", "verify_wait", "store",
    "validate", "exec", "state_save", "starved",
)
# stages inside another stage: their time is their parent's too
FASTSYNC_CHILD_STAGES = ("index_rows",)
FASTSYNC_CUTS = ("full", "pool_gap", "boundary")

FASTSYNC_STAGE_SECONDS = Histogram(
    "tendermint_fastsync_stage_seconds",
    "Fast-sync host time by stage: decode (a block_response, p2p thread), "
    "part_set (window prep: peek, part sets, block ids, linkage), "
    "verify_submit (sign-bytes, lanes, launch submit), verify_wait (the "
    "verdict join the pipeline failed to hide, plus the tally), store "
    "(save_block), validate / exec / state_save (apply_block), starved "
    "(the sync loop's idle tick: nothing to prepare, nothing in flight); "
    "and inside state_save, index_rows (building the block's tx index "
    "rows: keys, packed values and the run log's value section, before "
    "its append)",
    labelnames=("stage",),
    buckets=LATENCY_BUCKETS,
)
FASTSYNC_STAGE_CPU_SECONDS = Counter(
    "tendermint_fastsync_stage_cpu_seconds_total",
    "CPU time of the thread that ran each fast-sync stage, over the "
    "same stretches as tendermint_fastsync_stage_seconds (one Stage, "
    "two clocks). A stage's seconds less its CPU seconds is what its "
    "thread slept (a disk, the device, a socket) or waited for the "
    "interpreter lock",
    labelnames=("stage",),
)
FASTSYNC_BLOCKS_APPLIED = Counter(
    "tendermint_fastsync_blocks_applied_total",
    "Blocks stored and applied by fast-sync",
)
FASTSYNC_WINDOWS = Counter(
    "tendermint_fastsync_windows_total",
    "Verify windows joined, by why the window ended where it did: full "
    "(VERIFY_WINDOW commits), pool_gap (the next height was not "
    "downloaded yet), boundary (the validator set changes)",
    labelnames=("cut",),
)

for _stage in FASTSYNC_STAGES + FASTSYNC_CHILD_STAGES:
    FASTSYNC_STAGE_SECONDS.labels(stage=_stage)
    FASTSYNC_STAGE_CPU_SECONDS.labels(stage=_stage).inc(0)
for _cut in FASTSYNC_CUTS:
    FASTSYNC_WINDOWS.labels(cut=_cut).inc(0)

# A redo (`BlockchainReactor._redo`): a block that cannot be what the
# chain committed is forgotten with whatever else its server delivered,
# and the server is debited and dropped. `cause` is what found it:
# `block_id` (a block's id is not the one its successor's commit
# carries), `verdict` (a window's commit failed verification), `prep`
# (a malformed commit, refused before any launch), `body` (a verified
# block the state refused).

FASTSYNC_REDO_CAUSES = ("block_id", "verdict", "prep", "body")

FASTSYNC_REDOS = Counter(
    "tendermint_fastsync_redos_total",
    "Fast-sync redos, by what found the fault: block_id (a block's id "
    "against its successor's commit), verdict (a commit's signatures or "
    "power), prep (a malformed commit), body (a verified block the "
    "state refused)",
    labelnames=("cause",),
)
FASTSYNC_REDO_BLOCKS_DROPPED = Counter(
    "tendermint_fastsync_redo_blocks_dropped_total",
    "Downloaded blocks a redo made the pool forget: blamed (the debited "
    "peer served them) or others (anyone else did: fetched again for "
    "nothing; 0 since a redo forgets the blamed peer's blocks alone)",
    labelnames=("whose",),
)
FASTSYNC_PREFIX_BLOCKS_APPLIED = Counter(
    "tendermint_fastsync_prefix_blocks_applied_total",
    "Blocks applied from the verified prefix of a window whose verdict "
    "failed (the entries before the one the verdict names)",
)
FASTSYNC_REDO_RECOVER_SECONDS = Histogram(
    "tendermint_fastsync_redo_recover_seconds",
    "From a redo to the apply of the height it was called at: the "
    "standstill a fault costs (the refetch from another peer, the "
    "window prepared again)",
    buckets=LATENCY_BUCKETS,
)

for _cause in FASTSYNC_REDO_CAUSES:
    FASTSYNC_REDOS.labels(cause=_cause).inc(0)
for _whose in ("blamed", "others"):
    FASTSYNC_REDO_BLOCKS_DROPPED.labels(whose=_whose).inc(0)

# -- a vote's bytes (types/vote.py, types/block.py) ---------------------------
#
# Both are built at most once: over tendermint_fastsync_blocks_applied_total
# a catching-up node reads no wire encoding a vote where the peer's bytes
# were canonical (they are kept: tendermint_vote_wire_kept_total reads the
# validator count a block) and one where they were not, never one each for
# the part set, the commit's hash and the store; and, where every validator
# signed the same block at the same time, one sign-bytes encoding a commit.

VOTE_ENCODES = Counter(
    "tendermint_vote_encodes_total",
    "Vote wire encodings computed (Vote.encode keeps its bytes on the "
    "frozen vote, so every later caller reuses them): about none a "
    "fast-synced block from a peer that sends canonical bytes, the "
    "validator count from one that pads a varint in every vote",
)
COMMIT_SIGNBYTES = Counter(
    "tendermint_commit_signbytes_total",
    "Sign-bytes handed to a commit verifier, added once a commit walked "
    "(Commit.vote_sign_bytes): encoded (one canonical-JSON encoding a "
    "distinct signed content: block_id, height, round, timestamp, type) "
    "or shared (another vote of the commit had the same content)",
    labelnames=("source",),
)
for _source in ("encoded", "shared"):
    COMMIT_SIGNBYTES.labels(source=_source).inc(0)
VOTE_WIRE_KEPT = Counter(
    "tendermint_vote_wire_kept_total",
    "Votes whose decoded bytes were kept as their encoding (Vote.decode: "
    "every varint in them minimal, so Vote.encode would build the same "
    "bytes): the validator count a fast-synced block, 0 for a vote with "
    "a padded varint, which is encoded for itself on first use",
)
BLOCK_DATA_ENCODES = Counter(
    "tendermint_block_data_encodes_total",
    "Data.encode calls (a block's data section: the tx count and each tx "
    "under its length), one a call: kept (the section Data.decode_from read "
    "the txs from was handed back: no varint in it padded, nothing after "
    "its last tx, txs not reassigned) or walked (encoded tx by tx: a block "
    "that was made, or a section a decoder takes and an encoder would not "
    "write). A fast-synced block is encoded once, for its part set",
    labelnames=("how",),
)
for _how in ("kept", "walked"):
    BLOCK_DATA_ENCODES.labels(how=_how).inc(0)
COMMIT_VOTES_DECODED = Counter(
    "tendermint_commit_votes_decoded_total",
    "Precommits Commit.decode_from read, added once a commit decoded: "
    "shared (read against the commit's first vote: the same layout, height, "
    "round, type and block_id by their bytes, one BlockID among them) or "
    "plain (Vote.decode: the first vote present, and any whose bytes differ "
    "in more than address, index, timestamp and signature): N - 1 and 1 a "
    "commit whose validators all signed the block, canonical bytes",
    labelnames=("path",),
)
for _path in ("shared", "plain"):
    COMMIT_VOTES_DECODED.labels(path=_path).inc(0)

# -- a validator set's root (types/validator_set.py) --------------------------

VALSET_HASHES = Counter(
    "tendermint_valset_hashes_total",
    "Validator-set Merkle roots actually computed (ValidatorSet.hash keeps "
    "its root and copy() hands it on; only a membership or power change "
    "drops it): about one a process on a static set, not one a block",
)

VALSET_CHANGES = Counter(
    "tendermint_valset_changes_total",
    "Validators a block's EndBlock diffs changed, as state/state.py "
    "applied them: join (a key the set did not hold), leave (power 0) or "
    "power (a held key re-weighted); one `valset.change` span a block "
    "that changes the set carries the three counts and the seconds",
    labelnames=("kind",),
)
for _kind in ("join", "leave", "power"):
    VALSET_CHANGES.labels(kind=_kind).inc(0)

# -- databases (db/kv.py) -----------------------------------------------------

DB_COMMITS = Counter(
    "tendermint_db_commits_total",
    "Durable writes (one fsync each) by store: on a SQLite file a "
    "transaction (a set, a set_sync, a delete or a whole write batch), on "
    "the tx index's run log (db/runlog.py) a block's one appended record. "
    "Over tendermint_fastsync_blocks_applied_total a block reads 4: "
    "blockstore 1, state 2 (ABCI responses, state), txindex 1",
    labelnames=("db",),
)

DB_READS = Counter(
    "tendermint_db_reads_total",
    "Reads of a SQLite file by store, one a get and one a get_many "
    "whatever the number of its keys: each is one taking of the "
    "database's lock and one statement of one row. Over "
    "tendermint_rpc_phase_seconds_count{method=\"block\",phase=\"handle\"} "
    "the blockstore's reads are 2 a /block answer (the meta row, then "
    "all part rows) where they were 1 + the block's parts",
    labelnames=("db",),
)

DB_COMMIT_SECONDS = Histogram(
    "tendermint_db_commit_seconds",
    "One durable write, timed where it happens (the `db.commit` stage): "
    "exactly what tendermint_db_commits_total counts, so _count equals "
    "it: on a SQLite file the commit() of the one transaction, on the tx "
    "index's run log the record's write and fsync",
    labelnames=("db",),
    buckets=LATENCY_BUCKETS,
)
DB_COMMIT_CPU_SECONDS = Counter(
    "tendermint_db_commit_cpu_seconds_total",
    "CPU time of the committing thread inside those durable writes (a "
    "commit runs some C as well as sleeping on the disk): "
    "tendermint_db_commit_seconds_sum less this is the sleep",
    labelnames=("db",),
)

# -- the tx index's run log (db/runlog.py) ------------------------------------

TXINDEX_BYTES_WRITTEN = Counter(
    "tendermint_txindex_bytes_written_total",
    "Bytes the tx index wrote, by kind: append (a block's record: its "
    "sorted keys, its values, header and checksum; 40 bytes a row and a "
    "packed value of 33 and the tx, data and log) and merge (key files a "
    "merge wrote; values are never rewritten). merge over append is the "
    "write amplification",
    labelnames=("kind",),
)
for _kind in ("append", "merge"):
    TXINDEX_BYTES_WRITTEN.labels(kind=_kind).inc(0)
TXINDEX_MERGES = Counter(
    "tendermint_txindex_merges_total",
    "Merges of the tx index's runs finished (each: one key file written "
    "and fsynced, one manifest switch), all off the thread that appends",
)
TXINDEX_RUNS = Gauge(
    "tendermint_txindex_runs",
    "Live runs of the tx index: what a lookup of an absent hash probes. "
    "Under 8 a size tier once the merger has caught up",
)
TXINDEX_PROBES = Histogram(
    "tendermint_txindex_probes",
    "Runs probed by one lookup of the tx index (newest first, until the "
    "hash is found)",
    buckets=(1, 2, 4, 8, 16, 32, 64),
)
TXINDEX_VALUES_READ = Counter(
    "tendermint_txindex_values_read_total",
    "Values of the tx index decoded for a lookup, by the form their first "
    "byte gives: packed (a fixed header and the raw tx, data and log) or "
    "json (a row written before the packed form, in a txindex/ directory "
    "or a txindex.db)",
    labelnames=("form",),
)
for _form in ("packed", "json"):
    TXINDEX_VALUES_READ.labels(form=_form).inc(0)

# -- state sync ---------------------------------------------------------------

STATESYNC_CHUNKS = Counter(
    "tendermint_statesync_chunks_total",
    "Snapshot chunks received while syncing (ok/corrupt/timeout)",
    labelnames=("result",),
)
STATESYNC_CHUNKS_SERVED = Counter(
    "tendermint_statesync_chunks_served_total",
    "Snapshot chunks served to syncing peers",
)
STATESYNC_CHUNK_VERIFY_SECONDS = Histogram(
    "tendermint_statesync_chunk_verify_seconds",
    "Batched Merkle verification latency over a full snapshot chunk set",
    buckets=LATENCY_BUCKETS,
)
STATESYNC_RESTORE_SECONDS = Histogram(
    "tendermint_statesync_restore_seconds",
    "Wall time from snapshot selection to restored state (incl. chunk fetch)",
    buckets=LATENCY_BUCKETS,
)
STATESYNC_SNAPSHOT_SECONDS = Histogram(
    "tendermint_statesync_snapshot_seconds",
    "Snapshot creation latency (serialize + chunk + device tree + persist)",
    buckets=LATENCY_BUCKETS,
)
STATESYNC_SNAPSHOTS_TAKEN = Counter(
    "tendermint_statesync_snapshots_taken_total", "Snapshots created by this node"
)
STATESYNC_SNAPSHOTS_REJECTED = Counter(
    "tendermint_statesync_snapshots_rejected_total",
    "Offered snapshots rejected (trust anchoring, bad chunks, timeouts)",
)
STATESYNC_RESTORES = Counter(
    "tendermint_statesync_restores_total",
    "Snapshot restore attempts by outcome (ok/failed)",
    labelnames=("result",),
)

for _result in ("ok", "corrupt", "timeout"):
    STATESYNC_CHUNKS.labels(result=_result).inc(0)

# -- light-client serving layer (tendermint_tpu/lightclient/) -----------------
#
# `result` is the fixed walk-outcome vocabulary: ok (trust advanced to
# the target), too_much_change (bisection bottomed out — the valset
# churned faster than the source's commit density can bridge), forged
# (a candidate carried an invalid signature / impossible quorum — a
# provider offense, never a bisection trigger), trust_expired (the
# LOCAL pin outlived the trust period — operator action, not a peer
# offense), no_source (the source provider had nothing to offer —
# fetch timeout / lagging provider, environmental). Only `forged` is
# an alertable provider offense. `mode` distinguishes
# the legacy header-by-header walk (sequential — the
# InquiringCertifier baseline) from the skipping walk (bisect).
# `kind` on the proofs-served counter is the fixed query vocabulary
# (full_commit / commit / validators / tx / abci_query) — never
# heights or peer ids.

LIGHTCLIENT_BISECTIONS = Counter(
    "tendermint_lightclient_bisections_total",
    "Skipping-verification walks by outcome (ok / too_much_change / "
    "forged / trust_expired / no_source)",
    labelnames=("result",),
)
LIGHTCLIENT_WALK_SECONDS = Histogram(
    "tendermint_lightclient_walk_seconds",
    "Wall time one certifier walk took to move trust to the target "
    "height (sequential = header-by-header InquiringCertifier, "
    "bisect = batched skipping verification)",
    labelnames=("mode",),
    buckets=LATENCY_BUCKETS,
)
LIGHTCLIENT_CACHE_HITS = Counter(
    "tendermint_lightclient_cache_hits_total",
    "FullCommit lookups answered from the certified-commit cache",
)
LIGHTCLIENT_CACHE_MISSES = Counter(
    "tendermint_lightclient_cache_misses_total",
    "FullCommit lookups that missed the certified-commit cache",
)
REPLICA_PROOFS_SERVED = Counter(
    "tendermint_replica_proofs_served_total",
    "Light-client queries answered by this node's serving layer, by "
    "proof kind (p2p FullCommit channel + proof-carrying RPC routes)",
    labelnames=("kind",),
)

for _result in ("ok", "too_much_change", "forged", "trust_expired", "no_source"):
    LIGHTCLIENT_BISECTIONS.labels(result=_result).inc(0)
for _mode in ("sequential", "bisect"):
    LIGHTCLIENT_WALK_SECONDS.labels(mode=_mode)
for _kind in ("full_commit", "commit", "validators", "tx", "abci_query"):
    REPLICA_PROOFS_SERVED.labels(kind=_kind).inc(0)

# -- p2p ----------------------------------------------------------------------

P2P_SENT_BYTES = Counter(
    "tendermint_p2p_sent_bytes_total", "Frame bytes sent to peers"
)
P2P_RECV_BYTES = Counter(
    "tendermint_p2p_recv_bytes_total", "Frame bytes received from peers"
)
P2P_PEERS = Gauge("tendermint_p2p_peers", "Connected peers")
P2P_SEND_RATE = Gauge(
    "tendermint_p2p_send_rate_bytes", "Aggregate send rate over live peers, bytes/s"
)
P2P_RECV_RATE = Gauge(
    "tendermint_p2p_recv_rate_bytes", "Aggregate recv rate over live peers, bytes/s"
)
# Send-queue depth is the backpressure signal: a climbing depth means a
# peer drains slower than reactors produce. Exported as the aggregate
# sum and the worst single peer (per-peer series would be unbounded
# cardinality — peer ids churn; the max pinpoints "one slow peer"
# vs "everyone backed up" without it).
P2P_SEND_QUEUE = Gauge(
    "tendermint_p2p_send_queue_depth",
    "Frames queued for send across all peers and channels",
)
P2P_SEND_QUEUE_MAX = Gauge(
    "tendermint_p2p_send_queue_max",
    "Deepest single-peer send queue (frames)",
)
# The wait twin of the depth gauges: enqueue -> send-loop dequeue per
# frame, aggregated over all peers/channels — the p2p leg of the
# queue-wait unification (dump_telemetry?profile=1 "queues" view).
P2P_SEND_WAIT = Histogram(
    "tendermint_p2p_send_wait_seconds",
    "Time a frame waited in a peer send queue before hitting the wire",
    buckets=LATENCY_BUCKETS,
)
# Adversarial-input defense (p2p/score.py + Switch.report_misbehavior):
# `kind` is the fixed offense vocabulary (bad_frame/oversize_frame/
# bad_msg/bad_sig/bad_vote/forged_block/forged_fullcommit/
# bad_evidence/flood) — never
# peer ids (per-peer scores live in the scorer's diagnostics snapshot).
PEER_MISBEHAVIOR = Counter(
    "tendermint_p2p_peer_misbehavior_total",
    "Classified peer offenses debited against misbehavior scores",
    labelnames=("kind",),
)
PEER_BANS = Counter(
    "tendermint_p2p_peer_bans_total",
    "Peers banned for crossing the misbehavior threshold",
)

for _kind in (
    "bad_frame",
    "oversize_frame",
    "bad_msg",
    "bad_sig",
    "bad_vote",
    "forged_block",
    "forged_fullcommit",
    "bad_evidence",
    "flood",
):
    PEER_MISBEHAVIOR.labels(kind=_kind).inc(0)

# -- gossip observatory (telemetry/gossiplog.py) ------------------------------
#
# Per-channel bandwidth attribution and duplicate-delivery redundancy.
# `channel` and `kind` are the FIXED wire vocabularies below — the
# channel-id map and first-byte message tags mirrored from the reactors
# by gossiplog.py (unknown -> "other"), never peer ids or heights.
# Per-peer tables and first-seen propagation stamps are dump-only
# (`dump_telemetry?gossip=1`); tools/gossip_report.py merges them
# across nodes.

GOSSIP_CHANNELS = (
    "pex",
    "cns_state",
    "cns_data",
    "cns_vote",
    "cns_votebits",
    "mempool",
    "evidence",
    "blockchain",
    "statesync",
    "lightclient",
    "ctrl",
    "other",
)
GOSSIP_KINDS = (
    "pex_request",
    "pex_addrs",
    "new_round_step",
    "commit_step",
    "proposal",
    "proposal_pol",
    "block_part",
    "vote",
    "has_vote",
    "vote_set_maj23",
    "vote_set_bits",
    "proposal_heartbeat",
    "tx",
    "evidence_list",
    "block_request",
    "block_response",
    "no_block",
    "status_request",
    "status_response",
    "snapshots_request",
    "snapshots_response",
    "chunk_request",
    "chunk_response",
    "no_chunk",
    "commit_request",
    "commit_response",
    "fc_request",
    "fc_response",
    "fc_subscribe",
    "fc_announce",
    "ping",
    "pong",
    "other",
)
# The silent-dedup vocabulary: kinds whose duplicate deliveries used to
# vanish (VoteSet exact-dup adds, PartSet already-have parts, mempool
# dup-cache hits on re-arrival, evidence-pool re-offers).
GOSSIP_REDUNDANT_KINDS = ("vote", "block_part", "tx", "evidence")

P2P_CHANNEL_BYTES = Counter(
    "tendermint_p2p_channel_bytes_total",
    "Frame bytes by p2p channel and direction (send/recv)",
    labelnames=("channel", "dir"),
)
GOSSIP_MSGS = Counter(
    "tendermint_gossip_msgs_total",
    "Gossip messages by wire kind and direction (send/recv)",
    labelnames=("kind", "dir"),
)
GOSSIP_REDUNDANT = Counter(
    "tendermint_gossip_redundant_total",
    "Duplicate gossip deliveries dedup'd after arrival, by kind",
    labelnames=("kind",),
)
GOSSIP_REDUNDANT_BYTES = Counter(
    "tendermint_gossip_redundant_bytes_total",
    "Payload bytes of duplicate gossip deliveries, by kind",
    labelnames=("kind",),
)

for _dir in ("send", "recv"):
    for _chan in GOSSIP_CHANNELS:
        P2P_CHANNEL_BYTES.labels(channel=_chan, dir=_dir).inc(0)
    for _kind in GOSSIP_KINDS:
        GOSSIP_MSGS.labels(kind=_kind, dir=_dir).inc(0)
for _kind in GOSSIP_REDUNDANT_KINDS:
    GOSSIP_REDUNDANT.labels(kind=_kind).inc(0)
    GOSSIP_REDUNDANT_BYTES.labels(kind=_kind).inc(0)

# -- WAN link chaos + scenario engine (p2p/transport.py, testing/) ------------
#
# `result` on the link-send counter is the fixed delivery vocabulary of
# the chaos layer: delivered (immediate), delayed (rode the delivery
# wheel), dup (extra copy scheduled), dropped, partitioned. No per-link
# labels — a WAN harness runs O(n^2) links and peer-pair series would
# be unbounded; `tools/scenario_run.py` reports are per-link instead.

LINK_SENDS = Counter(
    "tendermint_link_sends_total",
    "ChaosEndpoint sends by delivery outcome (delivered / delayed / "
    "dup / dropped / partitioned)",
    labelnames=("result",),
)
LINK_DELIVERY_DELAY = Histogram(
    "tendermint_link_delivery_delay_seconds",
    "Extra latency injected per delayed delivery (propagation delay + "
    "jitter + bandwidth serialization), as scheduled on the wheel",
    buckets=LATENCY_BUCKETS,
)
LINK_BANDWIDTH_WAIT = Histogram(
    "tendermint_link_bandwidth_wait_seconds",
    "Token-bucket serialization wait per bandwidth-capped send (the "
    "queueing component of the injected delay)",
    buckets=LATENCY_BUCKETS,
)
LINK_INFLIGHT = Gauge(
    "tendermint_link_inflight_deliveries",
    "Delayed deliveries pending on the shared delivery wheel (the "
    "thread-count regression signal: one thread serves all of these)",
)
SCENARIO_RUNS = Counter(
    "tendermint_scenario_runs_total",
    "Declarative scenarios executed by ScenarioRunner, by verdict",
    labelnames=("result",),
)
SCENARIO_SECONDS = Histogram(
    "tendermint_scenario_seconds",
    "Wall time per executed scenario (build + run + report)",
    buckets=LATENCY_BUCKETS,
)

for _result in (
    "delivered", "delayed", "dup", "dropped", "partitioned", "congested",
):
    LINK_SENDS.labels(result=_result).inc(0)
for _result in ("pass", "fail"):
    SCENARIO_RUNS.labels(result=_result).inc(0)

# -- evidence -----------------------------------------------------------------

EVIDENCE_POOL_DEPTH = Gauge(
    "tendermint_evidence_pool_depth",
    "Verified misbehavior proofs pending commitment into a block",
)
EVIDENCE_COMMITTED = Counter(
    "tendermint_evidence_committed_total",
    "Evidence retired from the pool by block commitment",
)
EVIDENCE_EXPIRED = Counter(
    "tendermint_evidence_expired_total",
    "Pending evidence pruned past the ConsensusParams max-age window",
)

# -- mempool ------------------------------------------------------------------
#
# `result` outcomes are fixed: ok / rejected (app said no) / duplicate
# (dup-cache hit) / bad_sig (signed-envelope verify failed) / flushed
# (operator flush invalidated an in-flight admission). Ingress `reason`
# mirrors the coalescer's flush triggers (window/size/barrier).

MEMPOOL_SIZE = Gauge("tendermint_mempool_size", "Pending txs in the mempool")
MEMPOOL_TXS = Counter(
    "tendermint_mempool_txs_total",
    "CheckTx outcomes (ok/rejected/duplicate/bad_sig/flushed)",
    labelnames=("result",),
)
MEMPOOL_ADMISSION_SECONDS = Histogram(
    "tendermint_mempool_admission_seconds",
    "CheckTx arrival to admission verdict (ingress queue + verify window "
    "+ app check); exemplar-linked to the admitted tx's trace id",
    buckets=LATENCY_BUCKETS,
)
MEMPOOL_INGRESS_WINDOW = Histogram(
    "tendermint_mempool_ingress_window_txs",
    "Txs merged per ingress verify window",
    buckets=SIZE_BUCKETS,
)
MEMPOOL_INGRESS_FLUSH = Counter(
    "tendermint_mempool_ingress_flush_total",
    "Ingress window flushes by trigger (window/size/barrier)",
    labelnames=("reason",),
)

for _reason in ("window", "size", "barrier"):
    MEMPOOL_INGRESS_FLUSH.labels(reason=_reason).inc(0)
for _result in ("ok", "rejected", "duplicate", "bad_sig", "flushed"):
    MEMPOOL_TXS.labels(result=_result).inc(0)

# -- consensus WAL ------------------------------------------------------------

WAL_FSYNC_SECONDS = Histogram(
    "tendermint_wal_fsync_seconds",
    "Consensus WAL write+fsync latency per record",
    buckets=LATENCY_BUCKETS,
)
WAL_WRITTEN_BYTES = Counter(
    "tendermint_wal_written_bytes_total", "Framed bytes appended to the consensus WAL"
)

# -- rpc ----------------------------------------------------------------------

RPC_REQUESTS = Counter(
    "tendermint_rpc_requests_total",
    "RPC calls served, by method and outcome",
    labelnames=("method", "result"),
)
RPC_PHASE_SECONDS = Histogram(
    "tendermint_rpc_phase_seconds",
    "A read's life inside the server, by phase (each a `rpc.<phase>` "
    "stage on the connection's thread): parse (from the request line "
    "read to the dispatch: headers, body, JSON or query), handle (the "
    "route function), encode (json.dumps of the answer), write (status "
    "line, headers and body, to the return of the socket write); and "
    "inside block's handle, load (the store's part rows and "
    "Block.decode) and render (the answer's dict). The wait between a "
    "request's bytes reaching the socket and its thread getting the "
    "interpreter to read them is before parse, and in no phase",
    labelnames=("method", "phase"),
    buckets=LATENCY_BUCKETS,
)
RPC_PHASE_CPU_SECONDS = Counter(
    "tendermint_rpc_phase_cpu_seconds_total",
    "CPU time of the connection's thread inside each phase of "
    "tendermint_rpc_phase_seconds: a phase's seconds less this is what "
    "the read waited, for the interpreter lock, a store or the socket",
    labelnames=("method", "phase"),
)
RPC_RESPONSE_BYTES = Counter(
    "tendermint_rpc_response_bytes_total",
    "Bytes of answer bodies written, by method",
    labelnames=("method",),
)


def bind_node_gauges(node) -> None:
    """Point the live-view gauges at a composed `node.Node`. Called from
    the node's start(); the callbacks read cheap in-memory state at
    scrape time only."""

    # GC pause timing rides along: a serving node always wants it, and
    # the hook is idempotent + process-lifetime cheap
    _process.install_gc_telemetry()

    P2P_PEERS.set_function(lambda: node.switch.n_peers() if node.switch else 0)
    P2P_SEND_RATE.set_function(lambda: node.switch.send_rate_total())
    P2P_RECV_RATE.set_function(lambda: node.switch.recv_rate_total())
    P2P_SEND_QUEUE.set_function(lambda: node.switch.send_queue_depth_total())
    P2P_SEND_QUEUE_MAX.set_function(lambda: node.switch.send_queue_depth_max())
    MEMPOOL_SIZE.set_function(lambda: node.mempool.size())
