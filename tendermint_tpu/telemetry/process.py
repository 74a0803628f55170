"""Process-level resource telemetry: RSS, fds, threads, GC pauses.

The whole-node observability floor under the contention observatory
(`telemetry/profiler.py`): before attributing time between subsystems,
an operator needs to know whether the *process* is healthy — resident
set growth, fd leaks, thread-count creep, and the stop-the-world GC
pauses that show up in consensus latency tails without any lock or
queue being at fault.

Exported through the catalog (`telemetry/metrics.py`):

* ``tendermint_process_rss_bytes`` / ``_open_fds`` / ``_threads`` —
  callback gauges read at scrape time only (`/proc/self` on Linux,
  `resource.getrusage` fallback elsewhere); idle cost is zero.
* ``tendermint_process_cpu_seconds_total`` and
  ``tendermint_process_thread_cpu_seconds{thread}`` — who has the
  interpreter: the process's CPU clock and every live thread's, summed
  by the contention profiler's thread classes; read at scrape time
  only, nothing on any hot path (`cpu_seconds`, `thread_cpu_seconds`).
* ``tendermint_process_gc_pause_seconds{gen}`` +
  ``tendermint_process_gc_collections_total{gen}`` — a `gc.callbacks`
  hook stamps `perf_counter` across each collection. CPython invokes
  the callbacks on whichever thread triggered the collection, start
  and stop paired on that thread, and collections never overlap, so a
  single module-global stamp is race-free. Installed idempotently by
  ``install_gc_telemetry()`` (node start / tests), ~100 ns per
  collection when installed. The hook never waits for a registry
  lock: a collection can start on a thread that is inside these very
  families' ``samples()`` (a scrape allocates under the family lock),
  and waiting there is waiting for oneself, which left ``/metrics`` and
  ``dump_telemetry`` unanswered for the rest of the node's life. What
  it cannot count at once it keeps and counts at the next collection.
* ``tendermint_process_gc_frozen_objects`` +
  ``tendermint_process_heap_settles_total`` — the collector's policy
  (`settle_heap`): what a process keeps for its whole life, JAX's traced
  programs before all, is frozen once its executables are met, so a
  collection of the oldest generation walks the blocks in flight and not
  half a million jaxpr nodes. Both are read at scrape time only.
"""

from __future__ import annotations

import gc
import os
import threading
import time
import weakref
from collections import deque

_PAGE_SIZE = 4096
try:
    _PAGE_SIZE = os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):  # pragma: no cover - exotic libc
    pass


def rss_bytes() -> float:
    """Resident set size. `/proc/self/statm` field 2 on Linux; the
    `ru_maxrss` high-water mark (kB) as the best-effort fallback."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return float(int(f.read().split()[1]) * _PAGE_SIZE)
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        return float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024)
    except Exception:
        return 0.0


def open_fds() -> float:
    """Open file descriptors (sockets, WAL handles, device fds)."""
    try:
        return float(len(os.listdir("/proc/self/fd")))
    except OSError:
        return 0.0


def thread_count() -> float:
    return float(threading.active_count())


# -- who has the interpreter ----------------------------------------------------

_cpu_lock = threading.Lock()
# live threads seen at the last scrape: thread -> (class, its CPU clock then)
_cpu_seen: "dict[threading.Thread, tuple[str, float]]" = {}
# class -> the last readings of its threads that have exited since
_cpu_exited: dict[str, float] = {}
# threads that read their own clock a last time (`retire_thread`): counted
_cpu_retired: "weakref.WeakSet[threading.Thread]" = weakref.WeakSet()


def cpu_seconds() -> dict:
    """The process's CPU clock: every thread of it, the interpreter's
    and the libraries' own (XLA's, the profiler's)."""
    return {(): time.process_time()}


def thread_cpu_seconds() -> dict:
    """CPU seconds of the interpreter's threads by class
    (`profiler.classify_thread` of the thread's NAME: a thread keeps the
    class it was first seen under, so no class's sum ever falls), every
    class of the vocabulary present. A thread that has exited keeps the
    last value read for it. What that loses: the CPU a thread took
    between its last scrape and its exit, and all of a thread that
    lived between two scrapes, unless it reads its own clock as it
    leaves (`retire_thread`: the RPC server's connection threads and
    the tx index's merger do);
    `tendermint_process_cpu_seconds_total` holds those, and the threads
    no `threading.enumerate()` lists (a library's own), in no class."""
    from tendermint_tpu.telemetry import profiler as _profiler

    with _cpu_lock:
        for thread in threading.enumerate():
            cpu = _profiler._cpu_clock(thread)
            if cpu is None or thread in _cpu_retired:
                continue
            seen = _cpu_seen.get(thread)
            sub = seen[0] if seen else _profiler.classify_thread(thread.name)
            _cpu_seen[thread] = (sub, cpu)
        for thread in [t for t in _cpu_seen if not t.is_alive()]:
            sub, cpu = _cpu_seen.pop(thread)
            _cpu_exited[sub] = _cpu_exited.get(sub, 0.0) + cpu
        totals = {sub: _cpu_exited.get(sub, 0.0) for sub in _profiler.SUBSYSTEMS}
        for sub, cpu in _cpu_seen.values():
            totals[sub] += cpu
    return {(sub,): total for sub, total in totals.items()}


def retire_thread() -> None:
    """The calling thread's CPU clock read a last time, by a thread about
    to exit whose kind comes and goes between scrapes: an RPC connection's
    thread lives as long as its connection (one request, for a client
    that keeps none open), the tx index's merger as long as merges are
    due. Its whole life is then in its class's sum, and not only what a
    scrape happened to see of it."""
    from tendermint_tpu.telemetry import profiler as _profiler

    thread = threading.current_thread()
    cpu = _profiler._cpu_clock(thread)
    if cpu is None:
        return
    with _cpu_lock:
        seen = _cpu_seen.pop(thread, None)
        sub = seen[0] if seen else _profiler.classify_thread(thread.name)
        _cpu_exited[sub] = _cpu_exited.get(sub, 0.0) + cpu
        _cpu_retired.add(thread)


# -- GC pause timing ----------------------------------------------------------

_installed = False
_install_lock = threading.Lock()
_gc_started_at: float | None = None
# collections seen but not yet in the registry (its lock was held)
_uncounted_gens: "deque[str]" = deque()
_uncounted_pauses: "deque[tuple[str, float]]" = deque()
_GENERATIONS = ("0", "1", "2")


def _gc_callback(phase: str, info: dict) -> None:
    global _gc_started_at, _heap_unsettled
    if phase == "start":
        _gc_started_at = time.perf_counter()
        return
    started = _gc_started_at
    _gc_started_at = None
    gen = str(info.get("generation", "?"))
    if gen not in _GENERATIONS:
        return
    _uncounted_gens.append(gen)
    if started is not None:
        _uncounted_pauses.append((gen, time.perf_counter() - started))
    if gen == "2" and _heap_unsettled and _settles:
        # an executable met since the last settle left its jaxprs on the
        # heap, and this collection has just paid for walking them
        _heap_unsettled = False
        _freeze()
    _count_what_can_be()


def _count_what_can_be() -> None:
    """Move the kept collections into the two families without ever
    waiting: `labels()` takes the family lock too, so the pre-seeded
    children are read straight from the map."""
    from tendermint_tpu.telemetry import metrics as _m

    collections = _m.PROCESS_GC_COLLECTIONS._children
    while _uncounted_gens and collections[(_uncounted_gens[0],)].try_inc():
        _uncounted_gens.popleft()
    pauses = _m.PROCESS_GC_PAUSE._children
    while _uncounted_pauses:
        gen, seconds = _uncounted_pauses[0]
        if not pauses[(gen,)].try_observe(seconds):
            break
        _uncounted_pauses.popleft()


def install_gc_telemetry() -> bool:
    """Idempotently hook `gc.callbacks`; returns True when the hook is
    (now) installed. Never uninstalled — the hook is process-lifetime
    cheap and a second install is a no-op."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        gc.callbacks.append(_gc_callback)
        _installed = True
        return True


# -- the collector's policy: a static heap is walked once ------------------------

# True until the first settle, and again once an executable has been met
# (`utils/jax_cache.py` hears every one built or loaded): what tracing
# left on the heap is not frozen yet
_heap_unsettled = True
_settles = 0
_settle_lock = threading.Lock()
# The young generation's threshold once the heap is frozen (CPython's own
# is 700). With the jaxprs frozen the survivors of a full collection are
# the blocks in flight alone, CPython's quarter rule is met at once, and
# at 700 a catching-up node at 1,000 validators (3,000 containers a
# block) ran 4,800 young, 440 middle and 33 full collections in 30 s:
# 5.2-5.7% of the window (12.1% before the freeze), the full ones 0.03-
# 0.09 s each. At 50,000, about a pool batch's worth of votes, most of a
# block's containers die by reference count before any collection looks
# at them: 40 young, 4 middle and no full collection in 30 s, 2.0-2.2%
# of the window, the longest pause 0.056 s (my chip runs, PR 44:
# `fastsync-1k.sparse`, same seeds).
YOUNG_GENERATION_THRESHOLD = 50_000


def mark_heap_unsettled() -> None:
    global _heap_unsettled
    _heap_unsettled = True


def _freeze() -> None:
    global _settles
    gc.freeze()
    _settles += 1


def settle_heap() -> bool:
    """Freeze what the process holds now, if anything was traced since
    the last settle; True when it did. JAX keeps every traced program for
    the life of the process (one `verify_commits` leaves 455,000 tracked
    objects: `JaxprEqn`, `SourceInfo`, `Var`, their lists and tuples), and
    CPython walks all of them at each collection of the oldest generation:
    0.3-0.4 s with every thread of the node stopped, eight times in 30 s
    of catch-up at 1,000 validators. One full collection first, so that
    no cyclic garbage is frozen in, then `gc.freeze()`: a frozen object is
    still freed when its last reference goes, it is only never walked
    again. What that costs: a reference cycle made of objects that were
    alive at a settle is never collected, so a settle happens once a
    process and once more for each executable met later (`Node.start`
    calls this; after a later compile the collector's own hook freezes at
    the end of its next full collection, which has just paid for the
    walk), never per block, per peer or per validator-set change. RSS
    therefore keeps what was frozen and later fell into a cycle: for a
    node, the reactors of a peer that left, bounded by the settles.
    A second call with nothing traced since is a no-op: tests start
    hundreds of nodes in one process.

    With the static heap gone from the walk, the young generation is
    sized to `YOUNG_GENERATION_THRESHOLD` (the other two thresholds stay
    the interpreter's). What can wait: a reference cycle that dies young
    is found once 50,000 more containers have been allocated than freed
    (700 before), one that had survived a young collection at the middle
    generation's turn, ten young collections on; so in the worst case,
    garbage that is all cycles, half a million containers and what they
    alone hold stand uncollected, a hundred megabytes at a vote's size.
    What dies by reference count is freed at once as before, and a
    block, its parts and its votes hold no cycle: the node's RSS over a
    window of catch-up reads within 0.3% of what it read before."""
    global _heap_unsettled
    with _settle_lock:
        if not _heap_unsettled:
            return False
        # the flag first: an executable met from here on is frozen next
        # time, and the hook leaves this collection to its caller
        _heap_unsettled = False
        gc.collect()
        _freeze()
        gc.set_threshold(YOUNG_GENERATION_THRESHOLD, *gc.get_threshold()[1:])
        return True


def heap_settles() -> dict:
    return {(): float(_settles)}


def frozen_objects() -> float:
    """Objects the collector no longer walks (`gc.get_freeze_count()`
    walks their list: scrape time only)."""
    return float(gc.get_freeze_count())
