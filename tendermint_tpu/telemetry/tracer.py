"""Lightweight span tracer for consensus/device-path timelines.

The in-process half of tracing: a bounded ring of completed spans
(name, wall-clock start/end, attributes) cheap enough to leave on in
production. Consensus records one span per round phase
(`consensus.propose` → `consensus.commit`, attributed with
height/round), device dispatch records verify/hash batches; the
`dump_telemetry` RPC serves the recent window so a stalled height can
be read as a timeline instead of reverse-engineered from logs.

Spans that carry a `trace` attribute (a `telemetry/tracectx.py`
trace id) are the distributed half: `tools/trace_timeline.py` merges
span logs from N nodes and stitches same-trace spans into one
cross-cluster timeline. Every span name recorded with a literal must be
registered in `telemetry/metrics.py`'s SPAN_CATALOG (collection-time
lint, same discipline as the metric catalog).

`TRACER.stage(name)` is the stopwatch for stretches too frequent for a
span each (fast-sync's per-block stages, an RPC read's phases, a store's
commit): two clocks, the wall's (`perf_counter_ns`) and the calling
thread's CPU clock (`thread_time_ns`), for the caller's histogram and
counter, shown in the profiler's host plane while a `jax.profiler`
session runs, and nothing in the ring. The CPU clock is a system call
(6 us on the TPU host, 24 us beside a node's threads, where the wall
clock is 0.1 us), so stage boundaries of one thread that lie within
`CPU_SHARE_NS` of each other share one reading of it.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field


def _snapshot_attrs(attrs: dict) -> dict:
    """Copy `attrs` tolerating concurrent writers: a traced path may
    hand its attrs dict to another thread (callers add attrs mid-span),
    and a resize during the copy raises RuntimeError — retry, and never
    let the snapshot kill the traced path."""
    for _ in range(4):
        try:
            return dict(attrs)
        except RuntimeError:
            continue
    return {}


@dataclass
class Span:
    name: str
    start: float  # time.time() epoch seconds
    end: float
    attrs: dict = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.end - self.start

    def to_dict(self) -> dict:
        # attrs are COPIED: a reader serializing the dict must never
        # observe (or publish) a later writer's mutation
        return {
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_s": self.duration,
            **({"attrs": dict(self.attrs)} if self.attrs else {}),
        }


# Stage boundaries of one thread that lie this close together share one
# reading of its CPU clock: one stage's exit and the next one's enter, a
# parent's enter and its first child's, a child's exit and its parent's.
# Between two such boundaries lie a sink's bookkeeping and the next
# stage's making, 10-40 us here and 15-55 on the TPU host. The thread ran
# for at most this long between them, and that is what a shared reading
# can move into the later stage (whose CPU may read over its wall by as
# much) or out of a parent's end; a thread that lost the interpreter in
# between comes back later than this and reads the clock anew.
CPU_SHARE_NS = 100_000

_reading = threading.local()  # .at: the thread's last reading, (perf_counter_ns, thread_time_ns)


def _thread_cpu(now_ns: int, entered_with: tuple | None = None) -> tuple:
    """The calling thread's reading of its CPU clock for a boundary at
    `now_ns` of the wall clock: its last one if that is at most
    `CPU_SHARE_NS` old and not `entered_with`, else a new one."""
    at = getattr(_reading, "at", None)
    if at is not None and at is not entered_with and now_ns - at[0] <= CPU_SHARE_NS:
        return at
    cpu_ns = time.thread_time_ns()
    # stamped once the call is back: what it cost is not the reading's age
    at = _reading.at = (time.perf_counter_ns(), cpu_ns)
    return at


class Stage:
    """One timed stretch of host work: `with TRACER.stage(name) as st`
    leaves the duration in `st.seconds` and, beside it, the CPU time of
    the thread that ran it in `st.cpu_seconds`; when given, it calls
    `sink(seconds, cpu_seconds)` on exit (errors included). Wall less
    CPU is what the thread neither ran in Python nor in C with the
    interpreter lock let go: it slept on a disk, a socket or the device,
    or waited for the lock. Enter and exit on one thread: the CPU clock
    is that thread's own, and stages that meet at a boundary read it
    once (`CPU_SHARE_NS`), so back-to-back stages cost one reading each
    and their CPU adds up to the thread's with nothing lost between.

    While open it holds a `jax.profiler.TraceAnnotation(name)`, so the
    stretch sits in the profiler's host plane on the profiler's own
    clock, beside the device's operations; with no profiler session the
    annotation is a no-op. `telemetry/` imports no JAX: the annotation
    is taken from `sys.modules`, and a process that never loaded JAX
    (it has no device to trace) times the stage without one."""

    __slots__ = ("name", "seconds", "cpu_seconds", "_sink", "_t0", "_cpu0", "_annotation")

    def __init__(self, name: str, sink=None) -> None:
        self.name = name
        self.seconds = 0.0
        self.cpu_seconds = 0.0
        self._sink = sink

    def __enter__(self) -> "Stage":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            self._annotation = None
        else:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        self._cpu0 = _thread_cpu(self._t0)
        return self

    def __exit__(self, *exc) -> None:
        # a reading of its own, or a child's exit: never the one it entered
        # with, so a stage shorter than the share still reads what it ran;
        # inside the wall's reading, so a reading's own cost is wall
        cpu1 = _thread_cpu(time.perf_counter_ns(), self._cpu0)
        self.cpu_seconds = (cpu1[1] - self._cpu0[1]) * 1e-9
        self.seconds = (time.perf_counter_ns() - self._t0) * 1e-9
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._sink is not None:
            self._sink(self.seconds, self.cpu_seconds)


class Tracer:
    """Bounded ring of completed spans; thread-safe. Optional sink
    callbacks observe every completed span (the JSONL span log persists
    them across restarts — `telemetry/spanlog.py`); multiple sinks are
    supported so multi-node-in-process harnesses can keep one span log
    per node. Sink errors are swallowed: recording must never fail the
    traced path."""

    def __init__(self, capacity: int = 1024) -> None:
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(maxlen=capacity)
        self._sinks: tuple = ()

    def add_sink(self, fn) -> None:
        """Attach `fn(span)` as an additional completion sink."""
        with self._lock:
            if fn not in self._sinks:
                self._sinks = self._sinks + (fn,)

    def remove_sink(self, fn) -> None:
        """Detach one sink; other sinks (a successor node's span log)
        stay installed. Equality, not identity: bound methods are a new
        object per attribute access, so `log.append` must still match."""
        with self._lock:
            self._sinks = tuple(s for s in self._sinks if s != fn)

    def add(self, name: str, start: float, end: float, **attrs) -> Span:
        span = Span(name, start, end, attrs)
        with self._lock:
            self._spans.append(span)
            sinks = self._sinks
        for sink in sinks:
            try:
                sink(span)
            except Exception:
                pass
        return span

    @contextmanager
    def span(self, name: str, **attrs):
        """`with TRACER.span("mempool.admission", n=512): ...` — the span
        recorded on exit, errors included (attr `error` is set). The
        attrs are SNAPSHOT at completion: the yielded dict may keep
        being mutated (even from another thread) without racing the
        recorded span or its readers."""
        t0 = time.time()
        try:
            yield attrs  # callers may add attrs mid-span
        except BaseException as e:
            attrs["error"] = f"{type(e).__name__}"
            raise
        finally:
            self.add(name, t0, time.time(), **_snapshot_attrs(attrs))

    def stage(self, name: str, sink=None) -> Stage:
        """A `Stage` stopwatch (see there). Records no span: the caller
        sums stages into the one span it records per unit of work."""
        return Stage(name, sink)

    def recent(self, n: int | None = None, prefix: str = "") -> list[dict]:
        with self._lock:
            spans = list(self._spans)
        if prefix:
            spans = [s for s in spans if s.name.startswith(prefix)]
        if n is not None:
            spans = spans[-n:]
        return [s.to_dict() for s in spans]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


# Process-wide tracer, sized for ~2 minutes of 4-phase consensus at
# test speed plus device-path spans.
TRACER = Tracer(capacity=1024)
