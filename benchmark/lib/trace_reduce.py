"""From a profiler trace to device busy time, kernel time and idle gaps.

`extract` turns the profiler's `.xplane.pb` into plain lists (planes, their
lines, events as [name, start_ns, duration_ns], times counted from the
start of the trace); `reduce` works on that form alone, so it can be
checked against the small recorded trace kept beside this file
(`recorded_trace.json`).

On a TPU each chip is one plane, `/device:TPU:<i>`. Its line `XLA Ops`
holds one event per operation the core ran, and its line `XLA Modules`
one event per executable run, named after the jitted function
(`jit_verify_tables_kernel(...)`). Busy time is the union of the `XLA Ops`
intervals, averaged over the device planes.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(xplane_path: str, device_only: bool = True) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = []
    names = []
    for plane in data.planes:
        names.append(plane.name)
        if device_only and not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "plane_names": names}


def short_name(op: str) -> str:
    """An operation's own name out of the HLO line the trace carries
    (`%fusion.12 = s32[...] fusion(...)` -> `fusion.12`)."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _busy_lines(plane: dict) -> list[dict]:
    return [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]


def reduce(trace: dict, window_s: float) -> dict | None:
    """`trace` as `extract` gives it, `window_s` the length of the traced
    stretch by the host's clock. None when no device plane holds an
    operation (a CPU run, or a trace in which the device did nothing)."""
    devices = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]
    busy_by_plane: list[float] = []
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    module_runs: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for plane in devices:
        spans = [
            (start, start + dur)
            for line in _busy_lines(plane)
            for _name, start, dur in line["events"]
            if dur > 0
        ]
        merged = _union(spans)
        busy_by_plane.append(sum(hi - lo for lo, hi in merged) / 1e9)
        for line in _busy_lines(plane):
            for name, _start, dur in line["events"]:
                name = short_name(name)
                ops[name] = ops.get(name, 0.0) + dur / 1e9
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                for name, _start, dur in line["events"]:
                    modules[name] = modules.get(name, 0.0) + dur / 1e9
                    module_runs[name] = module_runs.get(name, 0) + 1
        if plane is devices[0]:
            edge = 0.0
            for lo, hi in merged:
                if lo > edge:
                    gaps.append((edge / 1e9, (lo - edge) / 1e9))
                edge = max(edge, hi)
            if window_s * 1e9 > edge:
                gaps.append((edge / 1e9, window_s - edge / 1e9))
    if not busy_by_plane or not any(busy_by_plane):
        return None
    n = len(devices)
    return {
        "window_s": window_s,
        "busy_s": sum(busy_by_plane) / n,
        "chips": n,
        # per chip, so that a four-chip trace reads like a one-chip one
        "ops": {k: v / n for k, v in ops.items()},
        "modules": {k: v / n for k, v in modules.items()},
        "module_runs": {k: v / n for k, v in module_runs.items()},
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def module_seconds(reduced: dict, needle: str) -> float:
    """Device seconds (per chip) of the executables whose name holds `needle`."""
    return sum(v for k, v in reduced["modules"].items() if needle in k)


def top_ops(reduced: dict, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(reduced: dict, trace_wall0: float, launches: list[dict], n: int = 10) -> list[list]:
    """The longest idle gaps, each named after the launch-ledger stage
    whose host-clock interval covers most of it (`prep`, `in_flight`,
    `finalize`), or `none` (download, decode, apply: no launch open)."""
    stages: list[tuple[float, float, str]] = []
    for rec in launches:
        end = float(rec.get("t", 0.0))
        for stage, key in (("finalize", "finalize_s"), ("in_flight", "in_flight_s"), ("prep", "host_prep_s")):
            dur = float(rec.get(key) or 0.0)
            stages.append((end - dur, end, f"{rec.get('kind', 'verify')}.{stage}"))
            end -= dur
    totals: dict[str, float] = {}
    for start, dur in reduced["gaps"]:
        lo, hi = trace_wall0 + start, trace_wall0 + start + dur
        cover: dict[str, float] = {}
        for s_lo, s_hi, label in stages:
            part = min(hi, s_hi) - max(lo, s_lo)
            if part > 0:
                cover[label] = cover.get(label, 0.0) + part
        label = max(cover, key=cover.get) if cover and max(cover.values()) > dur / 2 else "none"
        totals[label] = totals.get(label, 0.0) + dur
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]
