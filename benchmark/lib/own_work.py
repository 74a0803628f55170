"""Readers' view of the second clock (PR 38): time busy beside time
waited, where the work happens. Every `Stage` of the program reads its
thread's CPU clock beside the wall clock, so between the `/metrics`
pulls at the window's start and end one can tell what a block and a
read spent running, sleeping on a durability point, and waiting (for the
interpreter lock, mostly):

* fast-sync: `tendermint_fastsync_stage_cpu_seconds_total{stage}` beside
  `tendermint_fastsync_stage_seconds{stage}` (`fastsync_stages.py`);
* the disk: `tendermint_db_commit_seconds{db}` around exactly what
  `tendermint_db_commits_total{db}` counts, and
  `tendermint_db_commit_cpu_seconds_total{db}`;
* a read inside the server: `tendermint_rpc_phase_seconds{method,phase}`
  and `tendermint_rpc_phase_cpu_seconds_total{method,phase}`; the four
  top phases follow one another (`parse`, `handle`, `encode`, `write`),
  `block`'s `load` and `render` lie inside its `handle`; and what it
  wrote, `tendermint_rpc_response_bytes_total{method}`;
* who has the interpreter: `tendermint_process_cpu_seconds_total` and
  `tendermint_process_thread_cpu_seconds{thread}`.

A program without a series (the parent of PR 38, and every one before)
gives None, as every reader since PR 24 does.
"""

from __future__ import annotations

from benchmark.lib import fastsync_stages, rpc
from benchmark.lib.fastsync_stages import BLOCKS, SECONDS, SYNC_THREAD

STAGE_CPU = "tendermint_fastsync_stage_cpu_seconds_total"
COMMIT_SECONDS = "tendermint_db_commit_seconds_sum"
COMMIT_CPU = "tendermint_db_commit_cpu_seconds_total"
PHASE_SECONDS = "tendermint_rpc_phase_seconds_sum"
PHASE_COUNT = "tendermint_rpc_phase_seconds_count"
PHASE_CPU = "tendermint_rpc_phase_cpu_seconds_total"
RESPONSE_BYTES = "tendermint_rpc_response_bytes_total"
PROCESS_CPU = "tendermint_process_cpu_seconds_total"
THREAD_CPU = "tendermint_process_thread_cpu_seconds"
TABLE_BUILD = "tendermint_verify_table_build_seconds"

# what the sync thread ran: its stages but the idle tick
RUNNING = tuple(s for s in SYNC_THREAD if s != "starved")
# the stages that wait for nothing but the disk and the interpreter lock
# (`verify_wait` joins the device, `validate` calls it, `starved` sleeps)
DISK_AND_LOCK = ("part_set", "verify_submit", "store", "exec", "state_save")
# a read's phases that follow one another; `load` and `render` lie inside `handle`
TOP_PHASES = ("parse", "handle", "encode", "write")


def _rise(obs: dict, name: str, **labels) -> float:
    return rpc.rise(obs["metrics_start"], obs["metrics_end"], name, **labels)


def _has(obs: dict, *names: str) -> bool:
    return all(name in obs["metrics_end"] for name in names)


def _blocks(obs: dict, *names: str) -> float:
    """Blocks applied in the window; 0 where a series is missing."""
    return _rise(obs, BLOCKS) if _has(obs, *names) else 0.0


def _window_s(obs: dict) -> float:
    start, end = obs["window"]
    return end - start


# -- fast-sync -------------------------------------------------------------------


def cpu_ms_per_block(obs: dict, stages: tuple[str, ...]) -> float | None:
    """CPU time of the stages' thread inside them, a block."""
    blocks = _blocks(obs, STAGE_CPU)
    if blocks <= 0:
        return None
    return 1e3 * sum(_rise(obs, STAGE_CPU, stage=s) for s in stages) / blocks


def stage_ms_per_block(obs: dict, stage: str) -> float | None:
    """A stage's wall time a block; None where the program has no such
    stage (`index_rows` is PR 38's)."""
    known = {labels.get("stage") for labels, _ in obs["metrics_end"].get(SECONDS, [])}
    if stage not in known:
        return None
    return fastsync_stages.ms_per_block(obs, stage)


def disk_ms_per_block(obs: dict) -> float | None:
    """Wall time inside the stores' durable writes (every `db`), a block."""
    blocks = _blocks(obs, COMMIT_SECONDS)
    if blocks <= 0:
        return None
    return 1e3 * _rise(obs, COMMIT_SECONDS) / blocks


def lock_wait_parts(obs: dict) -> dict | None:
    """Seconds of the window: over `DISK_AND_LOCK` the stages' `wall` and
    `cpu`, the commits' `commit_wall` and `commit_cpu` (all of them lie
    inside `store` and `state_save`), and what is left,
    `lock_wait = wall - cpu - (commit_wall - commit_cpu)`: what the sync
    thread neither ran nor slept on a durability point. An upper bound on
    its waiting for the interpreter lock."""
    if not _has(obs, STAGE_CPU, COMMIT_SECONDS, COMMIT_CPU):
        return None
    parts = {
        "wall": sum(_rise(obs, SECONDS, stage=s) for s in DISK_AND_LOCK),
        "cpu": sum(_rise(obs, STAGE_CPU, stage=s) for s in DISK_AND_LOCK),
        "commit_wall": _rise(obs, COMMIT_SECONDS),
        "commit_cpu": _rise(obs, COMMIT_CPU),
    }
    parts["lock_wait"] = (
        parts["wall"] - parts["cpu"] - (parts["commit_wall"] - parts["commit_cpu"])
    )
    return parts


def lock_wait_ms_per_block(obs: dict) -> float | None:
    parts = lock_wait_parts(obs)
    blocks = _rise(obs, BLOCKS)
    if parts is None or blocks <= 0:
        return None
    return 1e3 * parts["lock_wait"] / blocks


# -- a read inside the server -------------------------------------------------------


def _phases(obs: dict, name: str, phases: tuple[str, ...], **labels) -> float:
    return sum(_rise(obs, name, phase=p, **labels) for p in phases)


def ms_per_read(obs: dict, method: str, phases: tuple[str, ...]) -> float | None:
    """The phases' wall time over the reads of `method` the server
    handled (the rise of `handle`'s count): milliseconds a read."""
    if not _has(obs, PHASE_SECONDS, PHASE_COUNT):
        return None
    reads = _rise(obs, PHASE_COUNT, method=method, phase="handle")
    if reads <= 0:
        return None
    return 1e3 * _phases(obs, PHASE_SECONDS, phases, method=method) / reads


def bytes_per_read(obs: dict, method: str) -> float | None:
    """The answer bodies written over the reads of `method` the server
    handled: what `load`, `render`, `encode` and `write` are the cost of."""
    if not _has(obs, RESPONSE_BYTES, PHASE_COUNT):
        return None
    reads = _rise(obs, PHASE_COUNT, method=method, phase="handle")
    if reads <= 0:
        return None
    return _rise(obs, RESPONSE_BYTES, method=method) / reads


def running_share(obs: dict) -> float | None:
    """Every method's top phases: CPU over wall, percent: the share of a
    read's life in the server in which its thread ran."""
    if not _has(obs, PHASE_SECONDS, PHASE_CPU):
        return None
    wall = _phases(obs, PHASE_SECONDS, TOP_PHASES)
    if wall <= 0:
        return None
    return 100.0 * _phases(obs, PHASE_CPU, TOP_PHASES) / wall


def mean_in_flight(obs: dict) -> float | None:
    """Every method's top phases' wall seconds over the window's: the
    mean number of reads inside the server at once."""
    if not _has(obs, PHASE_SECONDS):
        return None
    return _phases(obs, PHASE_SECONDS, TOP_PHASES) / _window_s(obs)


# -- who has the interpreter -----------------------------------------------------------


def process_cpu_share(obs: dict) -> float | None:
    """The process's CPU seconds over the window's, percent: 100 is one
    core, and more comes only from C code that let the lock go."""
    if not _has(obs, PROCESS_CPU):
        return None
    return 100.0 * _rise(obs, PROCESS_CPU) / _window_s(obs)


def thread_cpu_share(obs: dict, thread: str) -> float | None:
    """One thread class's CPU seconds over the window's, percent."""
    if not _has(obs, THREAD_CPU):
        return None
    return 100.0 * _rise(obs, THREAD_CPU, thread=thread) / _window_s(obs)


# -- a table build -------------------------------------------------------------------


def table_build_ms(obs: dict) -> float | None:
    """Mean of the table builds that ended in the window, every kind."""
    if not _has(obs, TABLE_BUILD + "_count"):
        return None
    builds = _rise(obs, TABLE_BUILD + "_count")
    if builds <= 0:
        return None
    return 1e3 * _rise(obs, TABLE_BUILD + "_sum") / builds
