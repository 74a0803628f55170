"""Persistent XLA compilation cache + compile telemetry.

A cold process compiles every kernel it touches (table build, verify
shapes, Merkle shapes — tens of seconds each); the persistent cache
serializes compiled executables to disk so every later process
deserializes instead. The cache directory is part of the cache key, so
it must not move between runs:

* where `JAX_COMPILATION_CACHE_DIR` is set, JAX itself caches there and
  this module sets no directory;
* otherwise the directory is `.jax_cache/` at the root of this checkout
  (derived from this file's location, git-ignored) — the same path for
  every process, whatever its CWD.

What is in an executable's key, beside the directory: its program. The
outer module's source locations are left out (JAX's default,
`jax_compilation_cache_include_metadata_in_key=False`), but a Mosaic
kernel's serialized body is an operand of its `tpu_custom_call`, and the
body names the file and line of each op's Python frames. So
`enable_persistent_cache()` keeps the callers' frames out of it
(`_CALL_FRAMES_IN_LOCATIONS`): the key of an executable that holds a
Pallas kernel reads the kernel's own file (`ops/ed25519_pallas.py`,
`ops/ed25519_ladder_pallas.py`) and no other. `tests/test_kernel_cache_key.py`
holds that.

Called from every entry point that compiles kernels (node CLI,
`chip_smoke.py`, the benchmark's node, graft entries), before anything
lowers. Never initializes a JAX backend: a parent that only
orchestrates children must stay off the chip. Opt out with
TENDERMINT_TPU_XLA_CACHE=off.
"""

from __future__ import annotations

import os
import pathlib

from tendermint_tpu.telemetry import metrics, process

CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_listening = False

# `jax_include_full_tracebacks_in_locations`: an op's location is its own
# line, not the ten frames that called it. With the frames in, a line
# added above a launch's caller in `services/` re-keyed every executable
# with a Pallas kernel (a 20 s compile each, 165-241 s a cold set-up).
# JAX reads it when it first lowers; set later it changes nothing.
_CALL_FRAMES_IN_LOCATIONS = False


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        metrics.XLA_CACHE_EVENTS.labels(event=outcome).inc()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        metrics.XLA_COMPILE_SECONDS.labels(
            fun=str(kw.get("fun_name", ""))
        ).observe(duration)
        # what tracing it left on the heap is not frozen yet
        process.mark_heap_unsettled()


def _listen() -> None:
    """Feed JAX's own compile / persistent-cache events into the metric
    catalog (once per process): compile seconds per jitted function,
    apart from any launch's run time, and cache hits vs misses — what
    lets an outside process see that a restart found its executables.
    Every executable built or loaded also marks the heap unsettled
    (`telemetry/process.py` `settle_heap`)."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def enable_persistent_cache() -> str | None:
    """Arm the on-disk executable cache. Idempotent; returns the cache
    dir in effect, or None when disabled (TENDERMINT_TPU_XLA_CACHE=off:
    such a process keys nothing, and its locations stay JAX's own) or
    when the process is held to the CPU (which lowers the same kernel
    bodies as the chip's process, and caches none)."""
    if os.environ.get("TENDERMINT_TPU_XLA_CACHE", "").lower() in (
        "off", "0", "disable", "false", "no",
    ):
        return None
    import jax

    jax.config.update(
        "jax_include_full_tracebacks_in_locations", _CALL_FRAMES_IN_LOCATIONS
    )
    _listen()
    # TPU executables only: XLA:CPU AOT results bake in host machine
    # features (loading them on a different host warns of SIGILL), and
    # CPU compiles of the shapes CPU runs use are cheap. Read from
    # config, not from the backend: asking the backend would claim the
    # chip for this process.
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    path = jax.config.jax_compilation_cache_dir
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # every kernel here is worth caching, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    metrics.XLA_CACHE_ENABLED.set(1)
    return path
