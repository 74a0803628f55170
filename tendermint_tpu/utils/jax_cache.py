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

Called from every entry point that compiles kernels (node CLI, bench,
graft entries, tools). Never initializes a JAX backend: a parent that
only orchestrates children must stay off the chip. Opt out with
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
    dir in effect, or None when disabled (TENDERMINT_TPU_XLA_CACHE=off)
    or when the process is held to the CPU."""
    if os.environ.get("TENDERMINT_TPU_XLA_CACHE", "").lower() in (
        "off", "0", "disable", "false", "no",
    ):
        return None
    import jax

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
