"""BatchVerifier: accumulate→flush ed25519 verification service.

The reference verifies one signature at a time inside its hot loops
(`types/vote_set.go:177`, `types/validator_set.go:253`). Here every
consumer — VoteSet, ValidatorSet.verify_commit, fast-sync, light client —
talks to a `BatchVerifier`:

* `verify_batch(triples)` — synchronous batch verdicts (the call the
  types layer already targets);
* `add(pk, msg, sig)` / `flush()` — optimistic accumulation across
  call sites, flushed as one device batch (SURVEY.md §7 hard part 3:
  per-item verdict masks preserve per-vote error attribution).

Backends: `HostBatchVerifier` (sequential host library — the CPU
baseline and the no-TPU test fake) and `DeviceBatchVerifier` (the
batched curve kernel in `ops.ed25519_kernel`, padded to power-of-two
buckets so recompiles stay bounded).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Sequence

import numpy as np

from tendermint_tpu.telemetry import TRACER
from tendermint_tpu.telemetry import launchlog as _launchlog
from tendermint_tpu.telemetry import metrics as _metrics
from tendermint_tpu.utils.log import kv, logger

Triple = tuple[bytes, bytes, bytes]  # (pubkey32, message, signature64)


def _observe_verify(
    backend: str, n: int, seconds: float, kind: str = "verify"
) -> None:
    """One verify call's worth of hot-path telemetry. Each executing
    backend reports itself, so a resilient host fallback shows up under
    backend="host" while the failed device attempt stays attributed to
    the dispatch-failure counters. Also closes/annotates the ambient
    LaunchLedger record (telemetry/launchlog.py) — the device
    observatory's one-record-per-launch seam."""
    _metrics.VERIFY_BATCH_SIZE.labels(backend=backend).observe(n)
    _metrics.VERIFY_SECONDS.labels(backend=backend).observe(seconds)
    _launchlog.observe(kind, backend, n, seconds)


class BatchVerifier:
    """Interface + shared accumulate/flush bookkeeping."""

    # Every in-tree verifier tolerates the `consumer=` tag on its async
    # surface (the coalescer uses it for fairness + wait telemetry;
    # plain backends ignore it). Call sites gate on this attribute so
    # minimal test fakes without the kwarg keep working
    # (`services/batcher.py::consumer_kwargs`).
    accepts_consumer = True

    def verify_batch(self, triples: Sequence[Triple]) -> np.ndarray:
        raise NotImplementedError

    def __init__(self) -> None:
        self._pending: list[Triple] = []

    def add(self, pubkey: bytes, msg: bytes, sig: bytes) -> int:
        """Queue a triple; returns its index into the next flush()'s mask."""
        self._pending.append((pubkey, msg, sig))
        return len(self._pending) - 1

    def pending(self) -> int:
        return len(self._pending)

    def flush(self) -> np.ndarray:
        """Verify everything queued since the last flush; per-item verdicts."""
        triples, self._pending = self._pending, []
        if not triples:
            return np.zeros(0, dtype=bool)
        return self.verify_batch(triples)

    def verify_one(self, pubkey: bytes, msg: bytes, sig: bytes) -> bool:
        return bool(self.verify_batch([(pubkey, msg, sig)])[0])

    # -- async seam (services/dispatch.py) ---------------------------------
    #
    # Backends split a verify into `launch` (host prep + device kernel
    # dispatch — cheap, the device computes in the background) and
    # `finalize` (materialize + mask — where np.asarray blocks). The
    # base implementation has no device half, so launch does the whole
    # verify — still useful: run on a DispatchQueue worker it overlaps
    # with the submitter's host work.

    def launch_verify_batch(self, triples: Sequence[Triple]):
        return self.verify_batch(triples)

    def finalize_verify_batch(self, launched) -> np.ndarray:
        return launched

    def verify_batch_async(
        self, triples: Sequence[Triple], queue=None, consumer: str = "default"
    ):
        """Submit a batch verify through a `DispatchQueue`; returns a
        `VerifyHandle` whose `.result()` yields the same per-item
        verdict mask `verify_batch` would. Device arrays stay
        un-materialized until the join. `consumer` only matters to the
        coalescing wrapper; plain backends ignore it."""
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(
            lambda: self.launch_verify_batch(triples),
            self.finalize_verify_batch,
            kind="verify",
        )


class HostBatchVerifier(BatchVerifier):
    """Sequential host-library backend (CPU baseline / TPU-free tests)."""

    def verify_batch(self, triples: Sequence[Triple]) -> np.ndarray:
        from tendermint_tpu.crypto.keys import PUBKEY_LEN, PubKey

        t0 = time.perf_counter()
        out = np.zeros(len(triples), dtype=bool)
        for i, (pk, msg, sig) in enumerate(triples):
            if len(pk) != PUBKEY_LEN:
                continue
            out[i] = PubKey(pk).verify(msg, sig)
        _observe_verify("host", len(triples), time.perf_counter() - t0)
        return out


# Below this many REAL lanes the host library answers: every device
# launch has a fixed cost while a host verify is ~60 us, so single votes
# and small commits must never wait on a kernel launch (the consensus hot
# path verifies one gossiped vote at a time). For a commit window the
# count is k * n as handed in, never of the padded launch shape
# (`commit_launch_shape`): at 100 validators windows of K <= 5 stay on the
# host library; at 1,000 every window, K = 1 too, is a device launch (the cell
# `fastsync-1k.sparse`). Not measured on v5e (ROADMAP Queue 1 item 5).
DEVICE_MIN_BATCH = int(os.environ.get("TENDERMINT_TPU_MIN_DEVICE_BATCH", "512"))

# What a pad column of a commit window's launch carries, and what stands
# in for a malformed key: the identity point's encoding. It decompresses
# cleanly, so the packed table build stays sound; a pad column is absent
# in every commit and a malformed key's lanes are masked, so neither
# ever reports True.
PLACEHOLDER_KEY = b"\x01" + b"\x00" * 31


def commit_launch_shape(k: int, n: int) -> tuple[int, list[tuple[int, int]]]:
    """The one shape rule of a commit window on the fused path: `k`
    commits over `n` validators (per chip: the sharded verifier passes
    n // ndev) -> (n_launch, [(real, k_launch), ...]), one pair per
    kernel launch, `real` of the window's commits in a stack of
    `k_launch`.

    N rounds up to the fused kernel's 128-validator tile. K in 2..16
    rounds up to 16 (fast-sync's `VERIFY_WINDOW`), above that to 32,
    then 64 (`MAX_FUSED_STACK`), then chunks of 64 whose last is rounded
    the same way. So a validator set costs one executable for every
    fast-sync window size and three for a walk of any length, where
    every K off the tile was an executable of its own (12-18 s to load,
    50-60 s to compile, met as a stall). K = 1 is apart and keeps its
    stack of one on the materialized chain: it is consensus' own
    `verify_commit`, latency-bound, and a 16-stack of 1,024 validators
    is 16,384 lanes where it needs 1,024 (PERF.md section 6). It shares
    the padded table with the stacks: 1,000 validators launch both at 1,024
    columns (the cell `fastsync-1k.sparse`: `verify.single_commit_launch_share`).
    """
    from tendermint_tpu.ops.ed25519_tables import MAX_FUSED_STACK, V_TILE

    n_launch = -(-n // V_TILE) * V_TILE
    if k == 1:
        return n_launch, [(1, 1)]
    chunks = []
    for lo in range(0, k, MAX_FUSED_STACK):
        real = min(MAX_FUSED_STACK, k - lo)
        stack = 16
        while stack < real:
            stack *= 2
        chunks.append((real, stack))
    return n_launch, chunks


def _window_shape(k: int, n: int, fused: bool, ndev: int = 1):
    """(n_launch, chunks) of a window as a verifier launches it: the
    window's own shape off the fused path, else `commit_launch_shape`
    asked about one chip's `n // ndev` validators, its tile times the
    chips."""
    if not fused:
        return n, [(k, k)]
    shard_launch, chunks = commit_launch_shape(k, n // ndev)
    return shard_launch * ndev, chunks


def _pad_lanes(a: np.ndarray, real: int, n: int, k_launch: int, n_launch: int):
    """Commit-major lanes of `real` commits over `n` validators, set into
    zeros at the launch shape: pad commits and pad columns are written
    as one zero array, never walked lane by lane."""
    if (real, n) == (k_launch, n_launch):
        return a
    out = np.zeros((k_launch, n_launch) + a.shape[1:], dtype=a.dtype)
    out[:real, :n] = a.reshape((real, n) + a.shape[1:])
    return out.reshape((k_launch * n_launch,) + a.shape[1:])


def _window_chunks(pubkeys, commits, n_launch: int, chunks):
    """Host prep of each launch of a window: yields (real, k_launch,
    s, h, r, precheck), the lane arrays at the launch shape and the
    precheck as the (real, n) grid of the real lanes. The launch
    ledger's `rows_padded` and `k_launch` are counted when the caller
    comes back for the next chunk, so a launch that raised (a shard
    fault retried onto survivors) is not counted twice."""
    from tendermint_tpu.ops.ed25519_tables import prepare_commit_lanes

    n = len(pubkeys)
    lo = 0
    for real, k_launch in chunks:
        s, h, r, precheck = prepare_commit_lanes(pubkeys, commits[lo : lo + real])
        lo += real
        s, h, r = (_pad_lanes(a, real, n, k_launch, n_launch) for a in (s, h, r))
        yield real, k_launch, s, h, r, precheck.reshape(real, n)
        _launchlog.annotate(
            _additive=True,
            rows_padded=k_launch * n_launch - real * n,
            k_launch=k_launch,
        )


class DeviceBatchVerifier(BatchVerifier):
    """TPU-batched backend over `ops.ed25519_kernel.batch_verify`.

    Batches are padded to power-of-two buckets (min 8) inside
    batch_verify; compiled executables persist in the jit cache per
    bucket size. Batches smaller than DEVICE_MIN_BATCH short-circuit to
    the host library (launch overhead dominates there).
    """

    def __init__(self, min_device_batch: int | None = None) -> None:
        super().__init__()
        self._host = HostBatchVerifier()
        self._min_batch = (
            DEVICE_MIN_BATCH if min_device_batch is None else min_device_batch
        )

    def verify_batch(self, triples: Sequence[Triple]) -> np.ndarray:
        return self.finalize_verify_batch(self.launch_verify_batch(triples))

    def launch_verify_batch(self, triples: Sequence[Triple]):
        """Host prep + device kernel dispatch; the verdict array stays
        on device until `finalize_verify_batch` (sub-min batches answer
        on host immediately — a single vote must never wait in a
        pipeline behind a kernel launch)."""
        if not triples:
            return ("host", np.zeros(0, dtype=bool))
        if len(triples) < self._min_batch:
            return ("host", self._host.verify_batch(triples))
        from tendermint_tpu.ops.ed25519_kernel import (
            bucket_size,
            launch_batch_verify,
        )

        pubs, msgs, sigs = zip(*triples)
        t0 = time.perf_counter()
        launched = launch_batch_verify(list(pubs), list(msgs), list(sigs))
        # occupancy/transfer attribution: launch_batch_verify pads to
        # the power-of-two bucket and ships 4 (size, 32) u8 arrays
        size = bucket_size(len(triples))
        _launchlog.annotate(_additive=True, rows_padded=size - len(triples))
        _launchlog.add_transfer(4 * size * 32)
        return ("device", launched, t0)

    def finalize_verify_batch(self, launched) -> np.ndarray:
        if launched[0] == "host":
            return launched[1]
        from tendermint_tpu.ops.ed25519_kernel import materialize_batch_verify

        _tag, payload, t0 = launched
        out = materialize_batch_verify(payload)
        # latency covers launch -> materialized (in-flight time included:
        # that is what the pipeline hides, and what the sync path paid)
        _observe_verify("device", len(out), time.perf_counter() - t0)
        return out


class TableBuildError(RuntimeError):
    """Comb-table construction is unavailable (device build faulted and
    the set is too large to host-build); callers answer with host
    crypto."""


def _host_keys(keys):
    """The comb tables of `keys`, a column each, by the host's Python
    integers (no executable of its own), put on the device; and which
    of the keys are well-formed."""
    import jax.numpy as jnp

    from tendermint_tpu.ops.ed25519_tables import host_build_key_tables

    tables, ok = host_build_key_tables(list(keys))
    _metrics.TABLE_KEYS_BUILT.labels(how="host").inc(len(keys))
    return jnp.asarray(tables), ok


def _device_keys(keys):
    """The same by the device's build kernel."""
    from tendermint_tpu.ops.ed25519_tables import build_key_tables

    pub = np.frombuffer(b"".join(keys), dtype=np.uint8).reshape(len(keys), 32)
    built = build_key_tables(pub)
    _metrics.TABLE_KEYS_BUILT.labels(how="device").inc(len(keys))
    return built


class TableBatchVerifier(DeviceBatchVerifier):
    """Valset-table-cached backend: the steady-state consensus fast path.

    Commit-shaped verification (lanes aligned to a known validator set)
    routes through per-validator comb tables (`ops.ed25519_tables`), one
    table a set, kept for `cache_size` sets under the hash of the set's
    key sequence AS LAUNCHED: on the TPU a padded set (`_launch_keys`
    repeats `PLACEHOLDER_KEY` up to the launch width: 1,000 keys -> 1,024
    columns, 125.8 MB), so a table is wider than its set has distinct keys.
    A build on the chip (PERF.md section 5): 19-33 s whole, most of it the
    build executable's first call; 57-61 ms where one or two keys join."""

    # a set with up to this many keys no cached set has builds them on
    # the host (26 ms a key alone on the chip's host, about 38 beside a
    # syncing node's threads) and joins them to the cached table by one
    # concatenate and one gather (4 ms); above it: the device's builder
    MAX_INCREMENTAL_KEYS = 128

    def __init__(self, cache_size: int = 4, min_device_batch: int | None = None) -> None:
        super().__init__(min_device_batch)
        from collections import OrderedDict

        from tendermint_tpu.utils.circuit import CircuitBreaker

        # key -> (pubkeys tuple, tables, ok); key -> a build in flight
        self._tables: "OrderedDict[bytes, tuple]" = OrderedDict()
        self._building: "dict[bytes, Future]" = {}
        self._cache_size = cache_size
        self._cache_lock = threading.RLock()
        # Table CONSTRUCTION gets its own breaker (ROADMAP open item):
        # the build kernel is a separate executable from the verify
        # kernel, so it can be sick on its own — N build faults stop us
        # dialing the device builder, and small sets degrade to the
        # compile-free host build while verify stays on device.
        self._build_breaker = CircuitBreaker(
            failure_threshold=int(
                os.environ.get("TENDERMINT_TPU_BREAKER_THRESHOLD", 3)
            ),
            reset_timeout_s=float(
                os.environ.get("TENDERMINT_TPU_BREAKER_RESET_S", 5.0)
            ),
            name="tables",
        )

    @staticmethod
    def _cache_key(pubkeys: tuple[bytes, ...]) -> bytes:
        import hashlib

        return hashlib.sha256(b"".join(pubkeys)).digest()

    def _incremental_build(self, pubkeys: tuple[bytes, ...]):
        """Assemble tables for `pubkeys` from the cached set that shares
        most of its real keys: build only the keys that set lacks, put
        their columns behind the cached table's and gather `pubkeys`'
        columns out of the two (EndBlock diffs touch few keys: reference
        `state/execution.go:120-159`). None when no cached set shares one.

        Sound at a padded shape: the new columns start at the cached
        table's WIDTH, on the TPU more than the count of its distinct
        keys; a repeated key (the placeholder, a malformed key degraded
        to it) maps to the first of its columns, which all hold one
        table; each new key is built once, the placeholder among them
        only when the cached set has none (it fills its tile); the `ok`
        returned is that of every column of `pubkeys`, pad columns
        (well-formed: the placeholder decompresses) included. Returns
        as `_build_tables` does."""
        import jax.numpy as jnp

        real = set(pubkeys) - {PLACEHOLDER_KEY}
        best = None
        with self._cache_lock:
            for cached in self._tables.values():
                hits = len(real.intersection(cached[0]))
                if hits and (best is None or hits > best[0]):
                    best = (hits, cached)
        if best is None:  # nothing shared: a concatenation would only copy
            return None
        old_pubs, tables, ok = best[1]
        column: dict[bytes, int] = {}
        for i, pk in enumerate(old_pubs):
            column.setdefault(pk, i)
        missing = [pk for pk in dict.fromkeys(pubkeys) if pk not in column]
        if missing:
            few = len(missing) <= self.MAX_INCREMENTAL_KEYS
            new_t, new_ok = (_host_keys if few else _device_keys)(missing)
            tables = jnp.concatenate([tables, new_t], axis=3)
            ok = np.concatenate([ok, new_ok])
            column.update((pk, len(old_pubs) + j) for j, pk in enumerate(missing))
        perm = np.array([column[pk] for pk in pubkeys], dtype=np.int32)
        gathered = jnp.take(tables, jnp.asarray(perm), axis=3)
        return gathered, ok[perm], "incremental", len(missing)

    def _tables_for(self, pubkeys: tuple[bytes, ...], kind: str | None = None):
        """The set's table and its columns' well-formedness: from the
        cache (`hit`); from a build of this very set in flight on
        another thread, `prebuild`'s or a launch's (`joined`: it waits
        and builds nothing); else by building it (`miss`). A build that
        fails fails its waiters the same way (`TableBuildError`). `kind`
        is the build's in the histogram and the span where the caller
        has a name for it (`prebuild`); else how the build went."""
        key = self._cache_key(pubkeys)
        with self._cache_lock:
            hit = self._tables.get(key)
            if hit is not None:
                self._tables.move_to_end(key)
                _metrics.TABLE_CACHE.labels(event="hit").inc()
                return hit[1], hit[2]
            flight = self._building.get(key)
            if flight is None:
                mine = self._building[key] = Future()
        if flight is not None:
            _metrics.TABLE_CACHE.labels(event="joined").inc()
            return flight.result()
        _metrics.TABLE_CACHE.labels(event="miss").inc()
        try:
            built = self._timed_build(pubkeys, kind)
        except BaseException as e:
            with self._cache_lock:
                del self._building[key]
            mine.set_exception(e)
            raise
        with self._cache_lock:
            self._tables[key] = (tuple(pubkeys), *built)
            del self._building[key]
            while len(self._tables) > self._cache_size:
                self._tables.popitem(last=False)
        mine.set_result(built)
        return built

    def _timed_build(self, pubkeys: tuple[bytes, ...], kind: str | None = None):
        """`_build_tables` under the `tables.build` stage:
        `tendermint_verify_table_build_seconds{kind}`, and a stretch in the
        profiler's host plane, so a launch that has to build its table no
        longer hides the build in its `host_prep_s` and a prebuild is timed
        at all. One `tables.build` span a build says how it went: its
        `kind` (the histogram's), the keys whose tables it computed
        (`keys_new`: 1 or 2 where a validator joins, the table's width
        where nothing cached overlapped) and the table's `columns`. A
        build that fails is timed as `full` with no key built."""
        how, keys_new = "full", 0
        t0 = time.time()
        try:
            with TRACER.stage("tables.build") as stage:
                tables, ok, how, keys_new = self._build_tables(pubkeys)
            return tables, ok
        finally:
            kind = kind or how
            _metrics.TABLE_BUILD_SECONDS.labels(kind=kind).observe(stage.seconds)
            TRACER.add(
                "tables.build",
                t0,
                time.time(),
                kind=kind,
                keys_new=keys_new,
                columns=len(pubkeys),
            )

    def _build_tables(self, pubkeys: tuple[bytes, ...]):
        """Construct tables for an uncached set, behind the table-build
        breaker: on the device (incremental when a cached set overlaps),
        else on the host where the set is small enough to afford it, else
        `TableBuildError`, and `verify_commits` answers with host crypto.
        Returns (tables, ok, how the build went: `full`, `incremental` or
        `host_build`, the count of keys it built)."""
        from tendermint_tpu.utils.fail import device_fail_point

        if self._build_breaker.allow():
            try:
                device_fail_point("tables")
                built = self._incremental_build(pubkeys)
                if built is not None:
                    _metrics.TABLE_CACHE.labels(event="incremental").inc()
                else:
                    built = (*_device_keys(pubkeys), "full", len(pubkeys))
                self._build_breaker.record_success()
                return built
            except Exception as e:
                self._build_breaker.record_failure()
                _metrics.DISPATCH_FAILURES.labels(kind="tables").inc()
                kv(
                    logger("resilient"),
                    logging.WARNING,
                    "table build failed",
                    n_keys=len(pubkeys),
                    error=f"{type(e).__name__}: {e}"[:120],
                    breaker=self._build_breaker.state,
                )
        if len(pubkeys) <= self.MAX_INCREMENTAL_KEYS:
            _metrics.TABLE_CACHE.labels(event="host_build").inc()
            return (*_host_keys(pubkeys), "host_build", len(pubkeys))
        raise TableBuildError(
            f"table build unavailable for {len(pubkeys)} keys "
            f"(breaker {self._build_breaker.state})"
        )

    def warm_kernels(self) -> None:
        """Background-load the chunked build executable (one dummy
        2048-key device build) so the FIRST real valset build doesn't
        pay the per-process compile (or, with a warm persistent cache —
        utils/jax_cache.py — the executable load). Called by the node at
        startup on TPU backends."""
        import jax

        if jax.default_backend() != "tpu":
            return  # XLA:CPU would burn minutes compiling a kernel this
            # host will never use at scale

        def _warm():
            try:
                import numpy as _np

                from tendermint_tpu.ops.ed25519_tables import build_key_tables

                dummy = _np.zeros((2048, 32), dtype=_np.uint8)
                dummy[:, 0] = 1  # identity encodings: decompress cleanly
                build_key_tables(dummy)
            except Exception as e:
                # warming is best-effort, but never silent: the first
                # real build will meet the same fault
                kv(
                    logger("resilient"),
                    logging.WARNING,
                    "table-build warm-up failed",
                    error=f"{type(e).__name__}: {e}"[:120],
                )

        threading.Thread(target=_warm, daemon=True, name="warm-build-kernel").start()

    def prebuild(self, pubkeys) -> None:
        """Build the NEXT set's table on a thread of its own, from when
        a block's EndBlock diffs decide the set (`apply_block`). The set
        is padded as a launch pads it, so this is the table the next
        launch looks up; a launch that comes before the build ends
        waits for it in `_tables_for` and builds nothing."""
        pubs, _ = self._launch_keys(
            pubkeys, self._fused(None), self._launch_chips(len(pubkeys))
        )
        key = self._cache_key(pubs)
        with self._cache_lock:
            if key in self._tables or key in self._building:
                return

        def build():
            # a build that cannot be had was logged where it failed and
            # fails the launch that needs the table the same way
            try:
                self._tables_for(pubs, kind="prebuild")
            except TableBuildError:
                pass

        threading.Thread(target=build, daemon=True, name="table-prebuild").start()

    @staticmethod
    def _fused(force_fused: bool | None) -> bool:
        """Whether commit windows are shaped for the fused kernel: on
        the TPU, where `verify_tables_kernel` picks it for a shape that
        tiles. Off it the kernel never does, and padding would be pure
        wasted lanes; `force_fused` lets CPU tests gate the pad logic."""
        if force_fused is not None:
            return force_fused
        import jax

        return jax.default_backend() == "tpu"

    def _launch_chips(self, n: int) -> int:
        """Chips a window over `n` validators is split across."""
        return 1

    @staticmethod
    def _launch_keys(pubkeys, fused: bool, ndev: int = 1):
        """The key sequence a launch's table is built and cached under,
        and which of the `pubkeys` are well-formed: malformed keys
        degrade to a False verdict (matching every other backend)
        instead of corrupting the packed table build, and on the fused
        path the set is padded to its launch width, so a set has ONE
        cache key whatever window sizes arrive."""
        n = len(pubkeys)
        length_ok = np.array([len(pk) == 32 for pk in pubkeys], dtype=bool)
        keys = [
            bytes(pk) if ok else PLACEHOLDER_KEY
            for pk, ok in zip(pubkeys, length_ok)
        ]
        keys.extend([PLACEHOLDER_KEY] * (_window_shape(1, n, fused, ndev)[0] - n))
        return tuple(keys), length_ok

    def verify_commits(
        self,
        pubkeys: Sequence[bytes],
        commits: Sequence[tuple[Sequence[bytes | None], Sequence[bytes | None]]],
        force_fused: bool | None = None,
    ) -> np.ndarray:
        """K commits over one N-validator set -> (K, N) bool verdicts.

        Each commit is (msgs, sigs): length-N sequences aligned to
        validator index, None marking absent votes (absent lanes report
        False — callers already track presence). Replaces the
        reference's per-commit sequential loop
        (`types/validator_set.go:236-261`) with one K*N-lane device
        batch against cached tables; fast-sync stacks many commits of
        the same valset into a single call (BASELINE config 3).

        `force_fused` overrides the fused-shaping decision (tests gate
        the chunk/pad logic on the CPU mesh with it); None = auto.
        """
        return self.finalize_verify_commits(
            self.launch_verify_commits(pubkeys, commits, force_fused=force_fused)
        )

    def launch_verify_commits(
        self, pubkeys, commits, force_fused: bool | None = None
    ):
        """Async half of `verify_commits`: lane prep + one kernel launch
        per chunk, device outputs left un-materialized (the fast-sync
        pipeline preps/applies other windows while these fly). Paths
        with no device half (small commits, table-build degradation)
        compute on the spot and tag themselves "host".

        The shape rule (`commit_launch_shape`): on the TPU every window
        is launched at one padded shape, N rounded up to the 128 tile
        and K in 2..16 up to 16 (32, 64, then chunks of 64 above), so
        the executables a validator set needs do not depend on which
        window sizes arrive. Pad columns carry `PLACEHOLDER_KEY` and
        pad commits no votes: both verify False, hold no power and are
        sliced off in `finalize_verify_commits`. What stays on the host
        library: windows of k * n < `DEVICE_MIN_BATCH` REAL lanes,
        where a launch costs more than the loop. K = 1 is apart:
        consensus' own commit keeps its stack of one (latency), over
        the same padded table. Off the TPU nothing is padded.
        """
        from tendermint_tpu.ops.ed25519_tables import verify_tables_kernel

        n = len(pubkeys)
        k = len(commits)
        if n == 0 or k == 0:
            return ("host", np.zeros((k, n), dtype=bool))
        if k * n < self._min_batch:
            # small commits: host loop beats a device launch
            return ("host", self._host_commit_loop(pubkeys, commits))
        fused = self._fused(force_fused)
        keys, length_ok = self._launch_keys(pubkeys, fused)
        try:
            tables, key_ok = self._tables_for(keys)
        except TableBuildError:
            # table construction is down and the set is too big to
            # host-build: answer this call with host crypto (slow but
            # correct) instead of raising out of the consensus path
            return ("host", self._host_commit_loop(pubkeys, commits))
        key_ok = key_ok[:n] & length_ok
        n_launch, chunks = _window_shape(k, n, fused)
        launches = []  # (device_out, lane_ok, k_launch) per chunk
        t0 = time.perf_counter()
        for _real, k_launch, s, h, r, precheck in _window_chunks(
            pubkeys, commits, n_launch, chunks
        ):
            dev = verify_tables_kernel(tables, s, h, r)
            launches.append((dev, precheck & key_ok, k_launch))
            _launchlog.add_transfer(s.nbytes + h.nbytes + r.nbytes)
        _launchlog.annotate(n_launch=n_launch)
        return ("device", launches, n, k, n_launch, t0)

    def finalize_verify_commits(self, launched) -> np.ndarray:
        if launched[0] == "host":
            return launched[1]
        _tag, launches, n, k, n_launch, t0 = launched
        out_rows = []
        for dev, lane_ok, k_launch in launches:
            out = np.asarray(dev).reshape(k_launch, n_launch)
            out_rows.append(out[: len(lane_ok), :n] & lane_ok)
        _observe_verify("tables", k * n, time.perf_counter() - t0, kind="tables")
        return np.concatenate(out_rows, axis=0)

    def verify_commits_async(
        self,
        pubkeys,
        commits,
        queue=None,
        force_fused: bool | None = None,
        consumer: str = "default",
    ):
        """`verify_commits` through the dispatch queue: a VerifyHandle
        resolving to the (K, N) verdict grid, kernels in flight until
        the consumer joins."""
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(
            lambda: self.launch_verify_commits(
                pubkeys, commits, force_fused=force_fused
            ),
            self.finalize_verify_commits,
            kind="verify",
        )

    def _host_commit_loop(self, pubkeys, commits) -> np.ndarray:
        """Sequential host verification of commit-shaped lanes — the
        small-commit path and the table-build degradation target."""
        n = len(pubkeys)
        out = np.zeros((len(commits), n), dtype=bool)
        for ci, (msgs, sigs) in enumerate(commits):
            lanes = [
                i
                for i in range(n)
                if msgs[i] is not None and sigs[i] is not None
            ]
            lane_triples = [(pubkeys[i], msgs[i], sigs[i]) for i in lanes]
            if lane_triples:
                verdicts = self._host.verify_batch(lane_triples)
                for i, v in zip(lanes, verdicts):
                    out[ci, i] = v
        return out


class _MeshFlatMixin:
    """Shared mesh plumbing for the sharded verifier backends: the flat
    (pub, r, s, h) batch lane over `parallel.mesh.MeshManager`, with the
    survivor re-mesh loop around every launch.

    Geometry: rows are padded to `per_shard_bucket * n_active` where the
    per-shard bucket is the power-of-two `bucket_size` of the per-chip
    share (min 8) — compiled executables are keyed by PER-CHIP shard
    shape, so the same buckets serve any mesh size and a survivor
    re-mesh only recompiles the step, not a new zoo of shapes.
    """

    mesh = None  # set by subclass __init__

    def _mesh_flat_launch(self, pub, r, s, h, powers):
        """One sharded launch over the active mesh; re-meshes onto
        survivors on an attributable shard fault, raises
        `MeshExhaustedError` (into the caller's breaker) when no
        devices remain. Returns (verdict, tally) un-materialized."""
        from tendermint_tpu.ops.ed25519_kernel import bucket_size
        from tendermint_tpu.ops.padding import pad_rows_to
        from tendermint_tpu.parallel.mesh import MeshExhaustedError
        from tendermint_tpu.utils.fail import ShardDeviceFault

        m = self.mesh
        m.maybe_reprobe()
        n = pub.shape[0]
        while True:
            ndev = m.n_active
            if ndev == 0:
                raise MeshExhaustedError(
                    f"all {m.n_total} mesh devices faulted"
                )
            try:
                m.check_shard_faults()
                size = bucket_size(-(-n // ndev)) * ndev
                arrs = pad_rows_to([pub, r, s, h, powers], size)
                step = m.verify_step()
                ok, total = step(*arrs)
                # annotated only on the successful attempt: a shard
                # fault retried onto survivors must not double-count
                _launchlog.annotate(_additive=True, rows_padded=size - n)
                _launchlog.annotate(mesh_width=ndev)
                _launchlog.add_transfer(sum(a.nbytes for a in arrs))
                return ok, total
            except ShardDeviceFault as e:
                if not m.record_shard_fault(e.shard):
                    raise MeshExhaustedError(
                        f"all {m.n_total} mesh devices faulted"
                    ) from e

    def launch_verify_batch(self, triples):
        """Flat-batch async half: host prep + ONE launch sharded over
        every active chip (the coalescer's merged batches land here —
        one logical device that is actually N chips). Sub-threshold
        batches and single-device meshes take the legacy paths."""
        if self.mesh.n_total <= 1:
            return super().launch_verify_batch(triples)
        if not triples:
            return ("host", np.zeros(0, dtype=bool))
        if len(triples) < self._min_batch:
            return ("host", self._host.verify_batch(triples))
        from tendermint_tpu.ops.ed25519_kernel import prepare_batch

        pubs, msgs, sigs = zip(*triples)
        t0 = time.perf_counter()
        pub, r, s, h, precheck = prepare_batch(pubs, msgs, sigs)
        powers = np.zeros(len(triples), dtype=np.int32)
        ok, _total = self._mesh_flat_launch(pub, r, s, h, powers)
        return ("mesh", ok, precheck, len(triples), t0)

    def finalize_verify_batch(self, launched) -> np.ndarray:
        if launched[0] != "mesh":
            return super().finalize_verify_batch(launched)
        _tag, ok, precheck, n, t0 = launched
        out = np.asarray(ok)[:n] & precheck
        _observe_verify("mesh", n, time.perf_counter() - t0)
        return out

    def verify_batch_with_powers(self, triples, powers):
        """Commit-tally lane: per-item verdicts PLUS the psum-reduced
        verified-power total computed on device across all shards (the
        `sharded_verify_and_tally` collective — no host gather for the
        2/3-quorum sum). Pad rows and precheck-failed rows carry zero
        bytes, verify False on every backend, and so never contribute
        power."""
        from tendermint_tpu.ops.ed25519_kernel import prepare_batch

        if not triples:
            return np.zeros(0, dtype=bool), 0
        pubs, msgs, sigs = zip(*triples)
        t0 = time.perf_counter()
        pub, r, s, h, precheck = prepare_batch(pubs, msgs, sigs)
        pw = np.asarray(powers, dtype=np.int32) * precheck
        ok, total = self._mesh_flat_launch(pub, r, s, h, pw)
        mask = np.asarray(ok)[: len(triples)] & precheck
        _observe_verify("mesh", len(triples), time.perf_counter() - t0)
        return mask, int(total)

    def snapshot(self) -> dict:
        return {"mesh": self.mesh.snapshot()}


class ShardedBatchVerifier(_MeshFlatMixin, DeviceBatchVerifier):
    """Generic-ladder batch verifier sharded over a device mesh.

    The CPU-mesh / ad-hoc-triple production backend: every launch splits
    the batch axis over the active chips of a `MeshManager` and psums
    the power tally. Commit grids flatten their present lanes into the
    same flat mesh lane and scatter verdicts back — so fast-sync windows
    and consensus commits ride N chips even without valset tables.
    """

    def __init__(self, mesh=None, min_device_batch: int | None = None) -> None:
        super().__init__(min_device_batch)
        from tendermint_tpu.parallel.mesh import MeshManager

        self.mesh = mesh if mesh is not None else MeshManager()

    def verify_commits(self, pubkeys, commits, force_fused=None) -> np.ndarray:
        return self.finalize_verify_commits(
            self.launch_verify_commits(pubkeys, commits, force_fused=force_fused)
        )

    def launch_verify_commits(self, pubkeys, commits, force_fused=None):
        """Commit grids as flat mesh lanes: present (msg, sig) lanes
        flatten into one batch-sharded launch (`force_fused` is a
        single-device-tables concept; ignored here)."""
        n, k = len(pubkeys), len(commits)
        lanes: list[tuple[int, int]] = []
        triples: list[Triple] = []
        for ci, (msgs, sigs) in enumerate(commits):
            for i in range(n):
                if msgs[i] is not None and sigs[i] is not None:
                    lanes.append((ci, i))
                    triples.append((pubkeys[i], msgs[i], sigs[i]))
        if self.mesh.n_total <= 1 or len(triples) < self._min_batch:
            grid = np.zeros((k, n), dtype=bool)
            if triples:
                verdicts = self._host.verify_batch(triples)
                for (ci, i), ok in zip(lanes, verdicts):
                    grid[ci, i] = bool(ok)
            return ("host_grid", grid)
        launched = self.launch_verify_batch(triples)
        return ("mesh_grid", launched, lanes, k, n)

    def finalize_verify_commits(self, launched) -> np.ndarray:
        if launched[0] == "host_grid":
            return launched[1]
        _tag, flat, lanes, k, n = launched
        mask = self.finalize_verify_batch(flat)
        grid = np.zeros((k, n), dtype=bool)
        for (ci, i), ok in zip(lanes, mask):
            grid[ci, i] = bool(ok)
        return grid

    def verify_commits_async(
        self, pubkeys, commits, queue=None, force_fused=None, consumer="default"
    ):
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(
            lambda: self.launch_verify_commits(
                pubkeys, commits, force_fused=force_fused
            ),
            self.finalize_verify_commits,
            kind="verify",
        )


class ShardedTableBatchVerifier(_MeshFlatMixin, TableBatchVerifier):
    """The mesh-aware TABLE fast path: the TPU steady-state backend.

    Commit-grid verification shards along the VALIDATOR axis
    (`parallel.mesh.sharded_tables_verify_and_tally`): each chip holds
    1/ndev of the cached comb-table columns plus the lanes of its own
    validators for every stacked commit, and the power tally psums over
    ICI. Stack/chunk geometry derives from the PER-CHIP shard size —
    the fused-pallas selection inside `verify_tables_kernel` sees
    per-shard shapes under shard_map, and the host-side K padding uses
    per-chip lane counts, not the global batch (the single-device
    assumption this class exists to remove).

    Falls back per-call to the single-device table path when the valset
    does not split evenly over the active chips (N % ndev != 0), and to
    flat mesh lanes when the mesh executor has no table program (the
    host-emulated CPU seam).
    """

    def __init__(
        self,
        mesh=None,
        cache_size: int = 4,
        min_device_batch: int | None = None,
    ) -> None:
        super().__init__(cache_size=cache_size, min_device_batch=min_device_batch)
        from tendermint_tpu.parallel.mesh import MeshManager

        self.mesh = mesh if mesh is not None else MeshManager()
        # (valset key, active device tuple) -> mesh-sharded table array;
        # re-sharding 1.25 GB of tables per launch would eat the win
        self._sharded_tables: dict = {}

    def _tables_for_mesh(self, pubkeys: tuple[bytes, ...], mesh_obj):
        """Valset tables placed WITH the validator-axis sharding for the
        active mesh (cached per device set). The build is `_tables_for`'s,
        of a set padded to the mesh's launch width: breaker, joins and all."""
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from tendermint_tpu.parallel.mesh import BATCH_AXIS

        tables, key_ok = self._tables_for(pubkeys)
        key = (self._cache_key(pubkeys), tuple(mesh_obj.devices.flat))
        with self._cache_lock:
            hit = self._sharded_tables.get(key)
        if hit is not None:
            _metrics.TABLE_DEVICE_CACHE.labels(result="hit").inc()
            return hit, key_ok
        _metrics.TABLE_DEVICE_CACHE.labels(result="miss").inc()
        sharding = NamedSharding(mesh_obj, _P(None, None, None, BATCH_AXIS))
        t_put = time.perf_counter()
        placed = _jax.device_put(tables, sharding)
        # the re-ship cost every placement-cache miss pays (GB-scale at
        # large valsets): both the bytes and the device_put stall land
        # on this launch's ledger record
        _launchlog.add_transfer(int(getattr(tables, "nbytes", 0)))
        _launchlog.annotate(
            _additive=True, device_put_s=time.perf_counter() - t_put
        )
        with self._cache_lock:
            self._sharded_tables[key] = placed
            while len(self._sharded_tables) > self._cache_size * 2:
                self._sharded_tables.pop(next(iter(self._sharded_tables)))
        return placed, key_ok

    def launch_verify_commits(self, pubkeys, commits, force_fused=None):
        n, k = len(pubkeys), len(commits)
        m = self.mesh
        if m.n_total <= 1:
            return super().launch_verify_commits(
                pubkeys, commits, force_fused=force_fused
            )
        if n == 0 or k == 0:
            return ("host", np.zeros((k, n), dtype=bool))
        if k * n < self._min_batch:
            return ("host", self._host_commit_loop(pubkeys, commits))
        if m.executor != "device":
            # host-emulated mesh: flat lanes keep the fault/re-mesh
            # choreography testable without the tables program
            return ShardedBatchVerifier.launch_verify_commits(
                self, pubkeys, commits
            )
        m.maybe_reprobe()
        from tendermint_tpu.parallel.mesh import MeshExhaustedError
        from tendermint_tpu.utils.fail import ShardDeviceFault

        while True:
            if m.n_active == 0:
                raise MeshExhaustedError(
                    f"all {m.n_total} mesh devices faulted"
                )
            if self._launch_chips(n) != m.n_active:
                # uneven split: per-call fallback to the single-device
                # table path (still breaker-guarded upstream)
                return super().launch_verify_commits(
                    pubkeys, commits, force_fused=force_fused
                )
            try:
                m.check_shard_faults()
                return self._launch_mesh_tables(
                    pubkeys, commits, force_fused=force_fused
                )
            except ShardDeviceFault as e:
                if not m.record_shard_fault(e.shard):
                    raise MeshExhaustedError(
                        f"all {m.n_total} mesh devices faulted"
                    ) from e

    def _launch_chips(self, n: int) -> int:
        """The active chips when the tables program serves the window:
        a device mesh whose chips split `n` evenly; else 1, the
        single-device table path."""
        m = self.mesh
        if m.n_total <= 1 or m.executor != "device":
            return 1
        ndev = m.n_active
        return ndev if ndev and n % ndev == 0 else 1

    def _launch_mesh_tables(self, pubkeys, commits, force_fused=None):
        from tendermint_tpu.parallel.mesh import shard_lanes_validator_major

        n, k = len(pubkeys), len(commits)
        m = self.mesh
        ndev = m.n_active
        fused = self._fused(force_fused)
        keys, length_ok = self._launch_keys(pubkeys, fused, ndev)
        try:
            tables, key_ok = self._tables_for_mesh(keys, m.mesh())
        except TableBuildError:
            return ("host", self._host_commit_loop(pubkeys, commits))
        key_ok = key_ok[:n] & length_ok
        # Stack/chunk geometry from the PER-CHIP lane count: the fused
        # plane shape inside verify_tables_kernel sees n_launch/ndev
        # columns per shard under shard_map, so the one shape rule is
        # asked about n // ndev validators. Real validators fill the
        # leading columns; the pad columns land on the last chips.
        n_launch, chunks = _window_shape(k, n, fused, ndev)
        step = m.tables_step()
        launches = []  # (device_ok, real, k_launch) per chunk
        t0 = time.perf_counter()
        for real, k_launch, s, h, r, precheck in _window_chunks(
            pubkeys, commits, n_launch, chunks
        ):
            lane_ok = _pad_lanes(
                (precheck & key_ok).reshape(-1), real, n, k_launch, n_launch
            )
            powers = np.ones(k_launch * n_launch, dtype=np.int32)
            s, h, r, lane_ok_s, powers = shard_lanes_validator_major(
                [s, h, r, lane_ok, powers], n_launch, ndev
            )
            ok, _total = step(tables, s, h, r, lane_ok_s, powers)
            launches.append((ok, real, k_launch))
            _launchlog.add_transfer(
                s.nbytes + h.nbytes + r.nbytes + lane_ok_s.nbytes + powers.nbytes
            )
        _launchlog.annotate(mesh_width=ndev, n_launch=n_launch)
        return ("mesh_tables", launches, ndev, k, n, n_launch, t0)

    def finalize_verify_commits(self, launched) -> np.ndarray:
        if launched[0] in ("host_grid", "mesh_grid"):
            return ShardedBatchVerifier.finalize_verify_commits(self, launched)
        if launched[0] != "mesh_tables":
            return super().finalize_verify_commits(launched)
        from tendermint_tpu.parallel.mesh import unshard_lanes_validator_major

        _tag, launches, ndev, k, n, n_launch, t0 = launched
        rows = []
        for ok, real, k_launch in launches:
            lanes = unshard_lanes_validator_major(np.asarray(ok), n_launch, ndev)
            rows.append(lanes.reshape(k_launch, n_launch)[:real, :n])
        _observe_verify("mesh", k * n, time.perf_counter() - t0, kind="tables")
        return np.concatenate(rows, axis=0)


_DEFAULT: BatchVerifier | None = None


def _mesh_opt_in_cpu() -> bool:
    """CPU backends join the sharded mesh only when
    TENDERMINT_TPU_MESH_DEVICES explicitly asks for >= 2 devices (the
    virtual-device test recipe, docs/PLATFORM_NOTES.md); on TPU the
    mesh is the default whenever more than one chip is visible."""
    knob = os.environ.get("TENDERMINT_TPU_MESH_DEVICES")
    if not knob or int(knob) < 2:
        return False
    from tendermint_tpu.parallel.mesh import mesh_device_count

    return mesh_device_count() > 1


def default_verifier() -> BatchVerifier:
    """Process-wide verifier: device-backed iff an accelerator is up,
    MESH-backed iff more than one chip is visible.

    On a multi-chip backend the device layer is the sharded mesh stack
    (`ShardedTableBatchVerifier` over `parallel.mesh.MeshManager`): the
    coalescer feeds one logical device that is actually N chips, and a
    per-shard device fault re-meshes onto the survivors before the
    breaker ever considers host fallback (docs/PERFORMANCE.md "Mesh
    scale-out"; TENDERMINT_TPU_MESH_DEVICES=1 forces the single-device
    legacy path).

    On CPU-only hosts the emulated curve kernel is far slower than the
    host crypto library, so fall back to HostBatchVerifier there —
    unless TENDERMINT_TPU_MESH_DEVICES >= 2 opts into the sharded stack
    over virtual devices (the CI mesh recipe, docs/PLATFORM_NOTES.md).
    Consensus paths that don't thread an explicit verifier use this
    (mirrors the reference's package-global crypto functions).

    Device backends are wrapped in `ResilientVerifier` so a device
    fault degrades verification to host instead of killing consensus
    (`services/resilient.py`); host-only runs get the wrapper too when
    fault injection (TENDERMINT_TPU_DEVICE_FAIL) is armed, so chaos
    tests exercise the same dispatch path CI-side.

    Whatever the backend stack, the outermost layer is the
    `CoalescingVerifier` (`services/batcher.py`): a verified-signature
    dedup cache plus the cross-consumer launch coalescer (the wrap is
    verdict-transparent — only positives are cached, failures always
    re-verify).
    """
    global _DEFAULT
    if _DEFAULT is None:
        import jax

        from tendermint_tpu.utils.fail import device_faults_armed

        if jax.default_backend() == "cpu":
            if _mesh_opt_in_cpu():
                # CPU hosts (incl. the 8-virtual-device CI mesh) ride
                # the sharded backend only on explicit opt-in — the
                # emulated kernels are slower than host crypto, so this
                # exists for mesh-path testing, not speed
                from tendermint_tpu.parallel.mesh import default_mesh_manager
                from tendermint_tpu.services.resilient import ResilientVerifier

                inner: BatchVerifier = ResilientVerifier(
                    ShardedBatchVerifier(mesh=default_mesh_manager())
                )
            elif device_faults_armed():
                from tendermint_tpu.services.resilient import ResilientVerifier

                inner = ResilientVerifier(DeviceBatchVerifier())
            else:
                inner = HostBatchVerifier()
        else:
            from tendermint_tpu.parallel.mesh import mesh_device_count
            from tendermint_tpu.services.resilient import ResilientVerifier

            if mesh_device_count() > 1:
                from tendermint_tpu.parallel.mesh import default_mesh_manager

                inner = ResilientVerifier(
                    ShardedTableBatchVerifier(mesh=default_mesh_manager())
                )
            else:
                inner = ResilientVerifier(TableBatchVerifier())
        from tendermint_tpu.services.batcher import CoalescingVerifier

        _DEFAULT = CoalescingVerifier(inner)
    return _DEFAULT


def set_default_verifier(v: BatchVerifier) -> None:
    global _DEFAULT
    _DEFAULT = v
