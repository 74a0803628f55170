"""The tx index's store: an append-only log of sorted runs.

Written for one write pattern, which the one-table B-tree of `kv.py`
cannot take cheaply: ten thousand rows a block under keys that are
hashes. A block's rows are ONE record appended to `data` and fsynced
once; nothing is rewritten in place, so a block costs its own bytes at
every height.

Files, in a directory of the store's own:

    data        records, one a block, appended:
                  "TXR1" | height u64 | count u32 | vlen u64
                  count x (key 32 B | pointer u64), sorted by key
                  vlen bytes of values, each: length u32 | bytes
                  crc32 u32 of all of the above
    *.keys      a merge's output: "TXKEYS01" | count u64 |
                  count x (key | pointer), sorted by key
    MANIFEST    JSON: the live runs, oldest first (a key file, or a
                  record of `data` by its offset), and `scan_from`:
                  records of `data` from there on are live as well

Integers are little-endian. A pointer is the offset in `data` of a
value's length; values are opaque bytes (what they hold is
`state/txindex.py`'s) and are never moved or copied. A run is a sorted
array of (key, pointer): a record's own, or a key file's. `get` probes
the runs newest first, so a key written twice answers with its latest
value.

`append` is handed a block's rows as the record wants them: the keys
end to end, the value section ready made (each value behind its
length), and where each length lies in it. What is left to it is
whole-array work: it checks that lengths and places agree, sorts the
keys (stable: of a key that a block holds twice the last stays, and the
earlier value stays in the section with no pointer to it), turns places
into pointers and writes.

What is durable when. `append` returns after one fsync that covers the
whole record, and only then do readers see the run: all of a block's
rows or none, to a reader on another thread and to a crash. A record
whose tail is torn or whose checksum fails at open is cut off, with
whatever follows it: that block's rows are "none".

Merging. A background thread merges runs that are neighbours in age and
alike in size (`_plan`) into one key file: only the 40-byte entries are
rewritten, a slice of the key space at a time (`_merged`), so a merge's
memory does not grow with its level. It writes and fsyncs the output,
switches the manifest (temp file, fsync, rename, fsync of the
directory), swaps the in-memory list, and only then removes its inputs;
a file the manifest does not name is removed at open. Readers hold one immutable tuple of runs and so never
see a row in neither the inputs nor the output. `append` never waits
for a merge, except to bound the backlog: while a level that is due
holds `2 * FAN_IN` runs (the merger is a whole round behind) it waits.
"""

from __future__ import annotations

import json
import logging
import mmap
import os
import struct
import threading
import zlib

import numpy as np

from tendermint_tpu.db.kv import CommitClock
from tendermint_tpu.telemetry.metrics import (
    TXINDEX_BYTES_WRITTEN,
    TXINDEX_MERGES,
    TXINDEX_PROBES,
    TXINDEX_RUNS,
)
from tendermint_tpu.telemetry.process import retire_thread

KEY_LEN = 32
ENTRY = np.dtype([("key", f"S{KEY_LEN}"), ("ptr", "<u8")])
FAN_IN = 8  # runs of one level that make a merge
SLICE = 1 << 15  # entries of one input that a merge holds at a time

_RECORD_MAGIC = b"TXR1"
_RECORD_HEAD = struct.Struct("<4sQIQ")
_KEYS_MAGIC = b"TXKEYS01"
_KEYS_HEAD = struct.Struct("<8sQ")
_U32 = struct.Struct("<I")
_DATA = "data"
_MANIFEST = "MANIFEST"

_log = logging.getLogger(__name__)


def _tier(count: int) -> int:
    """floor(log8(count)): runs of one tier are alike in size, and
    `FAN_IN` of them merge into a run one tier up."""
    return (max(count, 1).bit_length() - 1) // 3


def _write_all(fd: int, buf: bytes | np.ndarray) -> None:
    if isinstance(buf, np.ndarray):
        buf = buf.view(np.uint8)  # a structured array has no byte view of its own
    view = memoryview(buf)
    while view:
        view = view[os.write(fd, view):]


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class _Run:
    """A sorted array of `count` entries inside a memory-mapped file."""

    __slots__ = ("count", "file", "at", "_map", "_base")

    def __init__(self, fd: int, offset: int, count: int, *, file=None, at=None):
        self.count = count
        self.file = file  # a key file's name, or
        self.at = at  # the offset of a record of `data`
        # a mapping starts on a page: map from the boundary below
        self._base = offset % mmap.ALLOCATIONGRANULARITY
        self._map = mmap.mmap(
            fd,
            self._base + count * ENTRY.itemsize,
            access=mmap.ACCESS_READ,
            offset=offset - self._base,
        )

    def entries(self) -> np.ndarray:
        return np.frombuffer(self._map, dtype=ENTRY, count=self.count, offset=self._base)

    def lower_bound(self, key: bytes) -> int:
        """How many entries lie below `key`, by binary search."""
        buf, base, width = self._map, self._base, ENTRY.itemsize
        lo, hi = 0, self.count
        while lo < hi:
            mid = (lo + hi) >> 1
            at = base + mid * width
            if buf[at : at + KEY_LEN] < key:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def find(self, key: bytes) -> int | None:
        """The pointer stored under `key`."""
        i = self.lower_bound(key)
        at = self._base + i * ENTRY.itemsize
        if i < self.count and self._map[at : at + KEY_LEN] == key:
            return int.from_bytes(self._map[at + KEY_LEN : at + ENTRY.itemsize], "little")
        return None

    def listed(self) -> dict:
        if self.file is not None:
            return {"file": self.file, "count": self.count}
        return {"at": self.at, "count": self.count}


class RunLog:
    """32-byte keys to values, appended a block at a time."""

    def __init__(self, path: str) -> None:
        self._dir = path
        created = not os.path.isdir(path)
        os.makedirs(path, exist_ok=True)
        self._fd = os.open(
            os.path.join(path, _DATA), os.O_RDWR | os.O_CREAT | os.O_APPEND, 0o644
        )
        self._append_lock = threading.Lock()  # one record at a time
        self._cond = threading.Condition()  # guards _runs, _closed, _merger
        self._closed = False
        self._merger: threading.Thread | None = None
        self._commits = CommitClock("txindex")
        self._next_file = 0
        try:
            self._runs: tuple[_Run, ...] = self._recover()
        except BaseException:
            os.close(self._fd)
            raise
        _fsync_dir(path)
        if created:
            _fsync_dir(os.path.dirname(os.path.abspath(path)))
        TXINDEX_RUNS.set(len(self._runs))
        self._kick()

    # -- open -------------------------------------------------------------

    def _recover(self) -> tuple[_Run, ...]:
        """The manifest's runs, then every sound record of `data` from
        `scan_from` on; a torn tail is cut off and stray files go."""
        manifest = {"scan_from": 0, "runs": [], "next_file": 0}
        try:
            with open(os.path.join(self._dir, _MANIFEST), "rb") as f:
                manifest = json.loads(f.read())
        except FileNotFoundError:
            pass
        self._next_file = manifest["next_file"]
        size = os.fstat(self._fd).st_size
        if manifest["scan_from"] > size:
            raise OSError(
                f"{self._dir}: the manifest names {manifest['scan_from']} bytes "
                f"of data and the file has {size}"
            )
        runs = []
        for row in manifest["runs"]:
            if "file" in row:
                runs.append(self._open_keys(row["file"], row["count"]))
            else:
                runs.append(self._record_run(row["at"], row["count"]))
        named = {_DATA, _MANIFEST} | {r.file for r in runs if r.file is not None}
        for name in os.listdir(self._dir):
            if name not in named:
                os.unlink(os.path.join(self._dir, name))
        at = manifest["scan_from"]
        while at < size:
            record = self._sound_record(at, size)
            if record is None:
                _log.warning(
                    "%s: cutting %d bytes after a crash: the record at %d is torn",
                    self._dir, size - at, at,
                )
                os.ftruncate(self._fd, at)
                os.fsync(self._fd)
                break
            count, length = record
            runs.append(self._record_run(at, count))
            at += length
        self._end = at
        return tuple(runs)

    def _sound_record(self, at: int, size: int) -> tuple[int, int] | None:
        """The count and the length of the record at `at`, if it is whole."""
        head = os.pread(self._fd, _RECORD_HEAD.size, at)
        if len(head) < _RECORD_HEAD.size:
            return None
        magic, _height, count, vlen = _RECORD_HEAD.unpack(head)
        length = _RECORD_HEAD.size + count * ENTRY.itemsize + vlen + _U32.size
        if magic != _RECORD_MAGIC or at + length > size:
            return None
        body = os.pread(self._fd, length, at)
        if len(body) < length or zlib.crc32(body[: -_U32.size]) != _U32.unpack(body[-_U32.size :])[0]:
            return None
        return count, length

    def _record_run(self, at: int, count: int) -> _Run:
        return _Run(self._fd, at + _RECORD_HEAD.size, count, at=at)

    def _open_keys(self, name: str, count: int) -> _Run:
        fd = os.open(os.path.join(self._dir, name), os.O_RDONLY)
        try:
            magic, stored = _KEYS_HEAD.unpack(os.pread(fd, _KEYS_HEAD.size, 0))
            size = os.fstat(fd).st_size
            if (magic, stored, size) != (
                _KEYS_MAGIC, count, _KEYS_HEAD.size + count * ENTRY.itemsize
            ):
                raise OSError(f"{self._dir}/{name}: not the key file the manifest names")
            return _Run(fd, _KEYS_HEAD.size, count, file=name)
        finally:
            os.close(fd)  # the mapping outlives it

    # -- write ------------------------------------------------------------

    def append(self, height: int, keys: bytes, values: bytes, starts: np.ndarray) -> None:
        """A block's rows as one run, durable and then visible when this
        returns. `keys`: the rows' keys end to end, `KEY_LEN` bytes
        each. `values`: the record's value section as it goes to disk,
        every value behind its length u32, in the keys' order. `starts`:
        where in `values` each value's length lies. Of equal keys the
        last stays; the earlier one's value stays in the section with
        no pointer to it."""
        starts = np.asarray(starts, dtype=np.int64)
        count = len(starts)
        if not count:
            return
        if len(keys) != count * KEY_LEN:
            raise ValueError(f"a key is not {KEY_LEN} bytes")
        if not _framed(values, starts):
            raise ValueError("the values do not lie behind their lengths, end to end")
        keys = np.frombuffer(keys, dtype=ENTRY["key"])
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        last = np.ones(count, dtype=bool)
        last[:-1] = keys[1:] != keys[:-1]
        if not last.all():
            order, keys, count = order[last], keys[last], int(last.sum())
        entries = np.empty(count, dtype=ENTRY)
        entries["key"] = keys
        with self._append_lock:
            at = self._end
            entries["ptr"] = starts[order] + (at + _RECORD_HEAD.size + count * ENTRY.itemsize)
            head = _RECORD_HEAD.pack(_RECORD_MAGIC, height, count, len(values))
            keyed = entries.tobytes()
            crc = zlib.crc32(values, zlib.crc32(keyed, zlib.crc32(head)))
            record = b"".join((head, keyed, values, _U32.pack(crc)))
            try:
                with self._commits.stage() as commit:
                    _write_all(self._fd, record)
                    os.fsync(self._fd)
            except BaseException:
                os.ftruncate(self._fd, at)  # none of the block's rows
                raise
            run = self._record_run(at, count)
            with self._cond:
                # together: a merge's manifest takes both as one
                self._end = at + len(record)
                self._runs += (run,)
                live = len(self._runs)
                TXINDEX_RUNS.set(live)
        self._commits.add(commit)
        TXINDEX_BYTES_WRITTEN.labels(kind="append").inc(len(record))
        self._kick()
        if live >= 2 * FAN_IN:
            with self._cond:
                while self._merger is not None and any(
                    hi - lo >= 2 * FAN_IN for lo, hi in _due(self._runs)
                ):
                    self._cond.wait()

    # -- read -------------------------------------------------------------

    def get(self, key: bytes) -> bytes | None:
        runs = self._runs
        probes = 0
        for run in reversed(runs):
            probes += 1
            ptr = run.find(key)
            if ptr is not None:
                TXINDEX_PROBES.observe(probes)
                return self._value(ptr)
        TXINDEX_PROBES.observe(probes)
        return None

    def _value(self, ptr: int) -> bytes:
        guess = 1024  # most values come with their length in one read
        buf = os.pread(self._fd, _U32.size + guess, ptr)
        (length,) = _U32.unpack_from(buf)
        if length > guess:
            buf += os.pread(self._fd, length - guess, ptr + len(buf))
        return buf[_U32.size : _U32.size + length]

    # -- merge ------------------------------------------------------------

    def _kick(self) -> None:
        """Start the merger if a merge is due and none runs."""
        with self._cond:
            if self._closed or self._merger is not None or _plan(self._runs) is None:
                return
            self._merger = threading.Thread(
                target=self._merge_loop, name="txindex-merge", daemon=True
            )
            self._merger.start()

    def _merge_loop(self) -> None:
        """Merges until none is due, then ends: `_kick` starts another.
        Whether one is due and whether one runs change under one lock,
        so an append never finds a merger that has just given up."""
        try:
            while True:
                with self._cond:
                    plan = None if self._closed else _plan(self._runs)
                    if plan is None:
                        self._merger = None
                        self._cond.notify_all()
                        return
                try:
                    self._merge(*plan)
                except Exception:
                    # the index is whole and answers; the next append retries
                    _log.exception("%s: a merge failed", self._dir)
                    with self._cond:
                        self._merger = None
                        self._cond.notify_all()
                    return
        finally:
            # a merger lives for its merges, often between two scrapes:
            # its CPU goes to `txindex_merge` as it leaves
            retire_thread()

    def _merge(self, lo: int, hi: int) -> None:
        """Runs `lo` to `hi` of the list, neighbours in age, into one."""
        inputs = self._runs[lo:hi]
        name = f"{self._next_file:012d}.keys"
        self._next_file += 1
        path = os.path.join(self._dir, name)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
        try:
            count = 0
            _write_all(fd, _KEYS_HEAD.pack(_KEYS_MAGIC, count))  # known last
            for out in _merged(inputs):
                _write_all(fd, out)
                count += len(out)
            os.pwrite(fd, _KEYS_HEAD.pack(_KEYS_MAGIC, count), 0)
            os.fsync(fd)
        finally:
            os.close(fd)
        _fsync_dir(self._dir)  # the file's name, before a manifest names it
        TXINDEX_BYTES_WRITTEN.labels(kind="merge").inc(
            _KEYS_HEAD.size + count * ENTRY.itemsize
        )
        output = self._open_keys(name, count)
        # a list and the end of `data` that belong together: every run
        # appended since lies past `scan_from` and is found at open
        with self._cond:
            runs, scan_from = self._runs, self._end
        runs = runs[:lo] + (output,) + runs[hi:]
        self._switch_manifest(
            [r.listed() for r in runs if r.at is None or r.at < scan_from], scan_from
        )
        with self._cond:
            self._runs = self._runs[:lo] + (output,) + self._runs[hi:]
            TXINDEX_RUNS.set(len(self._runs))
            self._cond.notify_all()
        self._drop(inputs)
        TXINDEX_MERGES.inc()

    def _switch_manifest(self, listed: list[dict], scan_from: int) -> None:
        tmp = os.path.join(self._dir, _MANIFEST + ".tmp")
        body = json.dumps(
            {"scan_from": scan_from, "runs": listed, "next_file": self._next_file}
        ).encode()
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
        try:
            _write_all(fd, body)
            os.fsync(fd)
        finally:
            os.close(fd)
        os.rename(tmp, os.path.join(self._dir, _MANIFEST))
        _fsync_dir(self._dir)

    def _drop(self, inputs: tuple[_Run, ...]) -> None:
        """A merged record stays in `data`, where its values are; a
        merged key file goes (a reader that still probes it keeps its
        mapping)."""
        for run in inputs:
            if run.file is not None:
                os.unlink(os.path.join(self._dir, run.file))

    def close(self) -> None:
        """Ends the merger after the merge it is in. Every appended row
        is on disk already."""
        with self._cond:
            self._closed = True
            merger = self._merger
        if merger is not None:
            merger.join()
        with self._append_lock:
            if self._fd >= 0:
                os.close(self._fd)
                self._fd = -1


def _framed(values: bytes, starts: np.ndarray) -> bool:
    """Whether `values` is values end to end, each behind its length
    u32, and `starts` the places of those lengths: `get` trusts both."""
    if starts[0] != 0 or starts.min() < 0 or starts.max() + _U32.size > len(values):
        return False
    raw = np.frombuffer(values, dtype=np.uint8)
    lengths = raw[starts[:, None] + np.arange(_U32.size)].view("<u4")[:, 0]
    ends = starts + _U32.size + lengths
    return bool((ends[:-1] == starts[1:]).all() and ends[-1] == len(values))


def _merged(inputs: tuple[_Run, ...]):
    """The entries of `inputs` (oldest first) as one sorted run, the
    newest of equal keys kept, a slice at a time.

    The inputs are sorted, so they are cut at common keys and each cut
    is merged by itself: every `SLICE`-th key of every input is a cut,
    so no input has `SLICE` entries between two cuts, and a merge holds
    `len(inputs) * SLICE` entries in memory (three times over: the
    slices, their order, the output) however large its level is. The
    inputs stay memory-mapped; nothing else of them is copied."""
    samples = np.concatenate([run.entries()["key"][SLICE::SLICE] for run in inputs])
    samples.sort()
    raw = samples.tobytes()  # an item of dtype S loses its trailing zeros
    cuts = sorted({raw[i : i + KEY_LEN] for i in range(0, len(raw), KEY_LEN)})
    at = [0] * len(inputs)
    for cut in [*cuts, None]:
        to = [run.count if cut is None else run.lower_bound(cut) for run in inputs]
        # newest first and a stable sort: of equal keys the newest leads
        part = np.concatenate(
            [run.entries()[a:b] for run, a, b in zip(inputs[::-1], at[::-1], to[::-1])]
        )
        at = to
        order = np.argsort(part["key"], kind="stable")
        keys = part["key"][order]
        first = np.ones(len(keys), dtype=bool)
        first[1:] = keys[1:] != keys[:-1]
        yield part[order[first]]


def _due(runs: tuple[_Run, ...]) -> list[tuple[int, int]]:
    """The merges that are due, each a slice of `runs` (oldest first).

    Only neighbours in age merge, so "newest first" stays true of the
    list. Walking from the oldest: the largest tier among the runs left
    makes a level, which reaches up to the last run of that tier and
    takes in the smaller runs between (Lucene's log merge policy); a
    level of `FAN_IN` runs or more is due, whole. Tiers fall from one
    level to the next, so a settled list holds under `FAN_IN` runs a
    tier.
    """
    tiers = [_tier(run.count) for run in runs]
    due = []
    lo = 0
    while lo < len(runs):
        top = max(tiers[lo:])
        hi = len(tiers) - tiers[::-1].index(top)
        if hi - lo >= FAN_IN:
            due.append((lo, hi))
        lo = hi
    return due


def _plan(runs: tuple[_Run, ...]) -> tuple[int, int] | None:
    """The next merge: of those due, the one with the fewest entries."""
    return min(
        _due(runs),
        key=lambda level: sum(run.count for run in runs[level[0] : level[1]]),
        default=None,
    )
