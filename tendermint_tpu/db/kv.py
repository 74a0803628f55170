"""Minimal ordered key-value store interface + backends.

Mirrors the `dbm.DB` seam in the reference (`tmlibs/db`): Get/Set/Delete
with synchronous variants, a write `Batch`, and ordered iteration;
consumers are the block store, state DB, address book, and the tx index
of a node that is given its databases (`MemDB` in tests).

What is durable when. A write is one transaction: `set`, `set_sync` and
`delete` are a transaction of one row, `Batch.write` / `write_sync` a
transaction of all the batch's rows, applied under the database's lock,
so a reader on another thread sees all of a batch or none of it and a
crash leaves all of it or none. On `SQLiteDB` a transaction costs one
fsync of the WAL and is on disk when the call returns. A fast-synced or
committed block has three acknowledged points, in this order, one WAL
fsync each and atomic per block: the block store's watermark (with the
block's rows, one batch), the ABCI responses (`set_sync`, before the
app's commit), the state (with its validators pointer, one batch).

The tx index is a fourth durable write that nothing acknowledges, and
on a node that keeps files it is not a `SQLiteDB`: it is a log of
sorted runs in a directory of its own, `<db dir>/txindex/`
(`db/runlog.py`, built by `state/txindex.py` `RunTxIndexer`). When
`add_batch` returns, every row of the block is on disk under one fsync
of one appended record; a crash leaves all of a block's rows or none; a
`/tx` reader on another thread sees all or none. `add_batch` returns
between the responses and the state, before the app's commit, and no
one reads the index back before going on. After a restart `/tx` answers
for every block whose `add_batch` returned; a block the crash caught
before that stays unindexed, since the handshake replays it without an
indexer (`consensus/replay.py`). A `txindex.db` from before the run
log keeps answering for its rows, read-only, and so does every row
written as JSON before values were packed: a value's first byte says
which it is (`state/txindex.py`).
"""

from __future__ import annotations

import os
import sqlite3
import threading
from typing import Iterator

from tendermint_tpu.telemetry import TRACER
from tendermint_tpu.telemetry.metrics import (
    DB_COMMIT_CPU_SECONDS,
    DB_COMMIT_SECONDS,
    DB_COMMITS,
    DB_READS,
)

# keys a `get_many` statement asks for at once: one result column and one
# bound variable a key, under SQLite's limits on both (2,000 and 999)
_GET_MANY_CHUNK = 500


class CommitClock:
    """One store's durable writes, counted and timed where they happen:
    `with clock.stage() as st:` around exactly the write (the `db.commit`
    stage: wall and CPU, in the profiler's host plane), then
    `clock.add(st)` once it has returned, so
    `tendermint_db_commit_seconds_count{db}` is
    `tendermint_db_commits_total{db}`. A write that raised is neither."""

    def __init__(self, db: str) -> None:
        self._commits = DB_COMMITS.labels(db=db)
        self._seconds = DB_COMMIT_SECONDS.labels(db=db)
        self._cpu_seconds = DB_COMMIT_CPU_SECONDS.labels(db=db)
        self._cpu_seconds.inc(0)

    @staticmethod
    def stage():
        return TRACER.stage("db.commit")

    def add(self, stage) -> None:
        self._commits.inc()
        self._seconds.observe(stage.seconds)
        self._cpu_seconds.inc(stage.cpu_seconds)


class DB:
    """Interface: bytes -> bytes with ordered iteration."""

    def get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def get_many(self, keys: list[bytes]) -> list[bytes | None]:
        """The values of `keys`, in their order, None for an absent
        key, all read in ONE critical section: what `_apply` committed
        and nothing else, so never half of a batch another thread is
        writing."""
        raise NotImplementedError

    def set(self, key: bytes, value: bytes) -> None:
        self._apply({bytes(key): bytes(value)})

    def set_sync(self, key: bytes, value: bytes) -> None:
        """`set`, durable when it returns (reference `SetSync`)."""
        self.set(key, value)

    def delete(self, key: bytes) -> None:
        self._apply({bytes(key): None})

    def batch(self) -> "Batch":
        return Batch(self)

    def _apply(self, rows: dict[bytes, bytes | None]) -> None:
        """Every row (value None: delete the key) in ONE critical
        section and one transaction: all of them or none, to a reader
        and to a crash."""
        raise NotImplementedError

    def has(self, key: bytes) -> bool:
        return self.get(key) is not None

    def iterate(self, prefix: bytes = b"") -> Iterator[tuple[bytes, bytes]]:
        raise NotImplementedError

    def close(self) -> None:
        pass


class Batch:
    """Write batch (reference `tmlibs/db` `Batch`): `set` and `delete`
    buffer in memory, the last write of a key wins, and `write` /
    `write_sync` hand the rows to the database as one transaction.
    Nothing is visible, and no transaction is open, before that."""

    def __init__(self, db: DB) -> None:
        self._db = db
        self._rows: dict[bytes, bytes | None] = {}

    def set(self, key: bytes, value: bytes) -> None:
        self._rows[bytes(key)] = bytes(value)

    def delete(self, key: bytes) -> None:
        self._rows[bytes(key)] = None

    def write(self) -> None:
        rows, self._rows = self._rows, {}
        if rows:
            self._db._apply(rows)

    def write_sync(self) -> None:
        """`write`, durable when it returns (reference `WriteSync`)."""
        self.write()


class MemDB(DB):
    """In-memory store (reference memdb) — tests and replay fakes."""

    def __init__(self) -> None:
        self._data: dict[bytes, bytes] = {}
        self._lock = threading.Lock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            return self._data.get(bytes(key))

    def get_many(self, keys: list[bytes]) -> list[bytes | None]:
        with self._lock:
            return [self._data.get(bytes(key)) for key in keys]

    def _apply(self, rows: dict[bytes, bytes | None]) -> None:
        with self._lock:
            for key, value in rows.items():
                if value is None:
                    self._data.pop(key, None)
                else:
                    self._data[key] = value

    def iterate(self, prefix: bytes = b"") -> Iterator[tuple[bytes, bytes]]:
        with self._lock:
            items = sorted(
                (k, v) for k, v in self._data.items() if k.startswith(prefix)
            )
        yield from items


class SQLiteDB(DB):
    """SQLite-backed store — the persistent backend (goleveldb's role).

    WAL journal mode with `PRAGMA synchronous=FULL`, set here and not
    left to the library's build: a commit returns only after the WAL is
    fsynced, so by SQLite's own contract every transaction that returned
    survives a crash or a power loss, `set` and `set_sync` alike (the
    reference distinguishes SetSync at the same call sites; here both
    pay the one fsync). No write checkpoints: copying the WAL into the
    database file adds no durability, it bounds the WAL's size, and
    SQLite's automatic checkpoint (1,000 pages) and the one it makes as
    the last connection closes do that. `tendermint_db_commits_total{db}`
    counts the transactions and `tendermint_db_commit_seconds{db}` times
    their `commit()` (`CommitClock`); `tendermint_db_reads_total{db}`
    counts the reads, one a `get` and one a `get_many` whatever its keys.

    A read is one statement that answers with ONE row: the `sqlite3`
    module lets go of the interpreter lock around every step of a
    statement, and beside busy threads each step may have to wait a
    switch interval or more to get it back, under `_lock`. So `get_many`
    asks for its keys as the columns of one row (a scalar subquery a
    key), not as a row a key.
    """

    def __init__(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        name = os.path.splitext(os.path.basename(path))[0]
        self._commits = CommitClock(name)
        self._reads = DB_READS.labels(db=name)
        with self._lock:
            self._conn.execute("PRAGMA journal_mode=WAL")
            self._conn.execute("PRAGMA synchronous=FULL")
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)"
            )
            self._conn.commit()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            row = self._conn.execute(
                "SELECT v FROM kv WHERE k = ?", (bytes(key),)
            ).fetchone()
        self._reads.inc()
        return row[0] if row else None

    def get_many(self, keys: list[bytes]) -> list[bytes | None]:
        keys = [bytes(key) for key in keys]
        values: list[bytes | None] = []
        with self._lock:
            for i in range(0, len(keys), _GET_MANY_CHUNK):
                chunk = keys[i : i + _GET_MANY_CHUNK]
                sql = "SELECT " + ", ".join(
                    ["(SELECT v FROM kv WHERE k = ?)"] * len(chunk)
                )
                values.extend(self._conn.execute(sql, chunk).fetchone())
        self._reads.inc()
        return values

    def _apply(self, rows: dict[bytes, bytes | None]) -> None:
        sets = [(k, v) for k, v in rows.items() if v is not None]
        deletes = [(k,) for k, v in rows.items() if v is None]
        with self._lock:
            try:
                if sets:
                    self._conn.executemany(
                        "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)", sets
                    )
                if deletes:
                    self._conn.executemany("DELETE FROM kv WHERE k = ?", deletes)
                with self._commits.stage() as commit:
                    self._conn.commit()
            except BaseException:
                # never leave half a transaction open on the shared
                # connection: the next caller's commit would land it
                self._conn.rollback()
                raise
        self._commits.add(commit)

    def iterate(self, prefix: bytes = b"") -> Iterator[tuple[bytes, bytes]]:
        with self._lock:
            if prefix:
                hi = bytes(prefix[:-1] + bytes([prefix[-1] + 1])) if prefix[-1] < 255 else None
                if hi is not None:
                    rows = self._conn.execute(
                        "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k",
                        (bytes(prefix), hi),
                    ).fetchall()
                else:
                    rows = self._conn.execute(
                        "SELECT k, v FROM kv WHERE k >= ? ORDER BY k", (bytes(prefix),)
                    ).fetchall()
                    rows = [(k, v) for k, v in rows if bytes(k).startswith(prefix)]
            else:
                rows = self._conn.execute("SELECT k, v FROM kv ORDER BY k").fetchall()
        for k, v in rows:
            yield bytes(k), bytes(v)

    def close(self) -> None:
        with self._lock:
            # SQLite checkpoints and drops the WAL as the file's last
            # connection closes
            self._conn.close()


def db_provider(name: str, backend: str, db_dir: str) -> DB:
    """Factory matching the reference's node DBProvider seam
    (`node/node.go:59-72`)."""
    if backend == "memdb":
        return MemDB()
    if backend == "sqlite":
        return SQLiteDB(os.path.join(db_dir, f"{name}.db"))
    raise ValueError(f"unknown db backend {backend!r}")
