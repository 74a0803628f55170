"""The `DB` seam's write batch (`db/kv.py`, reference `tmlibs/db`
`Batch`), over both backends: the suite runs almost wholly on `MemDB`,
so its batch has to be as atomic as `SQLiteDB`'s; and what `SQLiteDB`
promises of a transaction (one commit, `synchronous=FULL`, counted).
"""

from __future__ import annotations

import os
import sqlite3
import sys
import threading
import time

import pytest

from tendermint_tpu.db.kv import MemDB, SQLiteDB
from tendermint_tpu.telemetry import REGISTRY

COMMITS = "tendermint_db_commits_total"


class Opener:
    """Opens the backend under test, and opens it again: a new
    connection to the same file for SQLite (after a `close()`, as after
    a restart), the same object for `MemDB`, which has no other life."""

    def __init__(self, backend: str, tmp_path) -> None:
        self.backend = backend
        self.path = str(tmp_path / "batchtest.db")
        self.db = None

    def open(self):
        if self.backend == "sqlite":
            self.db = SQLiteDB(self.path)
        elif self.db is None:
            self.db = MemDB()
        return self.db

    def reopen(self):
        self.db.close()
        return self.open()


@pytest.fixture(params=["memdb", "sqlite"])
def opener(request, tmp_path):
    o = Opener(request.param, tmp_path)
    yield o
    if o.db is not None:
        o.db.close()


def commits(db_name: str = "batchtest") -> float:
    """`tendermint_db_commits_total{db=db_name}` as it stands (0 before
    that file's first write; the registry is the process's)."""
    return sum(
        s["value"]
        for s in REGISTRY.to_dict()[COMMITS]["series"]
        if s["labels"]["db"] == db_name
    )


class TestBatch:
    def test_nothing_is_visible_before_write_and_everything_after(self, opener):
        db = opener.open()
        batch = db.batch()
        for i in range(5):
            batch.set(b"k%d" % i, b"v%d" % i)
        assert [db.get(b"k%d" % i) for i in range(5)] == [None] * 5
        batch.write()
        assert [db.get(b"k%d" % i) for i in range(5)] == [b"v%d" % i for i in range(5)]
        assert [k for k, _ in db.iterate(b"k")] == [b"k%d" % i for i in range(5)]

    def test_the_last_write_of_a_key_wins(self, opener):
        db = opener.open()
        db.set(b"gone", b"old")
        db.set(b"back", b"old")
        batch = db.batch()
        batch.set(b"gone", b"new")
        batch.delete(b"gone")
        batch.delete(b"back")
        batch.set(b"back", b"new")
        batch.set(b"twice", b"1")
        batch.set(b"twice", b"2")
        batch.delete(b"never-there")
        batch.write_sync()
        assert db.get(b"gone") is None
        assert db.get(b"back") == b"new"
        assert db.get(b"twice") == b"2"
        assert not db.has(b"never-there")

    @pytest.mark.parametrize("how", ["write", "write_sync"])
    def test_a_written_batch_is_read_back_after_close_and_reopen(self, opener, how):
        db = opener.open()
        db.set(b"dropped", b"x")
        batch = db.batch()
        batch.set(b"a", b"1")
        batch.set(b"b", bytes(range(256)) * 64)
        batch.delete(b"dropped")
        getattr(batch, how)()
        db = opener.reopen()
        assert db.get(b"a") == b"1"
        assert db.get(b"b") == bytes(range(256)) * 64
        assert db.get(b"dropped") is None

    def test_an_empty_batch_writes_nothing_and_a_written_one_is_empty_again(self, opener):
        db = opener.open()
        before = commits()
        db.batch().write()
        db.batch().write_sync()
        assert commits() == before
        batch = db.batch()
        batch.set(b"once", b"1")
        batch.write()
        db.delete(b"once")
        batch.write()  # holds nothing now: must not bring the row back
        assert db.get(b"once") is None

    def test_set_set_sync_and_delete_keep_their_meaning(self, opener):
        db = opener.open()
        db.set(b"a", b"1")
        db.set_sync(b"b", b"2")
        db.set(b"a", b"3")
        assert (db.get(b"a"), db.get(b"b")) == (b"3", b"2")
        db.delete(b"a")
        db.delete(b"a")
        assert db.get(b"a") is None and db.has(b"b")
        db = opener.reopen()
        assert db.get(b"a") is None and db.get(b"b") == b"2"

    def test_a_reader_sees_all_of_a_batch_or_none_of_it(self, opener):
        """Two keys always written together, to the same value: a reader
        on another thread that gets one then the other must never find
        the second OLDER than the first (it may be newer: a whole batch
        can land between its two reads)."""
        db = opener.open()
        stop = threading.Event()
        torn: list = []
        reads = [0]

        def reader():
            while not stop.is_set():
                first, second = db.get(b"pair/0"), db.get(b"pair/1")
                reads[0] += 1
                if int(second or b"0") < int(first or b"0"):
                    torn.append((first, second))
                    return

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=reader) for _ in range(3)]
        try:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 1.5
            n = 0
            while time.monotonic() < deadline and not torn:
                n += 1
                batch = db.batch()
                # written in the order the reader reads: were the rows to
                # land one by one, it would catch pair/0 ahead of pair/1
                batch.set(b"pair/0", b"%d" % n)
                batch.set(b"filler/%d" % (n % 7), b"x" * 512)
                batch.set(b"pair/1", b"%d" % n)
                batch.write()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not torn, torn
        assert n > 20 and reads[0] > 20


class TestSQLiteTransactions:
    def test_every_connection_is_wal_with_synchronous_full(self, tmp_path):
        for name in ("blockstore", "state", "txindex"):
            db = SQLiteDB(str(tmp_path / f"{name}.db"))
            try:
                assert db._conn.execute("PRAGMA synchronous").fetchone()[0] == 2
                assert db._conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
            finally:
                db.close()

    def test_one_count_a_transaction_under_the_files_name(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "counted.db"))
        try:
            before = commits("counted")
            db.set(b"a", b"1")
            db.set_sync(b"b", b"2")
            db.delete(b"a")
            assert commits("counted") - before == 3
            batch = db.batch()
            for i in range(100):
                batch.set(b"row%d" % i, b"v")
            batch.delete(b"b")
            batch.write_sync()
            assert commits("counted") - before == 4
        finally:
            db.close()

    def test_a_write_is_committed_when_its_call_returns(self, tmp_path):
        """No transaction stays open on the shared connection across
        calls: another connection to the file reads every write the
        moment its call is back, with no checkpoint and no close."""
        path = str(tmp_path / "seen.db")
        db = SQLiteDB(path)
        other = sqlite3.connect(path)
        try:
            def seen(key: bytes):
                row = other.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
                return row[0] if row else None

            db.set(b"a", b"1")
            assert seen(b"a") == b"1"
            batch = db.batch()
            batch.set(b"b", b"2")
            batch.delete(b"a")
            assert seen(b"b") is None
            batch.write_sync()
            assert (seen(b"a"), seen(b"b")) == (None, b"2")
            assert not db._conn.in_transaction
        finally:
            other.close()
            db.close()

    def test_a_failed_batch_leaves_no_half_transaction_behind(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "failed.db"))
        try:
            db.set(b"kept", b"1")
            with pytest.raises(sqlite3.Error):
                # a row sqlite3 cannot bind, after one it has taken
                db._apply({b"half": b"x", b"bad": _Unstorable()})
            assert not db._conn.in_transaction
            db.set(b"next", b"2")
            assert db.get(b"half") is None
            assert (db.get(b"kept"), db.get(b"next")) == (b"1", b"2")
        finally:
            db.close()

    def test_close_leaves_no_wal_behind_and_may_be_called_twice(self, tmp_path):
        path = str(tmp_path / "closed.db")
        db = SQLiteDB(path)
        batch = db.batch()
        for i in range(50):
            batch.set(b"k%d" % i, b"v" * 1000)
        batch.write()
        db.close()
        db.close()
        assert not os.path.exists(path + "-wal") or os.path.getsize(path + "-wal") == 0
        again = SQLiteDB(path)
        try:
            assert again.get(b"k49") == b"v" * 1000
        finally:
            again.close()


class _Unstorable:
    """A value sqlite3 cannot bind."""
