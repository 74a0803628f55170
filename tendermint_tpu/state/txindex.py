"""Transaction indexing (reference `state/txindex/`).

Each tx's execution result keyed by tx hash, batched per block
(reference `kv/kv.go:17-60`, batch built in
`state/execution.go:279-293`). `RunTxIndexer` is the index of a node
that keeps files: a log of sorted runs in a directory of its own
(`db/runlog.py`, the role LevelDB has in the reference). `KVTxIndexer`
is the same index over a `DB` a caller provides (`MemDB` in tests);
`NullTxIndexer` is the disabled default.

What the index promises (`db/kv.py`'s header has the block's other
three writes). When `add_batch` returns every row of the block is on
disk, under one fsync; a crash leaves all of a block's rows or none; a
`/tx` reader on another thread sees all of them or none. Nothing
acknowledges the index: `add_batch` returns before the state is saved
and before the app commits, and nothing reads the index back. After a
restart `/tx` answers for every block whose `add_batch` returned. The
block a crash caught between the store's watermark and `add_batch`
stays unindexed: the handshake replays it without an indexer
(`consensus/replay.py`), as it did when the index was a SQLite file.
"""

from __future__ import annotations

import json
import os
import pathlib
import sqlite3
import threading
from contextlib import nullcontext
from dataclasses import dataclass

from tendermint_tpu.abci.types import Result
from tendermint_tpu.db.kv import DB
from tendermint_tpu.db.runlog import RunLog
from tendermint_tpu.types.tx import tx_hash


@dataclass
class TxResult:
    """Where and how a tx executed (reference `types.TxResult`)."""

    height: int
    index: int
    tx: bytes
    result: Result

    def to_json(self) -> bytes:
        return json.dumps(
            {
                "height": self.height,
                "index": self.index,
                "tx": self.tx.hex(),
                "code": self.result.code,
                "data": self.result.data.hex(),
                "log": self.result.log,
            },
            sort_keys=True,
        ).encode()

    @classmethod
    def from_json(cls, raw: bytes) -> "TxResult":
        d = json.loads(raw.decode())
        return cls(
            height=d["height"],
            index=d["index"],
            tx=bytes.fromhex(d["tx"]),
            result=Result(d["code"], bytes.fromhex(d["data"]), d["log"]),
        )


_UNTIMED = nullcontext()


class TxIndexer:
    def add_batch(self, block, abci_responses, stage=None) -> None:
        """Index a block's txs. `stage`, when given, is `apply_block`'s
        stopwatch: `stage("index_rows")` is held around building the
        rows, apart from the write that follows."""
        raise NotImplementedError

    def get(self, tx_hash: bytes) -> TxResult | None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class NullTxIndexer(TxIndexer):
    """Indexing disabled (reference `null.TxIndex`)."""

    def add_batch(self, block, abci_responses, stage=None) -> None:
        pass

    def get(self, tx_hash: bytes) -> TxResult | None:
        return None


def _rows(block, abci_responses, stage=None) -> dict[bytes, bytes]:
    """A block's index rows by tx hash; of a tx that is in the block
    twice the later one stays. Built under `stage("index_rows")` where
    the caller has a stopwatch."""
    height = block.header.height
    rows = {}
    with stage("index_rows") if stage else _UNTIMED:
        for i, tx in enumerate(block.data.txs):
            tx = bytes(tx)
            rows[tx_hash(tx)] = TxResult(
                height=height, index=i, tx=tx, result=abci_responses.deliver_tx[i]
            ).to_json()
    return rows


class KVTxIndexer(TxIndexer):
    def __init__(self, db: DB) -> None:
        self._db = db

    def add_batch(self, block, abci_responses, stage=None) -> None:
        batch = self._db.batch()
        for key, row in _rows(block, abci_responses, stage).items():
            batch.set(b"tx:" + key, row)
        batch.write()

    def get(self, tx_hash: bytes) -> TxResult | None:
        raw = self._db.get(b"tx:" + tx_hash)
        return TxResult.from_json(raw) if raw is not None else None


class RunTxIndexer(TxIndexer):
    """The index under `<db_dir>/txindex/`. A data directory from before
    it holds a `txindex.db` (`KVTxIndexer` over `SQLiteDB`): a hash the
    run log does not have is looked up there, and that file is never
    written again."""

    def __init__(self, db_dir: str) -> None:
        self._log = RunLog(os.path.join(db_dir, "txindex"))
        old = os.path.join(db_dir, "txindex.db")
        self._old = _OldIndexFile(old) if os.path.exists(old) else None

    def add_batch(self, block, abci_responses, stage=None) -> None:
        self._log.append(block.header.height, _rows(block, abci_responses, stage))

    def get(self, tx_hash: bytes) -> TxResult | None:
        raw = self._log.get(tx_hash)
        if raw is None and self._old is not None:
            raw = self._old.get(b"tx:" + tx_hash)
        return TxResult.from_json(raw) if raw is not None else None

    def close(self) -> None:
        self._log.close()
        if self._old is not None:
            self._old.close()


class _OldIndexFile:
    """`SQLiteDB`'s one table, opened read-only."""

    def __init__(self, path: str) -> None:
        self._conn = sqlite3.connect(
            pathlib.Path(path).resolve().as_uri() + "?mode=ro",
            uri=True,
            check_same_thread=False,
        )
        self._lock = threading.Lock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            row = self._conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
        return row[0] if row else None

    def close(self) -> None:
        with self._lock:
            self._conn.close()
