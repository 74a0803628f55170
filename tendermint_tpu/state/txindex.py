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
import struct
import threading
from contextlib import nullcontext
from dataclasses import dataclass

import numpy as np

from tendermint_tpu.abci.types import Result
from tendermint_tpu.db.kv import DB
from tendermint_tpu.db.runlog import RunLog
from tendermint_tpu.telemetry.metrics import TXINDEX_VALUES_READ
from tendermint_tpu.types.tx import tx_hash

_PACKED = 1  # the form byte
_VALUE = struct.Struct("<BQIIIII")
# the same behind the length u32 that a record of the run log wants
# before each value: one `pack` a row
_FRAMED = struct.Struct("<IBQIIIII")
_LENGTH = _FRAMED.size - _VALUE.size


@dataclass
class TxResult:
    """Where and how a tx executed (reference `types.TxResult`)."""

    height: int
    index: int
    tx: bytes
    result: Result

    @classmethod
    def decode(cls, raw: bytes) -> "TxResult":
        """A stored value of either form, told apart by its first byte."""
        if raw[:1] == b"{":
            TXINDEX_VALUES_READ.labels(form="json").inc()
            d = json.loads(raw)
            return cls(
                height=d["height"],
                index=d["index"],
                tx=bytes.fromhex(d["tx"]),
                result=Result(d["code"], bytes.fromhex(d["data"]), d["log"]),
            )
        form, height, index, code, n_tx, n_data, n_log = _VALUE.unpack_from(raw)
        data = _VALUE.size + n_tx
        log = data + n_data
        if form != _PACKED or log + n_log != len(raw):
            raise ValueError(f"not a tx index value: form {form}, {len(raw)} bytes")
        TXINDEX_VALUES_READ.labels(form="packed").inc()
        return cls(
            height=height,
            index=index,
            tx=raw[_VALUE.size : data],
            result=Result(code, raw[data:log], raw[log:].decode("utf-8", "surrogatepass")),
        )


_UNTIMED = nullcontext()


class TxIndexer:
    def add_batch(self, block, abci_responses, stage=None) -> None:
        """Index a block's txs. `stage`, when given, is `apply_block`'s
        stopwatch: `stage("index_rows")` is held around building the
        rows (keys, packed values, the run log's value section and its
        pointers), apart from the write that follows."""
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


def _rows(block, abci_responses) -> tuple[list[bytes], list[bytes]]:
    """A block's index rows in the block's order: the keys (tx hashes)
    and, beside each, its packed value behind its length u32."""
    height = block.header.height
    txs = [bytes(tx) for tx in block.data.txs]
    pack, body = _FRAMED.pack, _VALUE.size
    framed = []
    for i, (tx, result) in enumerate(zip(txs, abci_responses.deliver_tx, strict=True)):
        data, log = result.data, result.log.encode("utf-8", "surrogatepass")
        n_tx, n_data, n_log = len(tx), len(data), len(log)
        framed.append(
            pack(body + n_tx + n_data + n_log, _PACKED, height, i, result.code, n_tx, n_data, n_log)
            + tx + data + log
        )
    return [tx_hash(tx) for tx in txs], framed


class KVTxIndexer(TxIndexer):
    def __init__(self, db: DB) -> None:
        self._db = db

    def add_batch(self, block, abci_responses, stage=None) -> None:
        with stage("index_rows") if stage else _UNTIMED:
            keys, framed = _rows(block, abci_responses)
        batch = self._db.batch()
        for key, value in zip(keys, framed):  # of a tx twice in the block the later stays
            batch.set(b"tx:" + key, value[_LENGTH:])
        batch.write()

    def get(self, tx_hash: bytes) -> TxResult | None:
        raw = self._db.get(b"tx:" + tx_hash)
        return TxResult.decode(raw) if raw is not None else None


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
        with stage("index_rows") if stage else _UNTIMED:
            keys, framed = _rows(block, abci_responses)
            sizes = np.fromiter(map(len, framed), dtype=np.int64, count=len(framed))
            rows = b"".join(keys), b"".join(framed), np.cumsum(sizes) - sizes
        self._log.append(block.header.height, *rows)

    def get(self, tx_hash: bytes) -> TxResult | None:
        raw = self._log.get(tx_hash)
        if raw is None and self._old is not None:
            raw = self._old.get(b"tx:" + tx_hash)
        return TxResult.decode(raw) if raw is not None else None

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
