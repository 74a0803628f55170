"""The tx index's run log (`db/runlog.py`, `state/txindex.py`
`RunTxIndexer`): what `/tx` answers, what a crash leaves, what a merge
may and may not do, and what a block costs in bytes. Counts and bytes
only: no time is asserted here.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import struct
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu.abci.types import Result
from tendermint_tpu.db.kv import MemDB, SQLiteDB
from tendermint_tpu.db import runlog
from tendermint_tpu.db.runlog import FAN_IN, RunLog
from tendermint_tpu.state import txindex
from tendermint_tpu.state.txindex import KVTxIndexer, RunTxIndexer, TxResult
from tendermint_tpu.telemetry.metrics import (
    TXINDEX_BYTES_WRITTEN,
    TXINDEX_MERGES,
    TXINDEX_PROBES,
    TXINDEX_RUNS,
    TXINDEX_VALUES_READ,
)
from tendermint_tpu.types.tx import tx_hash
from tests.test_db_batch import commits


def _block(height: int, txs: list[bytes]):
    """What `add_batch` reads of a block and its responses."""
    block = SimpleNamespace(
        header=SimpleNamespace(height=height), data=SimpleNamespace(txs=txs)
    )
    responses = SimpleNamespace(
        deliver_tx=[Result(i % 3, b"d%d" % i, "log %d" % height) for i in range(len(txs))]
    )
    return block, responses


def _old_row(height: int, index: int, tx: bytes, result: Result) -> bytes:
    """A row as every commit before the packed form wrote it (what
    `TxResult.to_json` was): the test holds the old bytes, not the old
    code; `test_the_old_rows_are_the_parents_bytes` pins it to literals."""
    return json.dumps(
        {
            "height": height, "index": index, "tx": tx.hex(),
            "code": result.code, "data": result.data.hex(), "log": result.log,
        },
        sort_keys=True,
    ).encode()


def _reads() -> dict[str, float]:
    return {form: TXINDEX_VALUES_READ.labels(form=form).value for form in ("packed", "json")}


def _rows(height: int, n: int, width: int = 140) -> dict[bytes, bytes]:
    """`n` rows under distinct 32-byte keys, cheaply."""
    return {
        hashlib.sha256(b"%d/%d" % (height, i)).digest(): (b"%d:%d:" % (height, i)).ljust(width, b".")
        for i in range(n)
    }


def _put(log: RunLog, height: int, rows: dict[bytes, bytes] | list[tuple[bytes, bytes]]) -> None:
    """`append` as its one caller calls it: the keys end to end, the
    values each behind its length u32, and where those lengths lie."""
    pairs = list(rows.items()) if isinstance(rows, dict) else rows
    framed = [struct.pack("<I", len(v)) + v for _, v in pairs]
    sizes = np.array([len(f) for f in framed], dtype=np.int64)
    log.append(
        height, b"".join(k for k, _ in pairs), b"".join(framed), np.cumsum(sizes) - sizes
    )


def _written() -> float:
    return sum(TXINDEX_BYTES_WRITTEN.labels(kind=k).value for k in ("append", "merge"))


def _live(log: RunLog) -> int:
    """Live runs: what a `get` of an absent key probes."""
    return len(log._runs)


def _settled(log: RunLog, timeout: float) -> bool:
    """Wait until no merge is due or running."""
    with log._cond:
        return log._cond.wait_for(
            lambda: log._merger is None and runlog._plan(log._runs) is None, timeout
        )


def _files(path) -> set[str]:
    return set(os.listdir(path))


class TestAnswers:
    def test_every_get_equals_the_kv_indexers_through_several_merges(self, tmp_path):
        """Blocks of 3 rows and of 10,000, some txs repeated across
        heights and inside a block: `INSERT OR REPLACE`'s answers."""
        rnd = random.Random(37)
        runs, kv = RunTxIndexer(str(tmp_path)), KVTxIndexer(MemDB())
        merges = TXINDEX_MERGES.value
        asked: list[bytes] = []
        repeated = [b"again-%d=v" % i for i in range(40)]
        for height in range(1, 41):
            if height % 4 == 0:
                txs = [b"k%07d=%d" % (i, height) for i in range(10_000)]
                asked += rnd.sample(txs, 200)
            else:
                txs = [b"small-%d-%d=v" % (height, i) for i in range(3)]
                asked += txs
            txs += rnd.sample(repeated, 2)
            if height % 5 == 0:
                txs.append(txs[0])  # twice in one block: the later index stays
            for indexer in (runs, kv):
                indexer.add_batch(*_block(height, txs))
        assert _settled(runs._log, 30)
        assert TXINDEX_MERGES.value - merges >= 3
        for tx in asked + repeated:
            got, want = runs.get(tx_hash(tx)), kv.get(tx_hash(tx))
            assert got == want
        assert sum(runs.get(tx_hash(tx)) is not None for tx in repeated) > 30
        assert runs.get(tx_hash(b"never indexed")) is None
        assert runs.get(b"short") is None  # `/tx?hash=` takes any hex
        runs.close()

    def test_a_lookup_counts_the_runs_it_probed_newest_first(self, tmp_path):
        log = RunLog(str(tmp_path / "txindex"))
        for height in (1, 2, 3):
            _put(log, height, {b"k" * 32: b"v%d" % height, bytes([height]) * 32: b"own"})
        before = TXINDEX_PROBES.value
        assert log.get(b"k" * 32) == b"v3"  # the newest run answers
        assert log.get(b"\x01" * 32) == b"own"  # the oldest: three probes
        assert log.get(b"z" * 32) is None
        after = TXINDEX_PROBES.value
        assert after["count"] - before["count"] == 3
        assert after["sum"] - before["sum"] == 1 + 3 + 3
        assert _live(log) == TXINDEX_RUNS.value == 3
        log.close()

    def test_an_empty_block_writes_nothing(self, tmp_path):
        runs = RunTxIndexer(str(tmp_path))
        before = commits("txindex"), _written()
        runs.add_batch(*_block(1, []))
        assert (commits("txindex"), _written()) == before
        assert os.path.getsize(tmp_path / "txindex" / "data") == 0
        runs.close()

    def test_of_equal_keys_in_one_block_the_last_stays(self, tmp_path):
        log = RunLog(str(tmp_path / "txindex"))
        a, b, c = b"a" * 32, b"b" * 32, b"c" * 32
        _put(log, 1, [(b, b"b1"), (a, b"a1"), (b, b"b2"), (c, b"c1"), (b, b"b3"), (a, b"a2")])
        assert [log.get(k) for k in (a, b, c)] == [b"a2", b"b3", b"c1"]
        assert log._runs[0].count == 3  # one entry a key; the dead values stay in the record
        log.close()
        log = RunLog(str(tmp_path / "txindex"))
        assert [log.get(k) for k in (a, b, c)] == [b"a2", b"b3", b"c1"]
        log.close()

    @pytest.mark.parametrize(
        "keys, values, starts",
        [
            (b"k" * 31, b"\x01\0\0\0v", [0]),  # a short key
            (b"k" * 64, b"\x01\0\0\0v", [0]),  # a key without a value
            (b"k" * 32, b"\x02\0\0\0v", [0]),  # a length past the end
            (b"k" * 32, b"\x01\0\0\0vw", [0]),  # bytes after the last value
            (b"k" * 64, b"\x01\0\0\0v\x01\0\0\0w", [0, 4]),  # a start inside a value
            (b"k" * 64, b"\x01\0\0\0v\x01\0\0\0w", [5, 0]),  # not in the keys' order
            (b"k" * 32, b"\x01\0\0\0v", [2]),
            (b"k" * 32, b"\x01\0", [0]),  # half a length
        ],
    )
    def test_rows_whose_lengths_and_starts_disagree_are_refused_whole(
        self, tmp_path, keys, values, starts
    ):
        """`get` trusts a stored length: `append` writes none it has not checked."""
        log = RunLog(str(tmp_path / "txindex"))
        with pytest.raises(ValueError):
            log.append(1, keys, values, np.array(starts))
        assert os.path.getsize(tmp_path / "txindex" / "data") == 0 and _live(log) == 0
        log.close()

    def test_a_value_longer_than_one_read_comes_back_whole(self, tmp_path):
        log = RunLog(str(tmp_path / "txindex"))
        rows = {b"a" * 32: b"x" * 100_000, b"b" * 32: b"", b"c" * 32: b"y" * 1024}
        _put(log, 1, rows)
        assert {k: log.get(k) for k in rows} == rows
        log.close()


class TestACrashAtTheAppend:
    N = 5

    def _five_blocks(self, path) -> tuple[dict[int, dict[bytes, bytes]], list[int]]:
        log = RunLog(path)
        blocks, ends = {}, []
        for height in range(1, self.N + 1):
            blocks[height] = _rows(height, 50)
            _put(log, height, blocks[height])
            ends.append(os.path.getsize(os.path.join(path, "data")))
        log.close()
        return blocks, ends

    def _all_but_the_last_answer(self, path, blocks, ends):
        log = RunLog(path)
        try:
            for height, rows in blocks.items():
                want = (lambda v: None) if height == self.N else (lambda v: v)
                assert all(log.get(k) == want(v) for k, v in rows.items())
            # the tail is gone from the file too, and the next block lands whole
            assert os.path.getsize(os.path.join(path, "data")) == ends[-2]
            _put(log, self.N, blocks[self.N])
            assert all(log.get(k) == v for k, v in blocks[self.N].items())
            assert _live(log) == self.N
        finally:
            log.close()

    @pytest.mark.parametrize("cut", [1, 4, 5, 3000, 7500, 9210, 9227])
    def test_a_truncated_tail_is_cut_at_open(self, tmp_path, cut):
        """A record of 50 rows is 24 + 2,000 + 7,200 + 4 bytes: the cut
        falls in the checksum, the values, the keys and the header."""
        path = str(tmp_path / "txindex")
        blocks, ends = self._five_blocks(path)
        assert ends[-1] - ends[-2] == 24 + 50 * 40 + 50 * 144 + 4
        with open(os.path.join(path, "data"), "r+b") as f:
            f.truncate(ends[-1] - cut)
        self._all_but_the_last_answer(path, blocks, ends)

    @pytest.mark.parametrize("back", [1, 5, 4000, 8000, 9210, 9227])
    def test_a_tail_with_a_flipped_byte_is_cut_at_open(self, tmp_path, back):
        path = str(tmp_path / "txindex")
        blocks, ends = self._five_blocks(path)
        with open(os.path.join(path, "data"), "r+b") as f:
            f.seek(ends[-1] - back)
            byte = f.read(1)
            f.seek(ends[-1] - back)
            f.write(bytes([byte[0] ^ 0x40]))
        self._all_but_the_last_answer(path, blocks, ends)

    def test_a_write_that_fails_leaves_none_of_the_block(self, tmp_path, monkeypatch):
        path = str(tmp_path / "txindex")
        log = RunLog(path)
        _put(log, 1, _rows(1, 20))
        size = os.path.getsize(os.path.join(path, "data"))
        real = os.fsync

        def no_space(fd):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(os, "fsync", no_space)
        with pytest.raises(OSError):
            _put(log, 2, _rows(2, 20))
        monkeypatch.setattr(os, "fsync", real)
        assert os.path.getsize(os.path.join(path, "data")) == size
        assert _live(log) == 1 and all(log.get(k) is None for k in _rows(2, 20))
        _put(log, 2, _rows(2, 20))
        assert all(log.get(k) == v for k, v in _rows(2, 20).items())
        log.close()


class TestACrashInAMerge:
    """What is on disk at a step of a merge is copied aside as the step
    begins: the copy is what a crash there leaves."""

    def _crash_at(self, tmp_path, monkeypatch, step: str, blocks: int):
        path, left = str(tmp_path / "txindex"), str(tmp_path / "crashed")
        real = getattr(RunLog, step)

        def copy_then_go_on(self, *args):
            if os.path.isdir(left):
                shutil.rmtree(left)
            shutil.copytree(path, left)
            return real(self, *args)

        monkeypatch.setattr(RunLog, step, copy_then_go_on)
        log = RunLog(path)
        rows = {}
        for height in range(1, blocks + 1):
            rows.update(_rows(height, 3))
            _put(log, height, _rows(height, 3))
            if height % FAN_IN == 0:
                assert _settled(log, 30)
        log.close()
        monkeypatch.setattr(RunLog, step, real)
        return left, rows

    def _every_row_once_and_no_stray_file(self, monkeypatch, left, rows, live: int):
        """What opening recovers, before any new merge moves it."""
        monkeypatch.setattr(RunLog, "_kick", lambda self: None)
        log = RunLog(left)
        try:
            assert all(log.get(k) == v for k, v in rows.items())
            assert _live(log) == live
            assert sum(run.count for run in log._runs) == len(rows)
            keys = {name for name in _files(left) if name.endswith(".keys")}
            assert keys == {run.file for run in log._runs if run.file}
            assert _files(left) - keys <= {"data", "MANIFEST"}
        finally:
            log.close()

    def test_output_written_and_manifest_not_switched(self, tmp_path, monkeypatch):
        """The last merge is the ninth: eight key files into one. Its
        output is on disk and no manifest names it."""
        left, rows = self._crash_at(tmp_path, monkeypatch, "_switch_manifest", 64)
        assert len([n for n in _files(left) if n.endswith(".keys")]) == 9
        self._every_row_once_and_no_stray_file(monkeypatch, left, rows, live=8)

    def test_manifest_switched_and_inputs_not_dropped(self, tmp_path, monkeypatch):
        left, rows = self._crash_at(tmp_path, monkeypatch, "_drop", 64)
        assert len([n for n in _files(left) if n.endswith(".keys")]) == 9
        self._every_row_once_and_no_stray_file(monkeypatch, left, rows, live=1)

    def test_the_first_merge_of_all_has_no_manifest_to_fall_back_on(
        self, tmp_path, monkeypatch
    ):
        left, rows = self._crash_at(tmp_path, monkeypatch, "_switch_manifest", 8)
        assert _files(left) == {"data", "000000000000.keys"}
        self._every_row_once_and_no_stray_file(monkeypatch, left, rows, live=8)

    def test_blocks_appended_while_a_merge_ran_are_found_again(self, tmp_path):
        """The manifest a merge writes lists the runs of its moment; what
        came after lies past `scan_from` and is scanned at open."""
        path = str(tmp_path / "txindex")
        log = RunLog(path)
        rows = {}
        for height in range(1, 3 * FAN_IN + 4):
            rows.update(_rows(height, 3))
            _put(log, height, _rows(height, 3))
        assert _settled(log, 30)
        live = _live(log)
        log.close()
        log = RunLog(path)
        assert _live(log) == live and all(log.get(k) == v for k, v in rows.items())
        log.close()


class TestAMergeInSlices:
    """`_merged` cuts the key space at every `SLICE`-th key of every
    input and merges each cut by itself."""

    def _eight_runs(self, tmp_path, monkeypatch, rows: int):
        monkeypatch.setattr(RunLog, "_kick", lambda self: None)  # merged by hand
        log = RunLog(str(tmp_path / "txindex"))
        want: dict[bytes, bytes] = {}
        for height in range(1, FAN_IN + 1):
            block = _rows(height, rows, width=12)
            # a tenth of a block's keys were the block before's: the newer stays
            for key in list(want)[-rows // 10 :] if height > 1 else ():
                block[key] = b"again at %d" % height
            want.update(block)
            _put(log, height, block)
        return log, want

    @pytest.mark.parametrize("slice_", [7, 64, 1 << 15])
    def test_slices_of_any_size_give_the_one_sorted_run(self, tmp_path, monkeypatch, slice_):
        monkeypatch.setattr(runlog, "SLICE", slice_)
        log, want = self._eight_runs(tmp_path, monkeypatch, 500)
        parts = list(runlog._merged(log._runs))
        assert len(parts) > 4_000 // (slice_ * FAN_IN)
        assert max(map(len, parts)) <= slice_ * FAN_IN
        keys = [bytes(k).ljust(32, b"\0") for part in parts for k in part["key"]]
        assert keys == sorted(want)
        log._merge(0, FAN_IN)
        assert _live(log) == 1 and log._runs[0].count == len(want)
        assert all(log.get(k) == v for k, v in want.items())
        log.close()

    def test_a_merges_memory_is_its_slices_not_its_level(self, tmp_path, monkeypatch):
        """Eight runs of 40,000 entries and more are 13.9 MB of entries.
        Merged 4,096 an input at a time, the most ever allocated is
        three copies of a slice of 8 x 4,096 x 40 B (the slice, its
        sorted keys, the output): 3.9 MB, whatever the level holds."""
        import tracemalloc

        monkeypatch.setattr(runlog, "SLICE", 4_096)
        log, want = self._eight_runs(tmp_path, monkeypatch, 40_000)
        level = sum(run.count for run in log._runs) * runlog.ENTRY.itemsize
        tracemalloc.start()
        try:
            log._merge(0, FAN_IN)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        a_slice = FAN_IN * 4_096 * runlog.ENTRY.itemsize
        assert peak < 4 * a_slice < level / 2
        assert log._runs[0].count == len(want)
        rnd = random.Random(3)
        assert all(log.get(k) == want[k] for k in rnd.sample(sorted(want), 500))
        log.close()


class TestReadersBesideTheWriter:
    def test_a_reader_never_sees_part_of_a_block_nor_loses_a_row(self, tmp_path):
        """Four readers beside one writer, through appends and merges of
        three tiers. A reader walks a block's rows in order: after one
        that is there, every later one is; and a row once seen stays."""
        log = RunLog(str(tmp_path / "txindex"))
        blocks = {h: _rows(h, 5, width=24) for h in range(1, 601)}
        stop, faults = threading.Event(), []

        def read(seed: int) -> None:
            rnd, seen = random.Random(seed), set()
            while not stop.is_set() and not faults:
                height = rnd.randrange(1, len(blocks) + 1)
                there = False
                for key, value in blocks[height].items():
                    got = log.get(key)
                    if got is None and (there or key in seen):
                        faults.append(("lost", height, key.hex()))
                    elif got is not None:
                        if got != value:
                            faults.append(("wrong", height, key.hex()))
                        there = True
                        seen.add(key)

        readers = [threading.Thread(target=read, args=(i,)) for i in range(4)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)
        try:
            for t in readers:
                t.start()
            for height, rows in blocks.items():
                _put(log, height, rows)
            assert _settled(log, 30)
            time.sleep(0.05)
        finally:
            stop.set()
            for t in readers:
                t.join(30)
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in readers)
        assert faults == []
        assert all(log.get(k) == v for rows in blocks.values() for k, v in rows.items())
        assert _live(log) < 3 * FAN_IN
        log.close()


class TestWhatABlockCosts:
    def _append(self, tmp_path, blocks: int, rows: int) -> tuple[float, int]:
        """Bytes a block and the most runs ever live. The merger is given
        its time every `FAN_IN` blocks, so the count is the policy's and
        not the scheduler's: how far `append` lets a merger fall behind
        is `test_an_append_waits_for_a_merger_a_whole_round_behind`."""
        log = RunLog(str(tmp_path / "txindex"))
        written, most = _written(), 0
        for height in range(1, blocks + 1):
            _put(log, height, _rows(height, rows))
            most = max(most, int(TXINDEX_RUNS.value))
            if height % FAN_IN == 0:
                assert _settled(log, 60)
        assert _settled(log, 60)
        assert _live(log) == TXINDEX_RUNS.value
        log.close()
        return (_written() - written) / blocks, most

    def test_sixty_full_blocks_are_under_4_mb_each_and_under_32_runs(self, tmp_path):
        """10,000 rows of 140 bytes: 1.84 MB appended a block and, for
        the 600,000 entries, seven merges of 80,000: 0.37 MB a block."""
        a_block, most = self._append(tmp_path, 60, 10_000)
        assert 1.8e6 < a_block < 4e6
        assert most < 32

    def test_two_thousand_blocks_of_three_rows_stay_under_32_runs(self, tmp_path):
        a_block, most = self._append(tmp_path, 2000, 3)
        assert most < 32
        # 580 B appended a block; every entry merged once a tier, four tiers
        assert a_block < 1200
        assert len(_files(tmp_path / "txindex")) < 32


    def test_an_append_waits_for_a_merger_a_whole_round_behind(self, tmp_path, monkeypatch):
        """The merger is held in its first merge: the appends go on
        until the level that is due holds `2 * FAN_IN` runs, the next
        one waits (its rows durable and visible already), and goes on
        when the merge is done."""
        held, real = threading.Event(), RunLog._merge

        def merge_when_let(self, lo, hi):
            assert held.wait(30)
            real(self, lo, hi)

        monkeypatch.setattr(RunLog, "_merge", merge_when_let)
        log = RunLog(str(tmp_path / "txindex"))
        for height in range(1, 2 * FAN_IN):
            _put(log, height, _rows(height, 3))
        assert _live(log) == 2 * FAN_IN - 1
        last = _rows(2 * FAN_IN, 3)
        appending = threading.Thread(target=_put, args=(log, 2 * FAN_IN, last))
        appending.start()
        appending.join(0.3)
        assert appending.is_alive() and _live(log) == 2 * FAN_IN
        assert all(log.get(k) == v for k, v in last.items())
        held.set()
        appending.join(30)
        assert not appending.is_alive() and _settled(log, 30)
        assert _live(log) < FAN_IN
        log.close()


FULL = [b"k%07d=%d" % (i, 50_000 + i) for i in range(10_000)]  # the mix `full`'s txs


class TestThePackedValue:
    """A row's value: one fixed header and the raw tx, data and log."""

    EDGES = {
        "an_empty_tx": (b"", Result(0, b"", ""), 0),
        "a_tx_over_65535_bytes": (b"\xab" * 70_000, Result(0, b"", ""), 1),
        "data": (b"k=v", Result(0, b"\x00\xff{}" * 9, ""), 2),
        "a_log_that_is_not_ascii": (b"k=v", Result(0, b"", "grüß ✓ \U0001f512 \" \\ \n"), 3),
        "a_code_that_is_not_zero": (b"k=v", Result(4_000_000_000, b"", "refused"), 4),
        "index_0": (b"first=1", Result(1, b"d", "l"), 0),
        "index_9999": (b"last=1", Result(1, b"d", "l"), 9_999),
        "a_tx_that_opens_with_a_brace": (b'{"height": 1}', Result(0, b"{", "{"), 5),
        "every_field_as_large_as_its_word": (b"t" * 300, Result(2**32 - 1, b"d" * 300, "é" * 300), 6),
    }

    @pytest.mark.parametrize("edge", EDGES)
    def test_the_six_fields_come_back_from_both_indexers(self, tmp_path, edge):
        tx, result, index = self.EDGES[edge]
        txs = [b"filler-%d=v" % i for i in range(index)] + [tx]
        block, responses = _block(2**40 + 7, txs)
        responses.deliver_tx[index] = result
        want = TxResult(height=2**40 + 7, index=index, tx=tx, result=result)
        runs, kv = RunTxIndexer(str(tmp_path)), KVTxIndexer(MemDB())
        before = _reads()
        for indexer in (runs, kv):
            indexer.add_batch(block, responses)
            assert indexer.get(tx_hash(tx)) == want
        assert _reads() == {"packed": before["packed"] + 2, "json": before["json"]}
        # one encoder: the run log's value is the KV store's, and its layout is the header's
        raw = kv._db.get(b"tx:" + tx_hash(tx))
        assert raw == runs._log.get(tx_hash(tx)) and raw[:1] not in (b"{", b"")
        log = result.log.encode()
        assert raw == (
            struct.pack(
                "<BQIIIII", 1, 2**40 + 7, index, result.code, len(tx), len(result.data), len(log)
            )
            + tx + result.data + log
        )
        runs.close()

    @pytest.mark.parametrize("cut", [0, 1, 28, 29, -1])
    def test_a_value_that_is_not_whole_is_refused_not_misread(self, cut):
        block, responses = _block(3, [b"k=v"])
        kv = KVTxIndexer(MemDB())
        kv.add_batch(block, responses)
        raw = kv._db.get(b"tx:" + tx_hash(b"k=v"))
        assert TxResult.decode(raw).tx == b"k=v"
        with pytest.raises((ValueError, struct.error)):
            TxResult.decode(raw[:cut] if cut else b"\x02" + raw[1:])

    def test_the_old_rows_are_the_parents_bytes(self):
        """Literal output of the parent commit's `TxResult.to_json`."""
        rows = [
            (
                (7, 2, b"old-2=v", Result(2, b"d2", "log 7")),
                b'{"code": 2, "data": "6432", "height": 7, "index": 2, "log": "log 7", "tx": "6f6c642d323d76"}',
            ),
            (
                (7, 0, b"", Result(0, b"", "")),
                b'{"code": 0, "data": "", "height": 7, "index": 0, "log": "", "tx": ""}',
            ),
            (
                (3, 1, b"k=\xff", Result(1, b"\x00\x7b", "grüß ✓")),
                b'{"code": 1, "data": "007b", "height": 3, "index": 1, '
                b'"log": "gr\\u00fc\\u00df \\u2713", "tx": "6b3dff"}',
            ),
        ]
        before = _reads()
        for fields, raw in rows:
            assert _old_row(*fields) == raw
            assert TxResult.decode(raw) == TxResult(*fields)
        assert _reads() == {"packed": before["packed"], "json": before["json"] + 3}

    def test_a_full_block_is_packed_without_json_or_an_object_a_tx(self, tmp_path, monkeypatch):
        """The write path's work by count, the CPU proxy for its speed:
        10,000 txs of the mix `full`, no `json.dumps`, no `TxResult`, and
        under 90 bytes a row appended (40 of them the key and pointer)."""
        calls = {"dumps": 0, "TxResult": 0}
        real_dumps, real_init = txindex.json.dumps, TxResult.__init__

        def dumps(*args, **kwargs):
            calls["dumps"] += 1
            return real_dumps(*args, **kwargs)

        def init(self, *args, **kwargs):
            calls["TxResult"] += 1
            real_init(self, *args, **kwargs)

        block, responses = _block(5, FULL)
        responses.deliver_tx = [Result(0, b"", "")] * len(FULL)
        runs, kv = RunTxIndexer(str(tmp_path)), KVTxIndexer(MemDB())
        monkeypatch.setattr(txindex.json, "dumps", dumps)
        monkeypatch.setattr(TxResult, "__init__", init)
        appended = TXINDEX_BYTES_WRITTEN.labels(kind="append").value
        runs.add_batch(block, responses)
        kv.add_batch(block, responses)
        assert calls == {"dumps": 0, "TxResult": 0}
        appended = TXINDEX_BYTES_WRITTEN.labels(kind="append").value - appended
        assert appended == os.path.getsize(tmp_path / "txindex" / "data")
        assert 80 < appended / len(FULL) < 90
        got = runs.get(tx_hash(FULL[9_999]))
        assert calls == {"dumps": 0, "TxResult": 1}
        assert (got.height, got.index, got.tx) == (5, 9_999, FULL[9_999]) and got == kv.get(tx_hash(FULL[9_999]))
        runs.close()


class TestRowsOfBothForms:
    """A directory that an earlier commit wrote holds JSON values; the
    blocks after it are packed. One decoder reads both by the first
    byte, through merges (which move pointers, never values)."""

    def test_json_records_then_packed_ones_answer_through_a_merge_and_a_reopen(
        self, tmp_path, monkeypatch
    ):
        kick = RunLog._kick
        monkeypatch.setattr(RunLog, "_kick", lambda self: None)  # merged when the test says
        old, want = {}, {}
        log = RunLog(str(tmp_path / "txindex"))
        for height in range(1, FAN_IN):
            block, responses = _block(height, [b"old-%d-%d=v" % (height, i) for i in range(20)])
            rows = {}
            for i, tx in enumerate(block.data.txs):
                rows[tx_hash(tx)] = _old_row(height, i, tx, responses.deliver_tx[i])
                old[tx] = TxResult(height, i, tx, responses.deliver_tx[i])
            _put(log, height, rows)
        log.close()

        runs = RunTxIndexer(str(tmp_path))
        again = b"old-2-3=v"  # indexed once more, packed: the later answer
        for height in range(FAN_IN, FAN_IN + 3):
            txs = [b"new-%d-%d=v" % (height, i) for i in range(20)] + [again] * (height == FAN_IN)
            block, responses = _block(height, txs)
            runs.add_batch(block, responses)
            for i, tx in enumerate(txs):
                want[tx] = TxResult(height, i, tx, responses.deliver_tx[i])
        del old[again]

        def every_hash_answers(indexer) -> None:
            before = _reads()
            assert all(indexer.get(tx_hash(tx)) == row for tx, row in old.items())
            assert _reads() == {"packed": before["packed"], "json": before["json"] + len(old)}
            assert all(indexer.get(tx_hash(tx)) == row for tx, row in want.items())
            assert _reads() == {
                "packed": before["packed"] + len(want), "json": before["json"] + len(old)
            }

        assert _live(runs._log) == FAN_IN + 2
        every_hash_answers(runs)
        merges = TXINDEX_MERGES.value
        monkeypatch.setattr(RunLog, "_kick", kick)
        runs._log._kick()
        assert _settled(runs._log, 30)
        assert TXINDEX_MERGES.value == merges + 1 and _live(runs._log) == 1
        assert runs._log._runs[0].file is not None  # a key file that points at both forms
        every_hash_answers(runs)
        runs.close()
        runs = RunTxIndexer(str(tmp_path))
        every_hash_answers(runs)
        runs.close()


class TestACrashAtTheAppendOfPackedRows:
    """`TestACrashAtTheAppend`'s cases through the indexer: all of a
    block's packed rows or none."""

    BLOCKS = {h: [b"crash-%d-%d=v" % (h, i) for i in range(50)] for h in (1, 2, 3)}

    def _answers(self, indexer, height: int) -> list[bool]:
        return [indexer.get(tx_hash(tx)) is not None for tx in self.BLOCKS[height]]

    @pytest.mark.parametrize(
        "cut",
        [
            lambda record: 1,
            lambda record: 4,  # the checksum
            lambda record: 5,
            lambda record: 1500,  # the values
            lambda record: record - 24 - 50 * 40 + 1,
            lambda record: record - 25,  # the keys
            lambda record: record - 23,
            lambda record: record - 1,  # the header
        ],
        ids=["crc_1", "crc_4", "values_5", "values_1500", "keys_last", "keys_first", "head_23", "head_1"],
    )
    def test_a_truncated_tail_leaves_none_of_the_last_block(self, tmp_path, cut):
        runs = RunTxIndexer(str(tmp_path))
        ends = []
        for height, txs in self.BLOCKS.items():
            runs.add_batch(*_block(height, txs))
            ends.append(os.path.getsize(tmp_path / "txindex" / "data"))
        runs.close()
        record = ends[-1] - ends[-2]
        # header, 50 entries, 50 values (length, 29 bytes, tx, data, log), checksum
        assert record == 24 + 50 * 40 + sum(
            4 + 29 + len(tx) + len(b"d%d" % i) + len("log 3")
            for i, tx in enumerate(self.BLOCKS[3])
        ) + 4
        with open(tmp_path / "txindex" / "data", "r+b") as f:
            f.truncate(ends[-1] - cut(record))
        runs = RunTxIndexer(str(tmp_path))
        assert all(self._answers(runs, 1)) and all(self._answers(runs, 2))
        assert not any(self._answers(runs, 3))
        assert os.path.getsize(tmp_path / "txindex" / "data") == ends[-2]
        runs.add_batch(*_block(3, self.BLOCKS[3]))
        assert all(self._answers(runs, 3)) and runs.get(tx_hash(self.BLOCKS[3][49])).index == 49
        runs.close()

    def test_a_failed_fsync_leaves_none_of_the_block(self, tmp_path, monkeypatch):
        runs = RunTxIndexer(str(tmp_path))
        runs.add_batch(*_block(1, self.BLOCKS[1]))
        size = os.path.getsize(tmp_path / "txindex" / "data")
        real = os.fsync

        def no_space(fd):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(os, "fsync", no_space)
        with pytest.raises(OSError):
            runs.add_batch(*_block(2, self.BLOCKS[2]))
        monkeypatch.setattr(os, "fsync", real)
        assert os.path.getsize(tmp_path / "txindex" / "data") == size
        assert all(self._answers(runs, 1)) and not any(self._answers(runs, 2))
        runs.add_batch(*_block(2, self.BLOCKS[2]))
        assert all(self._answers(runs, 2))
        runs.close()


class TestTheOldFile:
    @pytest.mark.parametrize("closed", [True, False], ids=["closed", "wal_left"])
    def test_a_txindex_db_answers_through_the_new_store_and_is_not_written(
        self, tmp_path, closed
    ):
        old_txs = [b"old-%d=v" % i for i in range(30)]
        home = tmp_path / "live"
        home.mkdir()
        db = SQLiteDB(str(home / "txindex.db"))
        block, responses = _block(7, old_txs)
        batch = db.batch()  # what `KVTxIndexer` over this file wrote before the run log
        for i, tx in enumerate(old_txs):
            batch.set(b"tx:" + tx_hash(tx), _old_row(7, i, tx, responses.deliver_tx[i]))
        batch.write()
        if closed:
            db.close()
        else:
            # a node that was killed: the rows are in the WAL still
            shutil.copytree(home, tmp_path / "killed")
            db.close()
            home = tmp_path / "killed"
            assert os.path.getsize(home / "txindex.db-wal") > 0
        size = os.path.getsize(home / "txindex.db")
        stamp = os.stat(home / "txindex.db").st_mtime_ns

        runs = RunTxIndexer(str(home))
        want = KVTxIndexer(MemDB())
        want.add_batch(*_block(7, old_txs))
        read = _reads()
        assert all(runs.get(tx_hash(tx)) == want.get(tx_hash(tx)) for tx in old_txs)
        assert _reads() == {  # the file's rows are JSON, `want`'s packed
            "packed": read["packed"] + len(old_txs), "json": read["json"] + len(old_txs)
        }
        # new blocks go to the run log, and the newer answer wins
        before = commits("txindex")
        runs.add_batch(*_block(9, [b"new=v", old_txs[0]]))
        assert commits("txindex") - before == 1
        assert runs.get(tx_hash(b"new=v")).height == 9
        assert runs.get(tx_hash(old_txs[0])).height == 9
        assert runs.get(tx_hash(old_txs[1])).height == 7
        assert runs.get(tx_hash(b"nowhere")) is None
        assert _reads() == {
            "packed": read["packed"] + len(old_txs) + 2, "json": read["json"] + len(old_txs) + 1
        }
        runs.close()
        assert os.path.getsize(home / "txindex.db") == size
        assert os.stat(home / "txindex.db").st_mtime_ns == stamp

    def test_a_fresh_directory_gets_no_txindex_db(self, tmp_path):
        runs = RunTxIndexer(str(tmp_path))
        runs.add_batch(*_block(1, [b"a=b"]))
        runs.close()
        assert _files(tmp_path) == {"txindex"}
        assert _files(tmp_path / "txindex") == {"data"}


class TestANode:
    def test_tx_with_proof_answers_from_the_run_log_after_a_restart(self, tmp_path):
        from tendermint_tpu.cmd import main as cli_main
        from tendermint_tpu.config import Config
        from tendermint_tpu.merkle.simple import SimpleProof
        from tendermint_tpu.node import Node
        from tendermint_tpu.rpc.client import HTTPClient
        from tendermint_tpu.services.hasher import TreeHasher
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.types.tx import TxProof

        home = str(tmp_path / "solo")
        cli_main(["init", "--home", home, "--chain-id", "txindex-test"])

        def node() -> Node:
            cfg = Config.test_config(home)
            cfg.base.fast_sync = False
            n = Node(cfg, verifier=HostBatchVerifier(), hasher=TreeHasher("host"))
            n.start()
            return n

        first = node()
        try:
            assert isinstance(first.tx_indexer, RunTxIndexer)
            res = HTTPClient(f"127.0.0.1:{first.rpc_port}").broadcast_tx_commit(b"pk=pv")
            assert res["deliver_tx"]["code"] == 0
        finally:
            first.stop()
        data_dir = os.path.dirname(first.config.db_path("txindex"))
        assert not os.path.exists(os.path.join(data_dir, "txindex.db"))
        assert os.path.getsize(os.path.join(data_dir, "txindex", "data")) > 0

        second = node()
        try:
            c = HTTPClient(f"127.0.0.1:{second.rpc_port}")
            got = c.tx(bytes.fromhex(res["hash"]), prove=True)
            assert (got["height"], bytes.fromhex(got["tx"])) == (res["height"], b"pk=pv")
            pj = got["proof"]
            proof = TxProof(
                root_hash=bytes.fromhex(pj["root_hash"]),
                data=bytes.fromhex(pj["data"]),
                proof=SimpleProof(
                    index=int(pj["proof"]["index"]),
                    total=int(pj["proof"]["total"]),
                    leaf=bytes.fromhex(pj["proof"]["leaf"]),
                    aunts=[bytes.fromhex(a) for a in pj["proof"]["aunts"]],
                ),
            )
            header = c.block(res["height"])["block"]["header"]
            assert proof.validate(bytes.fromhex(header["data_hash"]))
            assert "proof" not in c.tx(bytes.fromhex(res["hash"]))
        finally:
            second.stop()
