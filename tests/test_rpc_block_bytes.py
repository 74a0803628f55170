"""A `/block` answer read off the stored bytes (ISSUE 39).

`rpc/core.py::block` answers from `BlockStore.load_block_bytes`: the
meta row and one `DB.get_many` of the part rows, then a scan of the
block's wire form. It is held here to the answer it gave from a `Block`
(`_block_json(store.load_block(h))`), for every shape of stored block,
over both database backends; and to building nothing and reading twice.
"""

from __future__ import annotations

import hashlib
import random
import threading
from types import SimpleNamespace

import pytest

from tendermint_tpu.blockchain import BlockStore
from tendermint_tpu.codec import Writer
from tendermint_tpu.db.kv import _GET_MANY_CHUNK, MemDB, SQLiteDB
from tendermint_tpu.merkle.simple import SimpleProof
from tendermint_tpu.rpc.core import _block_json, make_routes
from tendermint_tpu.rpc.server import RPCError
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT, BlockID, Commit, Txs, Vote
from tendermint_tpu.types.block import Block
from tendermint_tpu.types.part_set import DEFAULT_PART_SIZE, Part, PartSet

from tests.helpers import CHAIN_ID, make_block_id, pad_varint
from tests.test_evidence import duplicate_vote_evidence

BACKENDS = ("memdb", "sqlite")
READS = "tendermint_db_reads_total"


def _open(backend: str, directory) -> MemDB | SQLiteDB:
    """A block store's database: the SQLite file under the name a node
    gives it, which is the counter's `db` label."""
    return MemDB() if backend == "memdb" else SQLiteDB(str(directory / "blockstore.db"))


def _routes(store: BlockStore) -> dict:
    return make_routes(
        SimpleNamespace(
            block_store=store, config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False))
        )
    )


def _vote(i: int, height: int, block_id: BlockID) -> Vote:
    """A precommit with a signature of the right size and no key behind
    it: the store and `/block` verify nothing."""
    seed = hashlib.sha256(b"%d-%d" % (i, height)).digest()
    return Vote(
        validator_address=seed[:20],
        validator_index=i,
        height=height,
        round=0,
        timestamp=1_700_000_000_000_000_000 + i,
        type=VOTE_TYPE_PRECOMMIT,
        block_id=block_id,
        signature=seed + seed,
    )


def _commit(height: int, n: int, absent: str) -> Commit:
    """`n` precommits for the block under `height`: `absent` is `none`,
    `some` (a seeded third) or `all`."""
    block_id = make_block_id(b"block-%d" % (height - 1))
    rng = random.Random(f"{height}-{n}")
    gone = {
        "none": set(),
        "some": set(rng.sample(range(n), max(1, n // 3))),
        "all": set(range(n)),
    }[absent]
    return Commit(
        block_id=block_id,
        precommits=[None if i in gone else _vote(i, height - 1, block_id) for i in range(n)],
    )


def _block(height: int, txs: list[bytes], last_commit: Commit, evidence=None) -> Block:
    return Block.make_block(
        height=height,
        chain_id=CHAIN_ID,
        txs=Txs(txs),
        last_commit=last_commit,
        last_block_id=last_commit.block_id,
        time=1_700_000_000_000_000_000 + height,
        validators_hash=b"\x01" * 20,
        app_hash=b"\x02" * 20,
        evidence=evidence,
    )


def _padded_wire(block: Block) -> bytes:
    """`block.encode()` as a peer may send it and no encoder writes it:
    in every third vote of the commit the first varint (the address's
    length) padded by one byte or two, and in every fifth the vote's own
    length prefix too. A decoder gives the same block."""
    commit = Writer().raw(block.last_commit.block_id.encode())
    commit.uvarint(len(block.last_commit.precommits))
    for i, vote in enumerate(block.last_commit.precommits):
        if vote is None:
            commit.bytes(b"")
            continue
        wire = vote.encode()
        if i % 3 == 0:
            wire = pad_varint(wire, (0, 1), 1 + i % 2)
        prefixed = Writer().bytes(wire).build()
        if i % 5 == 0:
            prefixed = pad_varint(prefixed, (0, len(prefixed) - len(wire)), 1)
        commit.raw(prefixed)
    return (
        Writer()
        .bytes(block.header.encode())
        .bytes(block.data.encode())
        .bytes(commit.build())
        .build()
    )


# the stored blocks, one a height: (name, txs, precommits of the commit it
# carries, which of them are absent, what else is special)
SHAPES = (
    ("height_1_empty_last_commit", [], 0, "none", None),
    ("3_txs_4_precommits", [b"k%d=v" % i for i in range(3)], 4, "some", None),
    ("0_txs_100_precommits", [], 100, "some", None),
    (
        "10000_txs_1000_precommits",
        [b"k%07d=%d" % (i, 7 * i) for i in range(10_000)],
        1_000,
        "some",
        None,
    ),
    (
        "tx_sizes_1_127_128_20000",
        [b"a", b"b" * 127, b"c" * 128, bytes(range(256)) * 78 + b"d" * 32, b"e" * 129],
        100,
        "none",
        None,
    ),
    ("all_precommits_absent", [b"k=v"], 4, "all", None),
    ("1000_precommits_all_present", [b"k=v", b""], 1_000, "none", None),
    ("padded_varints_in_the_commit", [b"k=v"] * 3, 100, "some", "padded"),
    ("evidence_section", [b"k=v"], 4, "some", "evidence"),
)
HEIGHT_OF = {shape[0]: h for h, shape in enumerate(SHAPES, start=1)}


def _fill(store: BlockStore) -> None:
    for height, (_, txs, n, absent, special) in enumerate(SHAPES, start=1):
        last_commit = _commit(height, n, absent) if n else Commit.empty()
        evidence = [duplicate_vote_evidence(height=height)] if special == "evidence" else None
        block = _block(height, txs, last_commit, evidence)
        wire = _padded_wire(block) if special == "padded" else block.encode()
        if special == "padded":
            assert wire != block.encode() and Block.decode(wire) == block
        store.save_block(block, PartSet.from_data(wire), _commit(height + 1, 4, "none"))


@pytest.fixture(scope="module", params=BACKENDS)
def filled(request, tmp_path_factory):
    db = _open(request.param, tmp_path_factory.mktemp("shapes"))
    store = BlockStore(db)
    _fill(store)
    yield store
    db.close()


class TestTheAnswerIsTheSame:
    @pytest.mark.parametrize("shape", [s[0] for s in SHAPES])
    def test_for_every_shape_of_block(self, filled, shape):
        height = HEIGHT_OF[shape]
        block = filled.load_block(height)
        want = {"block": _block_json(block)}
        got = _routes(filled)["block"](height)
        assert got == want
        assert got["block"]["header"]["hash"] == block.header.hash().hex() != ""
        # the shape is the one its name says
        _, txs, n, absent, special = SHAPES[height - 1]
        assert got["block"]["txs"] == [tx.hex() for tx in txs]
        present = {"none": n, "all": 0}.get(absent)
        if present is not None:
            assert got["block"]["last_commit"]["precommits"] == present
        else:
            assert 0 < got["block"]["last_commit"]["precommits"] < n
        assert (got["block"]["last_commit"]["block_id"] == "") == (n == 0)
        assert (len(block.evidence) > 0) == (special == "evidence")

    def test_the_shapes_cross_part_boundaries_and_prefix_sizes(self, filled):
        """What the cases claim to cover: a tx that spans parts, one- and
        multi-byte length prefixes, a block of one part and one of many."""
        totals = {
            name: filled.load_block_meta(h).block_id.parts_header.total
            for name, h in HEIGHT_OF.items()
        }
        assert totals["height_1_empty_last_commit"] == 1
        assert totals["10000_txs_1000_precommits"] > 50
        sizes = [len(tx) for tx in SHAPES[HEIGHT_OF["tx_sizes_1_127_128_20000"] - 1][1]]
        assert sizes == [1, 127, 128, 20_000, 129] and sizes[3] > 4 * DEFAULT_PART_SIZE

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("case", ["above", "zero", "below_base", "pruned", "part_row_gone"])
    def test_a_height_that_does_not_load_is_the_error_it_was(self, tmp_path, backend, case):
        db = _open(backend, tmp_path)
        store = BlockStore(db)
        blocks = [_block(h, [b"x" * 9_000], _commit(h, 4, "none")) for h in range(1, 7)]
        seen = _commit(9, 4, "none")
        if case == "below_base":
            store.bootstrap([(b, seen) for b in blocks[3:]])
        else:
            for b in blocks:
                store.save_block(b, b.make_part_set(), seen)
        if case == "pruned":
            assert store.prune(4) == 3
        if case == "part_row_gone":
            assert store.load_block_meta(2).block_id.parts_header.total == 3
            db.delete(store._part_key(2, 1))
        height = {"above": 7, "zero": 0}.get(case, 2)
        assert store.load_block(height) is None
        assert store.load_block_bytes(height) is None
        with pytest.raises(RPCError) as err:
            _routes(store)["block"](height)
        assert (err.value.code, err.value.message) == (-32000, f"no block at height {height}")
        # and its neighbours answer
        assert _routes(store)["block"](5) == {"block": _block_json(store.load_block(5))}
        db.close()


class TestItEngagesAndBuildsNothing:
    def test_a_block_answer_is_two_reads_whatever_its_parts(self, tmp_path):
        db = _open("sqlite", tmp_path)
        store = BlockStore(db)
        _fill(store)
        routes = _routes(store)
        totals = set()
        for height in HEIGHT_OF.values():
            totals.add(store.load_block_meta(height).block_id.parts_header.total)
            before = REGISTRY.counter_value(READS, db="blockstore")
            routes["block"](height)
            assert REGISTRY.counter_value(READS, db="blockstore") - before == 2
        assert min(totals) == 1 and max(totals) > 50
        # what it replaced: a read a row
        before = REGISTRY.counter_value(READS, db="blockstore")
        store.load_block(HEIGHT_OF["10000_txs_1000_precommits"])
        assert REGISTRY.counter_value(READS, db="blockstore") - before == 1 + max(totals)
        db.close()

    def test_no_vote_part_proof_or_block_is_built(self, filled, monkeypatch):
        want = {h: {"block": _block_json(filled.load_block(h))} for h in HEIGHT_OF.values()}

        def refuse(*args, **kwargs):
            raise AssertionError("a /block answer builds no object of the block")

        for cls in (Vote, Part, Block, SimpleProof):
            monkeypatch.setattr(cls, "decode", refuse)
        monkeypatch.setattr(Commit, "decode_from", refuse)
        with pytest.raises(AssertionError):
            filled.load_block(2)
        routes = _routes(filled)
        assert {h: routes["block"](h) for h in HEIGHT_OF.values()} == want


class TestGetMany:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_values_in_the_order_of_the_keys_and_none_for_an_absent_one(self, tmp_path, backend):
        db = _open(backend, tmp_path)
        n = 2 * _GET_MANY_CHUNK + 37  # more keys than one SQLite statement takes
        batch = db.batch()
        for i in range(0, n, 2):
            batch.set(b"k:%d" % i, b"v" * (i % 7) + b"%d" % i)
        batch.write_sync()
        keys = [b"k:%d" % i for i in range(n)]
        random.Random(39).shuffle(keys)
        got = db.get_many(keys)
        assert got == [db.get(k) for k in keys]
        assert sum(v is None for v in got) == n // 2
        assert all(type(v) is bytes for v in got if v is not None)
        assert db.get_many([]) == []
        assert db.get_many([b"k:0", b"absent", b"k:0"]) == [b"0", None, b"0"]
        assert db.get_many([bytearray(b"k:2")]) == [b"vv2"]
        db.close()

    def test_one_read_is_counted_whatever_the_keys(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "counted.db"))
        db.set(b"a", b"1")
        for keys, rise in (([], 1), ([b"a"], 1), ([b"k%d" % i for i in range(1_200)], 1)):
            before = REGISTRY.counter_value(READS, db="counted")
            db.get_many(keys)
            assert REGISTRY.counter_value(READS, db="counted") - before == rise
        before = REGISTRY.counter_value(READS, db="counted")
        assert db.get(b"a") == b"1" and db.get(b"b") is None and db.has(b"a")
        assert REGISTRY.counter_value(READS, db="counted") - before == 3
        db.close()

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_a_reader_never_sees_half_of_a_block_being_written(self, tmp_path, backend):
        """A writer saves blocks of several parts in a loop; a reader asks
        `/block` for the height the store names, as a client that read
        `/status` does: every answer is whole and is that height's."""
        db = _open(backend, tmp_path)
        store = BlockStore(db)
        n_blocks = 120
        blocks = [
            _block(h, [b"%d" % h * 3_000, b"k=%d" % h], _commit(h, 4, "some"))
            for h in range(1, n_blocks + 1)
        ]
        seen = _commit(n_blocks + 1, 4, "none")
        part_sets = [b.make_part_set() for b in blocks]
        assert part_sets[-1].header.total >= 3
        store.save_block(blocks[0], part_sets[0], seen)
        failure = []

        def write():
            try:
                for block, part_set in zip(blocks[1:], part_sets[1:]):
                    store.save_block(block, part_set, seen)
            except BaseException as exc:  # noqa: BLE001 - reported by the test's thread
                failure.append(exc)

        writer = threading.Thread(target=write)
        routes = _routes(store)
        writer.start()
        answered = set()
        while writer.is_alive() or store.height not in answered:
            height = store.height
            answer = routes["block"](height)["block"]
            assert answer["header"]["height"] == height
            assert answer["txs"] == [tx.hex() for tx in blocks[height - 1].data.txs]
            # and all of that block's part rows were read in one piece
            meta, wire = store.load_block_bytes(height)
            assert hashlib.sha256(wire).digest() == hashlib.sha256(blocks[height - 1].encode()).digest()
            assert meta.block_id.parts_header == part_sets[height - 1].header
            answered.add(height)
        writer.join()
        assert not failure and store.height == n_blocks and n_blocks in answered
        db.close()


def test_the_meta_rows_hash_is_the_headers(filled):
    """`"hash"` comes from the meta row's block id: `_put_block` stores
    `block.hash()`, which is `header.hash()`."""
    for height in HEIGHT_OF.values():
        meta = filled.load_block_meta(height)
        assert meta.block_id.hash == meta.header.hash() == filled.load_block(height).hash()
