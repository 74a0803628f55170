import time

import pytest

from tendermint_tpu.codec import Reader, encode_uvarint
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.types import Block, BlockID, Commit, Data, Txs, ValidationError
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators, pad_varint


def make_test_block(height=2, n_txs=5):
    vs, privs = make_validators(4)
    last_bid = make_block_id(b"prev")
    last_commit = make_commit(vs, privs, height=height - 1, round_=0, block_id=last_bid)
    txs = Txs(f"tx-{i}".encode() for i in range(n_txs))
    return Block.make_block(
        height=height,
        chain_id=CHAIN_ID,
        txs=txs,
        last_commit=last_commit,
        last_block_id=last_bid,
        time=time.time_ns(),
        validators_hash=vs.hash(),
        app_hash=b"\x01" * 32,
    )


def test_block_hash_stable_and_nonempty():
    b = make_test_block()
    h1, h2 = b.hash(), b.hash()
    assert h1 == h2 and len(h1) == 32


def test_header_hash_changes_with_fields():
    b1, b2 = make_test_block(), make_test_block()
    b2.header.app_hash = b"\x02" * 32
    assert b1.hash() != b2.hash()


def test_validate_basic_ok():
    make_test_block().validate_basic()


def test_validate_basic_catches_num_txs():
    b = make_test_block()
    b.header.num_txs = 99
    with pytest.raises(ValidationError):
        b.validate_basic()


def test_validate_basic_catches_data_tamper():
    b = make_test_block()
    b.data.txs[0] = b"evil"
    with pytest.raises(ValidationError):
        b.validate_basic()


def test_encode_decode_roundtrip():
    b = make_test_block()
    b2 = Block.decode(b.encode())
    assert b2.hash() == b.hash()
    assert b2.data.txs == b.data.txs
    assert b2.last_commit.block_id == b.last_commit.block_id
    b2.validate_basic()


def test_part_set_roundtrip():
    b = make_test_block(n_txs=200)
    ps = b.make_part_set(part_size=512)
    assert ps.total > 1
    assert Block.decode(ps.assemble()).hash() == b.hash()


def test_commit_validate_basic():
    vs, privs = make_validators(4)
    bid = make_block_id()
    c = make_commit(vs, privs, height=3, round_=1, block_id=bid)
    c.validate_basic()
    assert c.height() == 3 and c.round() == 1
    assert c.bit_array().num_set() == 4


def test_empty_commit_for_height_1():
    b = make_test_block(height=2)
    assert Commit.empty().size() == 0


# -- a data section keeps the bytes it was read from --------------------------

DATA_ENCODES = "tendermint_block_data_encodes_total"


def data_encodes() -> dict:
    return {how: REGISTRY.counter_value(DATA_ENCODES, how=how) for how in ("kept", "walked")}


def data_encodes_since(before: dict) -> dict:
    return {how: value - before[how] for how, value in data_encodes().items()}


def section_txs(n: int) -> Txs:
    """`n` txs of the benchmark's form, with a tx of no bytes and one under a
    two-byte length among them where there is room."""
    txs = Txs(b"k%07d=%d" % (i, i * 7919) for i in range(n))
    if n > 1:
        txs[n // 2] = b""
        txs[-1] = b"\xab" * 300
    elif n:
        txs[0] = b"\x80" * 128
    return txs


@pytest.mark.parametrize("n", [0, 1, 3, 10_000])
def test_decoded_section_is_kept_as_the_encoding(n):
    txs = section_txs(n)
    section = Data(txs).encode()
    r = Reader(section)
    d = Data.decode_from(r)
    assert r.done() and not r.padded
    assert d.txs == txs and isinstance(d.txs, Txs) and d == Data(txs)
    assert all(type(tx) is bytes for tx in d.txs)
    before = data_encodes()
    assert d.encode() is section
    assert data_encodes_since(before) == {"kept": 1, "walked": 0}
    assert d.hash() == Data(txs).hash()


def _padded_count(section: bytes) -> Reader:
    return Reader(pad_varint(section, (0, 1), 2))


def _padded_length(section: bytes) -> Reader:
    # the first tx's one-byte length, after the one-byte count
    return Reader(pad_varint(section, (1, 2), 1))


def _padded_long_length(section: bytes) -> Reader:
    # the last tx's two-byte length
    at = len(section) - 300 - 2
    assert section[at : at + 2] == encode_uvarint(300)
    return Reader(pad_varint(section, (at, at + 2), 3))


def _trailing_bytes(section: bytes) -> Reader:
    return Reader(section + b"\x00\x01")


def _middle_of_a_buffer(section: bytes) -> Reader:
    return Reader(b"\x07" + section, 1)


def _not_bytes(section: bytes) -> Reader:
    return Reader(bytearray(section))


@pytest.mark.parametrize(
    "reader_over",
    [_padded_count, _padded_length, _padded_long_length, _trailing_bytes, _middle_of_a_buffer, _not_bytes],
)
def test_section_that_is_not_the_encoding_as_an_object_is_walked(reader_over):
    txs = section_txs(3)
    section = Data(txs).encode()
    d = Data.decode_from(reader_over(section))
    assert d.txs == txs and all(type(tx) is bytes for tx in d.txs)
    before = data_encodes()
    assert d.encode() == section
    assert data_encodes_since(before) == {"kept": 0, "walked": 1}
    # as before the bytes were kept, such txs may be changed in place
    d.txs[0] = b"other"
    assert d.encode() == Data(d.txs).encode() != section


@pytest.mark.parametrize(
    "bad, message",
    [
        (lambda s: b"", "truncated uvarint"),
        (lambda s: s[:1], "truncated uvarint"),  # the count, no tx
        (lambda s: s[:2], "truncated bytes"),  # a length, not its bytes
        (lambda s: s[:-1], "truncated bytes"),  # the last tx a byte short
        (lambda s: s[:-300], "truncated bytes"),  # the two-byte length alone
        (lambda s: s[:-301], "truncated uvarint"),  # half of that length
        (lambda s: b"\x04" + s[1:], "truncated uvarint"),  # one tx more than there is
        # 2**40 txs over ten bytes: fails where the bytes end, sizes nothing
        (lambda s: encode_uvarint(2**40) + s[1:5], "truncated"),
        (lambda s: b"\xff" * 11 + s, "uvarint too long"),
    ],
)
def test_truncated_section_or_count_beyond_it_raises(bad, message):
    import tracemalloc

    section = bad(Data(section_txs(3)).encode())
    tracemalloc.start()
    try:
        with pytest.raises(ValueError, match=message):
            Data.decode_from(Reader(section))
        _size, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 1 << 20
    with pytest.raises(ValueError, match=message):
        # the reader's own walk, as `decode_from` was before: the same refusal
        r = Reader(section)
        [r.bytes() for _ in range(r.uvarint())]


def test_assigning_txs_after_a_decode_drops_the_kept_bytes():
    section = Data(section_txs(3)).encode()
    d = Data.decode_from(Reader(section))
    d.txs = Txs([b"a", b"bc"])
    before = data_encodes()
    written = d.encode()
    assert data_encodes_since(before) == {"kept": 0, "walked": 1}
    assert written == Data(Txs([b"a", b"bc"])).encode() != section
    assert Data.decode_from(Reader(d.encode())).txs == [b"a", b"bc"]


@pytest.mark.parametrize(
    "change",
    [
        lambda txs: txs.__setitem__(0, b"evil"),
        lambda txs: txs.__delitem__(0),
        lambda txs: txs.append(b"evil"),
        lambda txs: txs.extend([b"evil"]),
        lambda txs: txs.insert(0, b"evil"),
        lambda txs: txs.pop(),
        lambda txs: txs.remove(txs[0]),
        lambda txs: txs.clear(),
        lambda txs: txs.sort(),
        lambda txs: txs.reverse(),
        lambda txs: txs.__iadd__([b"evil"]),
        lambda txs: txs.__imul__(2),
    ],
)
def test_txs_of_a_kept_section_refuse_change_in_place(change):
    txs = section_txs(3)
    section = Data(txs).encode()
    d = Data.decode_from(Reader(section))
    with pytest.raises(TypeError, match="read-only"):
        change(d.txs)
    assert d.txs == txs and d.encode() is section
    # a copy is a plain Txs, and assigning it is how a decoded block's txs change
    copy = Txs(d.txs)
    change(copy)
    d.txs = copy
    assert d.encode() == Data(copy).encode()


def test_made_block_walks_its_txs():
    b = make_test_block()
    before = data_encodes()
    wire = b.encode()
    assert data_encodes_since(before) == {"kept": 0, "walked": 1}
    b.data.txs[0] = b"evil"
    assert b.encode() != wire


def test_decoded_full_block_is_the_block_that_was_made():
    b = make_test_block(n_txs=10_000)
    wire = b.encode()
    before = data_encodes()
    b2 = Block.decode(wire)
    assert b2.make_part_set().header == b.make_part_set().header
    assert data_encodes_since(before) == {"kept": 1, "walked": 1}
    assert b2.hash() == b.hash()
    assert b2.encode() == wire
    assert b2.block_id() == b.block_id()
    b2.validate_basic()


def test_data_encodes_counter_is_seeded_and_documented():
    import pathlib

    text = REGISTRY.prometheus_text()
    docs = (pathlib.Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md").read_text()
    for how in ("kept", "walked"):
        assert f'{DATA_ENCODES}{{how="{how}"}}' in text
    assert f"| `{DATA_ENCODES}" in docs
