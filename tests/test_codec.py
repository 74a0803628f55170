import pytest

from tendermint_tpu.codec import (
    Reader,
    Writer,
    canonical_dumps,
    decode_svarint,
    decode_uvarint,
    encode_svarint,
    encode_uvarint,
)

from tests.helpers import pad_varint


@pytest.mark.parametrize("n", [0, 1, 127, 128, 300, 2**32, 2**63 - 1, 2**64])
def test_uvarint_roundtrip(n):
    enc = encode_uvarint(n)
    dec, off = decode_uvarint(enc)
    assert dec == n and off == len(enc)


@pytest.mark.parametrize("n", [0, 1, -1, 63, -64, 2**40, -(2**40), 2**62, -(2**62)])
def test_svarint_roundtrip(n):
    dec, off = decode_svarint(encode_svarint(n))
    assert dec == n


def test_uvarint_negative_raises():
    with pytest.raises(ValueError):
        encode_uvarint(-1)


def test_truncated_uvarint():
    with pytest.raises(ValueError):
        decode_uvarint(b"\x80")


def test_writer_reader_roundtrip():
    w = (
        Writer()
        .uvarint(42)
        .svarint(-7)
        .bytes(b"hello")
        .string("wörld")
        .bool(True)
        .bool(False)
        .raw(b"\xff\x00")
    )
    r = Reader(w.build())
    assert r.uvarint() == 42
    assert r.svarint() == -7
    assert r.bytes() == b"hello"
    assert r.string() == "wörld"
    assert r.bool() is True
    assert r.bool() is False
    assert r.raw(2) == b"\xff\x00"
    r.expect_done()


def test_reader_trailing_bytes_detected():
    r = Reader(b"\x00\x01")
    r.uvarint()
    with pytest.raises(ValueError):
        r.expect_done()


# -- Reader.padded: has the reader passed a varint that was not minimal? -----


def _padded(value: int, pad: int) -> bytes:
    """`value` as a varint `pad` bytes longer than it need be."""
    enc = encode_uvarint(value)
    return pad_varint(enc, (0, len(enc)), pad)


@pytest.mark.parametrize("n", [0, 1, 127, 128, 255, 16383, 16384, 2**32, 2**63 - 1, 2**64])
def test_a_minimal_varint_never_sets_the_flag(n):
    for read, enc in (("uvarint", encode_uvarint(n)), ("svarint", encode_svarint(n)),
                      ("svarint", encode_svarint(-n))):
        r = Reader(enc)
        getattr(r, read)()
        r.expect_done()
        assert r.padded is False, (read, enc)


def test_the_one_byte_zero_is_minimal():
    r = Reader(b"\x00\x00\x00\x00")
    assert (r.uvarint(), r.svarint(), r.bytes(), r.string()) == (0, 0, b"", "")
    r.expect_done()
    assert r.padded is False


@pytest.mark.parametrize("pad", (1, 2, 3))
@pytest.mark.parametrize("n", [0, 1, 127, 128, 16383, 16384, 2**40])
def test_a_padded_uvarint_decodes_to_its_value_and_sets_the_flag(n, pad):
    enc = _padded(n, pad)
    assert decode_uvarint(enc) == (n, len(enc))  # acceptance is unchanged
    r = Reader(enc)
    assert r.uvarint() == n and r.padded is True
    r.expect_done()


@pytest.mark.parametrize("n", [0, -1, 1, -64, 64, 2**40, -(2**40)])
def test_a_padded_svarint_sets_the_flag(n):
    zigzag = decode_uvarint(encode_svarint(n))[0]
    r = Reader(_padded(zigzag, 1))
    assert r.svarint() == n and r.padded is True


@pytest.mark.parametrize("read,payload,want", [
    ("bytes", b"hello", b"hello"),
    ("bytes", b"", b""),
    ("string", "wörld".encode(), "wörld"),
    ("string", b"", ""),
])
def test_a_padded_length_prefix_sets_the_flag(read, payload, want):
    tight = Reader(encode_uvarint(len(payload)) + payload)
    assert getattr(tight, read)() == want and tight.padded is False
    loose = Reader(_padded(len(payload), 1) + payload)
    assert getattr(loose, read)() == want and loose.padded is True
    loose.expect_done()


def test_the_flag_stays_set_and_is_each_readers_own():
    r = Reader(_padded(5, 1) + b"\x07" + encode_uvarint(300))
    assert r.padded is False
    assert r.uvarint() == 5 and r.padded is True
    assert r.uvarint() == 7 and r.uvarint() == 300 and r.padded is True
    assert Reader(b"\x07").padded is False
    # payload bytes that look like padding are not varints
    r = Reader(b"\x02\x80\x00" + b"\x01")
    assert r.bytes() == b"\x80\x00" and r.raw(1) == b"\x01" and r.padded is False


def test_reader_bytes_is_immutable_whatever_it_reads_from():
    for data in (b"\x02ab", bytearray(b"\x02ab"), memoryview(b"\x02ab")):
        got = Reader(data).bytes()
        assert type(got) is bytes and got == b"ab"
    with pytest.raises(ValueError, match="truncated bytes"):
        Reader(b"\x03ab").bytes()
    with pytest.raises(ValueError, match="truncated uvarint"):
        Reader(b"").uvarint()
    with pytest.raises(ValueError, match="truncated uvarint"):
        Reader(b"\x80\x80").bytes()


def test_canonical_json_deterministic_and_sorted():
    a = canonical_dumps({"b": 1, "a": b"\xde\xad", "c": {"z": 2, "y": [1, 2]}})
    b = canonical_dumps({"c": {"y": [1, 2], "z": 2}, "a": b"\xde\xad", "b": 1})
    assert a == b
    assert a == b'{"a":"DEAD","b":1,"c":{"y":[1,2],"z":2}}'


def test_canonical_json_rejects_floats():
    with pytest.raises(TypeError):
        canonical_dumps({"x": 1.5})
