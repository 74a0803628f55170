"""A commit's votes are decoded against the shape of its first.

`Commit.decode_from` reads the first precommit present with `Vote.decode`
and, where its bytes were canonical, every later one whose bytes show the
same layout, height, round, type and `block_id` against them
(`types/vote.py` `decode_commit_votes`); any other goes to `Vote.decode`.
Which way a vote went may show nowhere but in the counters and in the
`BlockID` the votes share: the commit has to be the one a `Vote.decode` of
each vote gives, kept bytes, hash, encoding and error with it. The loop
that did that before is kept here as the reference.

All on the CPU; signatures are seeded bytes, nothing is verified.
"""

from __future__ import annotations

import hashlib

import pytest

from tendermint_tpu.codec import Reader, Writer, encode_uvarint
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE, BlockID, Commit, Vote

from tests.helpers import make_block_id, pad_varint

HEIGHT = 1234
STAMP = 1_700_000_000_000_000_000
BLOCK = make_block_id(b"the block")
DECODED = "tendermint_commit_votes_decoded_total"
WIRE_KEPT = "tendermint_vote_wire_kept_total"
VARINTS = (
    "address_len", "index", "height", "round", "timestamp", "type",
    "hash_len", "parts_total", "parts_hash_len", "signature_len",
)  # fmt: skip


def reference_decode(raw: bytes) -> Commit:
    """`Commit.decode_from` as it was: a `Vote.decode` of every vote."""
    r = Reader(raw)
    block_id = BlockID.decode_from(r)
    precommits = []
    for _ in range(r.uvarint()):
        b = r.bytes()
        precommits.append(Vote.decode(b) if b else None)
    return Commit(block_id=block_id, precommits=precommits)


def vote(i: int, **fields) -> Vote:
    digest = hashlib.sha512(b"validator %d" % i).digest()
    base = dict(
        validator_address=digest[:20], validator_index=i, height=HEIGHT, round=0, timestamp=STAMP,
        type=VOTE_TYPE_PRECOMMIT, block_id=BLOCK, signature=hashlib.sha512(digest).digest(),
    )  # fmt: skip
    return Vote(**{**base, **fields})


def wire(i: int, **fields) -> bytes:
    return vote(i, **fields).encode()


def varint_spans(blob: bytes) -> dict:
    """Where each varint of a vote's wire form stands: name -> (start, end)."""
    r = Reader(blob)
    spans = {}

    def note(name, skip_bytes=False):
        start = r.offset
        n = r.uvarint()
        spans[name] = (start, r.offset)
        if skip_bytes:
            r.raw(n)

    note("address_len", True)
    for name in ("index", "height", "round", "timestamp", "type"):
        note(name)
    note("hash_len", True)
    note("parts_total")
    note("parts_hash_len", True)
    note("signature_len", True)
    assert r.done()
    return spans


def padded(blob: bytes, name: str, pad: int = 1) -> bytes:
    return pad_varint(blob, varint_spans(blob)[name], pad)


def commit_wire(blobs: list[bytes], block_id: BlockID = BLOCK) -> bytes:
    w = Writer().raw(block_id.encode()).uvarint(len(blobs))
    for b in blobs:
        w.bytes(b)
    return w.build()


def with_one(n: int, at: int, odd) -> list[bytes]:
    """`n` canonical votes for the block with `odd(i)` in place of vote
    `i = at % n`: `at` is 1, -1 or, for the first, 0."""
    blobs = [wire(i) for i in range(n)]
    blobs[at % n] = odd(at % n)
    return blobs


# name -> (n -> the votes' bytes, n -> how many of them may take the short
# path; None where the commit does not decode at all)
CASES: dict = {
    "one_timestamp": (lambda n: [wire(i) for i in range(n)], lambda n: n - 1),
    "a_timestamp_each": (lambda n: [wire(i, timestamp=STAMP + 1009 * i) for i in range(n)], lambda n: n - 1),
    "short_timestamps": (
        lambda n: [wire(i, timestamp=(0, -1, 63, 64, -65, 2**62, -(2**63))[i % 7]) for i in range(n)],
        lambda n: n - 1,
    ),
    "absent_first_last_middle": (
        lambda n: [b"" if i in (0, n // 2, n - 1) else wire(i) for i in range(n)],
        lambda n: max(n - len({0, n // 2, n - 1}) - 1, 0),
    ),
    "all_absent": (lambda n: [b""] * n, lambda n: 0),
    "nil_and_other_block": (
        lambda n: [
            wire(i, block_id=BlockID.zero()) if i == n // 3 + 1
            else wire(i, block_id=make_block_id(b"other")) if i == n // 2 + 2
            else wire(i)
            for i in range(n)
        ],
        lambda n: n - 1 - len({n // 3 + 1, n // 2 + 2} & set(range(1, n))),
    ),
    "first_is_nil": (
        lambda n: [wire(i, block_id=BlockID.zero()) if i in (0, n - 1) else wire(i) for i in range(n)],
        lambda n: 1 if n > 1 else 0,
    ),
    "other_round": (lambda n: with_one(n, 1, lambda i: wire(i, round=1)), lambda n: max(n - 2, 0)),
    "other_height": (lambda n: with_one(n, -1, lambda i: wire(i, height=HEIGHT + 1)), lambda n: max(n - 2, 0)),
    "a_prevote": (lambda n: with_one(n, 1, lambda i: wire(i, type=VOTE_TYPE_PREVOTE)), lambda n: max(n - 2, 0)),
    "long_height_and_round": (lambda n: [wire(i, height=2**40, round=300) for i in range(n)], lambda n: n - 1),
    "index_edges": (
        lambda n: [wire(i, validator_index=(0, 127, 128, 16_383, 16_384, 2**32, 2**70)[i % 7]) for i in range(n)],
        lambda n: n - 1,
    ),
    "address_19": (lambda n: with_one(n, 1, lambda i: wire(i, validator_address=b"a" * 19)), lambda n: max(n - 2, 0)),
    "address_21": (lambda n: with_one(n, -1, lambda i: wire(i, validator_address=b"a" * 21)), lambda n: max(n - 2, 0)),
    "address_200": (lambda n: with_one(n, 1, lambda i: wire(i, validator_address=b"a" * 200)), lambda n: max(n - 2, 0)),
    "signature_63": (lambda n: with_one(n, 1, lambda i: wire(i, signature=b"s" * 63)), lambda n: max(n - 2, 0)),
    "signature_65": (lambda n: with_one(n, 1, lambda i: wire(i, signature=b"s" * 65)), lambda n: max(n - 2, 0)),
    "signature_none": (lambda n: with_one(n, -1, lambda i: wire(i, signature=b"")), lambda n: max(n - 2, 0)),
    "first_is_odd_and_canonical": (
        lambda n: with_one(n, 0, lambda i: wire(i, validator_address=b"a" * 19, signature=b"s" * 63)),
        lambda n: n - 1,
    ),
    "first_is_padded": (lambda n: with_one(n, 0, lambda i: padded(wire(i), "height")), lambda n: 0),
    "first_present_is_padded": (
        lambda n: [b""] + with_one(n, 0, lambda i: padded(wire(i), "signature_len")),
        lambda n: 0,
    ),
    "trailing_byte": (lambda n: with_one(n, -1, lambda i: wire(i) + b"\x00"), lambda n: None),
    "trailing_byte_on_the_first": (lambda n: with_one(n, 0, lambda i: wire(i) + b"\x01"), lambda n: None),
    "index_varint_too_long": (
        lambda n: with_one(n, -1, lambda i: wire(i)[:21] + b"\x80" * 11 + wire(i)[21:]),
        lambda n: None,
    ),
    "timestamp_varint_unended": (
        lambda n: with_one(n, -1, lambda i: wire(i)[:25] + b"\xff" * 120),
        lambda n: None,
    ),
    **{
        f"padded_{name}": (
            lambda n, name=name: with_one(n, -1, lambda i: padded(wire(i), name, 1 + n % 2)),
            lambda n: max(n - 2, 0),
        )
        for name in VARINTS
    },
    **{
        f"cut_short_by_{k}": (lambda n, k=k: with_one(n, -1, lambda i: wire(i)[:-k]), lambda n: None)
        for k in (1, 64, 65, 66, 110, 122, 142)
    },
}
SIZES = (1, 2, 5, 130)


def outcome(decode, raw):
    try:
        return decode(raw)
    except Exception as e:  # the test compares whatever was raised
        return type(e)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("case", CASES)
def test_decoded_commit_is_what_a_vote_decode_of_each_vote_gives(case, n):
    blobs_of, shared_of = CASES[case]
    blobs, shared = blobs_of(n), shared_of(n)
    raw = commit_wire(blobs)
    want = outcome(reference_decode, raw)
    before = {path: REGISTRY.counter_value(DECODED, path=path) for path in ("shared", "plain")}
    kept_before = REGISTRY.counter_value(WIRE_KEPT)
    got = outcome(lambda data: Commit.decode_from(Reader(data)), raw)

    if shared is None:
        assert want is ValueError and got is ValueError
        return
    assert isinstance(want, Commit) and isinstance(got, Commit)
    kept = sum(1 for v in want.precommits if v is not None and v._encoded is not None)
    assert got == want and got.block_id == BLOCK and len(got.precommits) == len(blobs)
    for blob, v, w in zip(blobs, got.precommits, want.precommits):
        if not blob:
            assert v is None and w is None
            continue
        # field by field and the kept bytes with them: present or absent
        # alike, equal where present, and of the same types
        assert vars(v) == vars(w)
        assert {k: type(x) for k, x in vars(v).items()} == {k: type(x) for k, x in vars(w).items()}
        assert v._encoded == w._encoded and (v._encoded is None or v._encoded == blob)
        assert hash(v) == hash(w) and repr(v) == repr(w)
    assert got.hash() == want.hash()
    assert got.encode() == want.encode()
    for v, w in zip(got.precommits, want.precommits):
        assert v is None or v.encode() == w.encode()

    present = sum(1 for b in blobs if b)
    rise = {path: REGISTRY.counter_value(DECODED, path=path) - before[path] for path in before}
    assert rise == {"shared": shared, "plain": present - shared}
    assert REGISTRY.counter_value(WIRE_KEPT) - kept_before == kept


@pytest.mark.parametrize("data_type", [bytes, bytearray, memoryview])
@pytest.mark.parametrize("n", [1, 4, 100, 1000])
def test_votes_share_one_block_id_and_the_counters_read_n_less_one_and_one(n, data_type):
    votes = [vote(i, timestamp=STAMP + i) for i in range(n)]
    raw = Commit(block_id=BLOCK, precommits=votes).encode()
    before = {path: REGISTRY.counter_value(DECODED, path=path) for path in ("shared", "plain")}
    commit = Commit.decode_from(Reader(data_type(raw)))
    assert commit.precommits == votes and commit.encode() == raw
    first = commit.precommits[0]
    assert all(v.block_id is first.block_id for v in commit.precommits)
    assert all(type(v._encoded) is bytes and v._encoded == w.encode() for v, w in zip(commit.precommits, votes))
    rise = {path: REGISTRY.counter_value(DECODED, path=path) - before[path] for path in before}
    assert rise == {"shared": n - 1, "plain": 1}


def test_a_commit_cut_short_between_votes_raises_as_before():
    raw = commit_wire([wire(i) for i in range(4)])
    for cut in (1, 70, 150, 300):
        assert outcome(reference_decode, raw[:-cut]) is ValueError
        assert outcome(lambda data: Commit.decode_from(Reader(data)), raw[:-cut]) is ValueError


def test_both_paths_are_exported_and_documented():
    import pathlib

    from tendermint_tpu.analysis.rules_catalog import metric_offenders

    text = REGISTRY.prometheus_text()
    for path in ("shared", "plain"):
        assert f'{DECODED}{{path="{path}"}}' in text
    docs = (pathlib.Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md").read_text()
    assert f"| `{DECODED}{{path}}`" in docs
    assert metric_offenders() == []


def test_varint_spans_name_every_varint_of_a_vote():
    blob = wire(300)
    spans = varint_spans(blob)
    assert tuple(spans) == VARINTS
    for name in VARINTS:
        again = Vote.decode(padded(blob, name))
        assert again == Vote.decode(blob) and again._encoded is None
    assert blob[spans["signature_len"][0]] == 64 and encode_uvarint(300) == blob[slice(*spans["index"])]
