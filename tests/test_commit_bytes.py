"""A vote's bytes are built at most once.

`Commit.vote_sign_bytes` hands every commit verifier one sign-bytes
encoding per distinct signed content, `Vote.encode` keeps its bytes on
the frozen vote, and `Vote.decode` keeps the bytes it read as that
encoding when they are canonical (every varint minimal) and only then.
None may change a byte: what a verifier is given must be what
`Vote.sign_bytes` gives for each vote, whatever the commit looks like,
and a vote's encoding is the canonical one, whatever it was decoded from.

All on the CPU: the verifiers here record what they are handed and
answer with the host library.
"""

from __future__ import annotations

import dataclasses
import functools
import random

import numpy as np
import pytest

from tendermint_tpu.codec import Reader, Writer, encode_svarint, encode_uvarint
from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.services.verifier import HostBatchVerifier
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.types import (
    VOTE_TYPE_PRECOMMIT,
    BlockID,
    Commit,
    ValidationError,
    ValidatorSet,
    Vote,
)
from tendermint_tpu.types.part_set import PartSetHeader

from tests.helpers import (
    CHAIN_ID,
    det_priv_keys,
    make_block_id,
    make_validators,
    pad_varint,
)

HEIGHT = 7
STAMP = 1_700_000_000_000_000_000
SIZES = (1, 4, 100, 128)
SHAPES = ("all_signing", "some_absent", "other_block", "nil_votes", "timestamps", "mixed")
SIGNBYTES = "tendermint_commit_signbytes_total"


def signbytes_counts() -> dict:
    """`tendermint_commit_signbytes_total` by `source`, as it stands."""
    return {
        source: REGISTRY.counter_value(SIGNBYTES, source=source)
        for source in ("encoded", "shared")
    }


@functools.lru_cache(maxsize=None)
def _validators(n: int):
    vals, privs = make_validators(n)
    # the bare keys: a PrivValidator would refuse the second commit of a height
    keys = {k.pub_key.data: k for k in det_priv_keys(n)}
    return vals, [keys[p.pub_key.data] for p in privs]


def _vote(vals, keys, i: int, block_id: BlockID, stamp: int) -> Vote:
    vote = Vote(
        validator_address=vals.validators[i].address,
        validator_index=i,
        height=HEIGHT,
        round=0,
        timestamp=stamp,
        type=VOTE_TYPE_PRECOMMIT,
        block_id=block_id,
    )
    return vote.with_signature(keys[i].sign(vote.sign_bytes(CHAIN_ID)))


def make_commit(n: int, shape: str, seed: int) -> tuple[ValidatorSet, BlockID, Commit]:
    """A seeded commit of `n` validators for one block. `shape` says how
    a minority of under a third (all of them, at n = 1) departs from
    "everyone signed this block at one time"."""
    rng = random.Random(f"{n}-{shape}-{seed}")
    vals, keys = _validators(n)
    block_id = make_block_id(b"block-%d" % seed)
    odd = set() if shape == "all_signing" else set(rng.sample(range(n), max(1, (n - 1) // 3)))
    precommits: list[Vote | None] = []
    for i in range(n):
        kind = shape if shape != "mixed" else rng.choice(SHAPES[1:5])
        if i not in odd:
            precommits.append(_vote(vals, keys, i, block_id, STAMP))
        elif kind == "some_absent":
            precommits.append(None)
        elif kind == "other_block":
            precommits.append(_vote(vals, keys, i, make_block_id(b"other-%d" % rng.randrange(2)), STAMP))
        elif kind == "nil_votes":
            precommits.append(_vote(vals, keys, i, BlockID.zero(), STAMP))
        else:
            precommits.append(_vote(vals, keys, i, block_id, STAMP + rng.randrange(1, 3)))
    return vals, block_id, Commit(block_id=block_id, precommits=precommits)


def has_quorum(vals: ValidatorSet, block_id: BlockID, commit: Commit) -> bool:
    power = sum(
        vals.validators[i].voting_power
        for i, v in enumerate(commit.precommits)
        if v is not None and v.block_id == block_id
    )
    return power * 3 > vals.total_voting_power * 2


class FlatRecorder:
    """A verifier with `verify_batch` alone: records every triple."""

    def __init__(self) -> None:
        self.triples: list[tuple[bytes, bytes, bytes]] = []
        self._host = HostBatchVerifier()

    def verify_batch(self, triples):
        self.triples.extend(triples)
        return self._host.verify_batch(triples)


class GridRecorder(FlatRecorder):
    """The valset-table surface (`verify_commits`): records the lanes."""

    def verify_commits(self, pubkeys, commits):
        grid = np.zeros((len(commits), len(pubkeys)), dtype=bool)
        for ci, (msgs, sigs) in enumerate(commits):
            for i, (msg, sig) in enumerate(zip(msgs, sigs)):
                if msg is not None:
                    self.triples.append((pubkeys[i], msg, sig))
                    grid[ci, i] = PubKey(pubkeys[i]).verify(msg, sig)
        return grid


def expected_triples(vals: ValidatorSet, commit: Commit, only_for: BlockID | None = None):
    return [
        (vals.validators[i].pub_key.data, v.sign_bytes(CHAIN_ID), v.signature)
        for i, v in enumerate(commit.precommits)
        if v is not None and (only_for is None or v.block_id == only_for)
    ]


def _walk(path: str, vals, block_id, commit, verifier) -> None:
    if path == "verify_commit":
        vals.verify_commit(CHAIN_ID, block_id, HEIGHT, commit, verifier)
    elif path == "verify_commit_batched_async":
        vals.verify_commit_batched_async(
            CHAIN_ID, [(block_id, HEIGHT, commit)], verifier
        ).result()
    else:
        vals.verify_commit_any(vals, CHAIN_ID, block_id, HEIGHT, commit, verifier)


PATHS = ("verify_commit", "verify_commit_batched_async", "verify_commit_any")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("n", SIZES)
def test_every_verifier_is_handed_each_votes_own_sign_bytes(n, shape, path):
    vals, block_id, commit = make_commit(n, shape, seed=n)
    # the commit-grid surface for one path, flat triples for the others
    recorder = GridRecorder() if path == "verify_commit" else FlatRecorder()
    if has_quorum(vals, block_id, commit):
        _walk(path, vals, block_id, commit, recorder)
    else:
        assert n == 1  # its one validator is the minority: no power, or no vote at all
        with pytest.raises(ValidationError, match="insufficient|commit height"):
            _walk(path, vals, block_id, commit, recorder)
    # the any-walk leaves out votes for other blocks; the others verify all
    only_for = block_id if path == "verify_commit_any" else None
    assert recorder.triples == expected_triples(vals, commit, only_for)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("n", SIZES)
def test_shared_sign_bytes_are_one_object_a_content_and_counted_once_a_commit(n, shape):
    vals, block_id, commit = make_commit(n, shape, seed=1000 + n)
    before = signbytes_counts()
    msgs = commit.vote_sign_bytes(CHAIN_ID)
    rise = {k: v - before[k] for k, v in signbytes_counts().items()}
    assert len(msgs) == n
    present = [v for v in commit.precommits if v is not None]
    contents = {(v.block_id, v.timestamp) for v in present}
    for v, msg in zip(commit.precommits, msgs):
        assert msg == (v.sign_bytes(CHAIN_ID) if v is not None else None)
    assert len({id(m) for m in msgs if m is not None}) == len(contents)
    assert rise == {"encoded": len(contents), "shared": len(present) - len(contents)}
    if shape == "all_signing":
        assert rise == {"encoded": 1, "shared": n - 1}


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("n", (4, 100, 128))
def test_one_flipped_signature_bit_is_refused_at_its_validator(n, path):
    vals, block_id, commit = make_commit(n, "all_signing", seed=2000 + n)
    bad = (n * 5) // 7
    sig = bytearray(commit.precommits[bad].signature)
    sig[17] ^= 0x04
    commit.precommits[bad] = commit.precommits[bad].with_signature(bytes(sig))
    where = "old set" if path == "verify_commit_any" else f"from validator {bad}$"
    with pytest.raises(ValidationError, match=f"invalid commit signature.*{where}"):
        _walk(path, vals, block_id, commit, HostBatchVerifier())
    # and the verdict the verifier gave names that lane alone
    recorder = FlatRecorder()
    with pytest.raises(ValidationError):
        _walk(path, vals, block_id, commit, recorder)
    verdicts = HostBatchVerifier().verify_batch(recorder.triples)
    assert [i for i, ok in enumerate(verdicts) if not ok] == [bad]


def test_a_window_of_commits_shares_within_a_commit_never_across():
    """`verify_commit_batched`: K commits of one set, each with its own
    block and time: K encodings, and every lane its own vote's bytes."""
    n, k = 16, 5
    made = [make_commit(n, "all_signing", seed=3000 + j) for j in range(k)]
    vals = made[0][0]
    recorder = GridRecorder()
    vals.verify_commit_batched(
        CHAIN_ID, [(bid, HEIGHT, c) for _, bid, c in made], recorder
    )
    want = [t for _, _, c in made for t in expected_triples(vals, c)]
    assert recorder.triples == want
    assert len({id(msg) for _, msg, _ in recorder.triples}) == k


ENCODES = "tendermint_vote_encodes_total"
WIRE_KEPT = "tendermint_vote_wire_kept_total"

# the ten varints of a vote's encoding, in wire order
VARINTS = (
    "address_length", "index", "height", "round", "timestamp", "type",
    "hash_length", "parts_total", "parts_hash_length", "signature_length",
)


def vote_counts() -> dict:
    return {name: REGISTRY.counter_value(name) for name in (ENCODES, WIRE_KEPT)}


def rise_since(before: dict) -> dict:
    return {name: value - before[name] for name, value in vote_counts().items()}


def varint_spans(v: Vote) -> dict[str, tuple[int, int]]:
    """Where each varint of `v.encode()` starts and ends, by walking the
    fields as the format lays them out (`vote.py`, `block_id.py`,
    `part_set.py`): a varint's length is its canonical encoding's."""
    psh = v.block_id.parts_header
    blobs = (v.validator_address, v.block_id.hash, psh.hash, v.signature)
    # each varint as written, and the payload bytes that follow it
    layout = (
        (encode_uvarint(len(blobs[0])), len(blobs[0])),
        (encode_uvarint(v.validator_index), 0),
        (encode_uvarint(v.height), 0),
        (encode_uvarint(v.round), 0),
        (encode_svarint(v.timestamp), 0),
        (encode_uvarint(v.type), 0),
        (encode_uvarint(len(blobs[1])), len(blobs[1])),
        (encode_uvarint(psh.total), 0),
        (encode_uvarint(len(blobs[2])), len(blobs[2])),
        (encode_uvarint(len(blobs[3])), len(blobs[3])),
    )
    spans, at = {}, 0
    for name, (varint, payload) in zip(VARINTS, layout, strict=True):
        spans[name] = (at, at + len(varint))
        at += len(varint) + payload
    assert at == len(v.encode())
    return spans


class TestVoteEncode:
    def _vote(self, seed: int = 1) -> Vote:
        vals, _bid, commit = make_commit(4, "all_signing", seed=4000 + seed)
        return commit.precommits[seed % 4]

    @staticmethod
    def _computed() -> float:
        return REGISTRY.counter_value(ENCODES)

    def test_the_encoding_is_computed_once_and_every_call_returns_it(self):
        v = dataclasses.replace(self._vote())  # not encoded yet
        before = self._computed()
        first = v.encode()
        assert self._computed() - before == 1
        assert v.encode() is first and v.encode() == first
        assert self._computed() - before == 1

    def test_it_is_the_canonical_encoding_and_decodes_to_the_vote(self):
        v = self._vote(2)
        wire = v.encode()
        before = vote_counts()
        again = Vote.decode(wire)
        assert again == v and again is not v
        # canonical bytes are the decoded vote's encoding: kept, not rebuilt
        assert again._encoded is wire
        assert again.encode() is wire
        assert rise_since(before) == {ENCODES: 0, WIRE_KEPT: 1}

    @pytest.mark.parametrize("pad", (1, 2))
    @pytest.mark.parametrize("where", VARINTS)
    def test_a_non_minimal_varint_decodes_but_is_not_kept_as_the_encoding(self, where, pad):
        v = self._vote(3)
        wire = v.encode()
        padded = pad_varint(wire, varint_spans(v)[where], pad)
        assert len(padded) == len(wire) + pad
        before = vote_counts()
        loose = Vote.decode(padded)
        assert loose == v
        assert loose._encoded is None  # never handed its wire bytes
        assert rise_since(before) == {ENCODES: 0, WIRE_KEPT: 0}
        assert loose.encode() == wire != padded
        assert loose.encode() is loose._encoded
        assert rise_since(before) == {ENCODES: 1, WIRE_KEPT: 0}

    def test_bytes_that_are_not_immutable_are_copied_not_kept(self):
        v = self._vote(2)
        wire = v.encode()
        for mutable in (bytearray(wire), memoryview(bytearray(wire))):
            got = Vote.decode(mutable)
            assert got == v
            assert type(got._encoded) is bytes and got._encoded == wire
            mutable[-1] ^= 0xFF  # the caller's buffer is the caller's
            assert got.encode() == wire
        assert Vote.decode(memoryview(wire)).encode() == wire

    def test_trailing_bytes_are_refused_as_before(self):
        wire = self._vote().encode()
        with pytest.raises(ValueError, match="1 trailing bytes"):
            Vote.decode(wire + b"\x00")
        with pytest.raises(ValueError, match="truncated"):
            Vote.decode(wire[:-1])

    EDGES = (0, 1, 127, 128, 16383, 16384, 2**31, 2**63 - 1)
    # the decoder takes eleven bytes a varint: nine here, so two pads fit
    STAMPS = (0, -1, 1, -64, 63, 64, -65, STAMP, -STAMP, 2**61, -(2**61))

    def _random_vote(self, rng: random.Random) -> Vote:
        """Any vote the codec can carry, valid or not: the rule is about
        bytes, not about `validate_basic`."""
        def blob(*sizes: int) -> bytes:
            return rng.randbytes(rng.choice(sizes))

        block_id = rng.choice((
            BlockID.zero(),
            BlockID(blob(0, 20, 32), PartSetHeader(rng.choice(self.EDGES), blob(0, 20, 32))),
        ))
        return Vote(
            validator_address=blob(0, 20, 127, 128),
            validator_index=rng.choice(self.EDGES),
            height=rng.choice(self.EDGES),
            round=rng.choice(self.EDGES),
            timestamp=rng.choice(self.STAMPS),
            type=rng.choice((0, 1, 2, 127, 128)),
            block_id=block_id,
            signature=blob(0, 64, 64, 200),
        )

    def _by_hand(self, v: Vote) -> bytes:
        """The format, written out field by field without `Vote.encode`."""
        psh = v.block_id.parts_header
        return (
            Writer().bytes(v.validator_address).uvarint(v.validator_index)
            .uvarint(v.height).uvarint(v.round).svarint(v.timestamp).uvarint(v.type)
            .bytes(v.block_id.hash).uvarint(psh.total).bytes(psh.hash)
            .bytes(v.signature).build()
        )

    @pytest.mark.parametrize("seed", range(8))
    def test_decoded_bytes_are_kept_exactly_when_they_are_the_canonical_ones(self, seed):
        rng = random.Random(f"vote-bytes-{seed}")
        for _ in range(150):
            v = self._random_vote(rng)
            canonical = self._by_hand(v)
            assert v.encode() == canonical
            spans = varint_spans(v)
            # the canonical bytes, and the same with one to three varints padded
            wires = [bytes(bytearray(canonical))]  # an equal object, not the memo
            for k in (1, 2, 3):
                x = canonical
                # from the back, so earlier spans stay where they are
                for where in sorted(rng.sample(VARINTS, k), key=VARINTS.index, reverse=True):
                    x = pad_varint(x, spans[where], rng.choice((1, 2)))
                wires.append(x)
            for x in wires:
                got = Vote.decode(x)
                assert got == v
                assert (got._encoded is x) == (x == canonical)
                assert got.encode() == canonical
                assert (got.encode() is x) == (x == canonical)

    def test_a_generated_block_decodes_and_encodes_to_the_bytes_it_came_in(self):
        from tendermint_tpu.types.block import Block

        from tests.helpers import ChainSim

        sim = ChainSim(n_vals=7)
        for h in range(4):
            sim.advance(txs=[b"k%d=v" % h])
        for block in sim.blocks:
            wire = block.encode()
            header = block.make_part_set().header
            before = vote_counts()
            again = Block.decode(wire)
            n = sum(v is not None for v in again.last_commit.precommits)
            assert again.encode() == wire
            assert again.make_part_set().header == header
            assert again.last_commit.hash() == block.last_commit.hash()
            assert again.hash() == block.hash()
            assert rise_since(before) == {ENCODES: 0, WIRE_KEPT: n}
            for kept, v in zip(again.last_commit.precommits, block.last_commit.precommits):
                assert kept == v and kept.encode() == v.encode()

    def test_with_signature_and_replace_start_from_a_fresh_encoding(self):
        v = self._vote()
        old = v.encode()
        resigned = v.with_signature(bytes(64))
        assert resigned.encode() != old and resigned.encode().endswith(bytes(64))
        assert Vote.decode(resigned.encode()) == resigned
        # and so does a vote made from one that kept its wire bytes
        kept = Vote.decode(old)
        assert kept._encoded is old
        assert kept.with_signature(bytes(64))._encoded is None
        assert dataclasses.replace(kept)._encoded is None
        later = dataclasses.replace(v, timestamp=v.timestamp + 1)
        assert later.encode() != old and Vote.decode(later.encode()) == later
        assert v.encode() is old

    def test_equality_hash_and_repr_ignore_the_memo(self):
        v = self._vote()
        w = dataclasses.replace(v)
        v.encode()
        assert w._encoded is None and v._encoded is not None
        assert v == w and hash(v) == hash(w) and repr(v) == repr(w)
        assert len({v, w}) == 1
        assert "_encoded" not in {f.name for f in dataclasses.fields(Vote)}
        with pytest.raises(dataclasses.FrozenInstanceError):
            v.signature = b""

    @pytest.mark.parametrize("shape", SHAPES)
    def test_a_commits_hash_and_encoding_are_what_fresh_votes_give(self, shape):
        _vals, _bid, commit = make_commit(16, shape, seed=5000)
        fresh = Commit(
            block_id=commit.block_id,
            precommits=[v and dataclasses.replace(v) for v in commit.precommits],
        )
        for _ in range(2):  # the second pass reads every memo
            assert commit.encode() == fresh.encode()
            assert commit.hash() == fresh.hash()
        again = Commit.decode_from(Reader(commit.encode()))
        assert again.precommits == commit.precommits and again.hash() == commit.hash()


class TestFastSyncCounts:
    """Over a fast-synced chain the counters read what the mechanism
    promises: no wire encoding of a vote that came in canonical bytes
    (they are kept), one sign-bytes encoding a commit."""

    N_VALS, N_BLOCKS = 16, 40

    def _synced(self):
        from tendermint_tpu.types.block import Block

        from tests.helpers import ChainSim, signed_vote
        from tests.test_fastsync import _pipelined_reactor

        class OneStampSim(ChainSim):
            """Every validator signs a height at the same time, as the
            benchmark's chain has it."""

            def _commit_for(self, block, part_set):
                block_id = BlockID(block.hash(), part_set.header)
                height = block.header.height
                return Commit(
                    block_id=block_id,
                    precommits=[
                        signed_vote(
                            priv, i, height, 0, VOTE_TYPE_PRECOMMIT, block_id,
                            self.chain_id, timestamp=STAMP + height,
                        )
                        for i, priv in enumerate(self._privs_in_valset_order())
                    ],
                )

        sim = OneStampSim(n_vals=self.N_VALS)
        for _ in range(self.N_BLOCKS):
            sim.advance()
        wires = [b.encode() for b in sim.blocks]
        names = (ENCODES, WIRE_KEPT, "tendermint_fastsync_blocks_applied_total")

        def read() -> dict:
            return {
                **{name: REGISTRY.counter_value(name) for name in names},
                **signbytes_counts(),
            }

        before = read()
        # as they come off the wire: the p2p thread's decode is in the count
        sim.blocks = [Block.decode(w) for w in wires]
        reactor, _state, store = _pipelined_reactor(
            sim, depth=2, verifier=HostBatchVerifier()
        )
        reactor._try_sync()
        assert store.height == self.N_BLOCKS - 1
        return {k: v - before[k] for k, v in read().items()}

    def test_a_block_costs_one_encoding_a_vote_and_one_sign_bytes_a_commit(self):
        rise = self._synced()
        blocks = rise["tendermint_fastsync_blocks_applied_total"]
        assert blocks == self.N_BLOCKS - 1
        # the part set, the commit's hash and both store rows: the bytes the
        # votes came in, never an encoding (block 1's last commit is empty)
        assert rise[ENCODES] == 0
        assert rise[WIRE_KEPT] == self.N_VALS * blocks
        # one commit walked a block, all sixteen votes the same content
        assert rise["encoded"] == blocks
        assert rise["shared"] == (self.N_VALS - 1) * blocks

    def test_both_counters_are_cataloged_and_documented(self):
        import pathlib

        from tendermint_tpu.analysis.rules_catalog import metric_offenders

        text = REGISTRY.prometheus_text()
        docs = (
            pathlib.Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md"
        ).read_text()
        assert f"{ENCODES} " in text and f"{WIRE_KEPT} " in text
        for source in ("encoded", "shared"):
            assert f'{SIGNBYTES}{{source="{source}"}}' in text
        for name in (ENCODES, WIRE_KEPT, SIGNBYTES):
            assert f"| `{name}" in docs
        assert metric_offenders() == []
