"""Vote: a signed prevote/precommit (reference `types/vote.go`).

Sign-bytes are canonical JSON wrapped with the chain ID
(reference `types/canonical_json.go:50-53`, `types/vote.go:60-65`); the
validator's identity is NOT in the sign-bytes — identity binds via the
signature key, which is what makes commit signatures batchable as
(pubkey, message, signature) triples with a shared message per block.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import ClassVar

from tendermint_tpu.codec import (
    Reader,
    Writer,
    canonical_dumps,
    decode_svarint,
    decode_uvarint,
    encode_uvarint,
)
from tendermint_tpu.telemetry.metrics import COMMIT_VOTES_DECODED, VOTE_ENCODES, VOTE_WIRE_KEPT
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.errors import ValidationError

VOTE_TYPE_PREVOTE = 1
VOTE_TYPE_PRECOMMIT = 2

_DECODED_SHARED = COMMIT_VOTES_DECODED.labels(path="shared")
_DECODED_PLAIN = COMMIT_VOTES_DECODED.labels(path="plain")


def is_vote_type_valid(t: int) -> bool:
    return t in (VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT)


@dataclass(frozen=True)
class Vote:
    validator_address: bytes
    validator_index: int
    height: int
    round: int
    timestamp: int  # ns since epoch
    type: int
    block_id: BlockID
    signature: bytes = b""

    # `encode()`'s result, kept on the object once computed, or the bytes
    # `decode` read the vote from where they are that result. No dataclass
    # field: `__eq__`, `__hash__`, `repr` and `replace` never see it, and a
    # vote made by `replace` / `with_signature` starts without one.
    _encoded: ClassVar[bytes | None] = None

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical_dumps(
            {
                "chain_id": chain_id,
                "vote": {
                    "block_id": self.block_id.to_dict(),
                    "height": self.height,
                    "round": self.round,
                    "timestamp": self.timestamp,
                    "type": self.type,
                },
            }
        )

    def with_signature(self, sig: bytes) -> "Vote":
        return replace(self, signature=sig)

    def validate_basic(self) -> None:
        if not is_vote_type_valid(self.type):
            raise ValidationError(f"invalid vote type {self.type}")
        if self.height < 1:
            raise ValidationError("vote height must be >= 1")
        if self.round < 0:
            raise ValidationError("negative vote round")
        if self.validator_index < 0:
            raise ValidationError("negative validator index")

    def encode(self) -> bytes:
        """The canonical wire encoding, computed at most once a vote: the
        vote is frozen, and a commit's hash, its block's part set, the
        store and the WAL all ask for the same bytes. A vote that `decode`
        read from canonical bytes has them already and is never encoded."""
        encoded = self._encoded
        if encoded is None:
            encoded = (
                Writer()
                .bytes(self.validator_address)
                .uvarint(self.validator_index)
                .uvarint(self.height)
                .uvarint(self.round)
                .svarint(self.timestamp)
                .uvarint(self.type)
                .raw(self.block_id.encode())
                .bytes(self.signature)
                .build()
            )
            object.__setattr__(self, "_encoded", encoded)
            VOTE_ENCODES.inc()
        return encoded

    @classmethod
    def decode_from(cls, r: Reader) -> "Vote":
        return cls(
            validator_address=r.bytes(),
            validator_index=r.uvarint(),
            height=r.uvarint(),
            round=r.uvarint(),
            timestamp=r.svarint(),
            type=r.uvarint(),
            block_id=BlockID.decode_from(r),
            signature=r.bytes(),
        )

    @classmethod
    def decode(cls, data: bytes) -> "Vote":
        """The vote in `data`, and `data` kept as its encoding when, and
        only when, `encode()` would build those same bytes: the field
        order is fixed and nothing is optional, so that is when no varint
        in them was padded (a peer's only freedom; `Reader.padded`) and
        nothing trails. A vote from padded bytes is encoded for itself."""
        r = Reader(data)
        v = cls.decode_from(r)
        r.expect_done()
        if not r.padded:
            # immutable bytes are kept as they are; anything else is copied
            encoded = data if type(data) is bytes else bytes(data)
            object.__setattr__(v, "_encoded", encoded)
            VOTE_WIRE_KEPT.inc()
        return v

    def __str__(self) -> str:
        tname = {VOTE_TYPE_PREVOTE: "Prevote", VOTE_TYPE_PRECOMMIT: "Precommit"}.get(
            self.type, "?"
        )
        return (
            f"Vote{{{self.validator_index}:{self.validator_address.hex()[:8]} "
            f"{self.height}/{self.round}/{tname} {self.block_id}}}"
        )


def _votes_like(first: Vote):
    """A decoder for the votes of `first`'s commit. By `Commit.validate_basic`
    they have its height, round and type, and those for the block have its
    `block_id`: of a precommit's 143 bytes only the address, the index, the
    timestamp and the signature are a validator's own. `first` was read from
    canonical bytes, so its height and round stand in them as `head` and its
    type and `block_id`, up to and with the prefix of a 64-byte signature,
    as `tail`.

    `decode(b)` gives the vote in the non-empty `b` if `b` is laid out as
    `first`'s bytes are, and None, never an error, if not: a 20-byte
    address under a one-byte prefix, a minimal index, `head`, a minimal
    timestamp that ends where `tail` begins, `tail`, 64 bytes and no more.
    Every varint in such bytes is minimal, so they are the vote's encoding
    (`Vote.decode`'s rule) and are kept as that; the vote is the one
    `Vote.decode(b)` gives, but for the `BlockID` it shares with `first`.
    The timestamp is read from each vote."""
    head = encode_uvarint(first.height) + encode_uvarint(first.round)
    tail = encode_uvarint(first.type) + first.block_id.encode() + b"\x40"
    head_len, tail_len = len(head), len(tail)
    height, round_, type_, block_id = first.height, first.round, first.type, first.block_id
    new = object.__new__

    def decode(b: bytes) -> Vote | None:
        if b[0] != 20:
            return None
        try:
            index, head_at = decode_uvarint(b, 21)
            stamp_at = head_at + head_len
            timestamp, tail_at = decode_svarint(b, stamp_at)
        except ValueError:
            return None
        size = len(b)
        if (
            tail_at + tail_len + 64 != size
            or b[head_at:stamp_at] != head
            or b[tail_at : size - 64] != tail
            # a varint of several bytes that ends in 0x00 is padded
            or (head_at > 22 and not b[head_at - 1])
            or (tail_at > stamp_at + 1 and not b[tail_at - 1])
        ):
            return None
        v = new(Vote)
        # the frozen vote filled in one step, `_encoded` with its fields
        v.__dict__.update(
            validator_address=b[1:21],
            validator_index=index,
            height=height,
            round=round_,
            timestamp=timestamp,
            type=type_,
            block_id=block_id,
            signature=b[size - 64 :],
            _encoded=b,
        )
        return v

    return decode


def decode_commit_votes(r: Reader, n: int) -> list[Vote | None]:
    """A commit's `n` precommits off `r`, each under a length prefix, None
    where the bytes are empty (an absent validator).

    The first one present is read by `Vote.decode`. If its bytes were kept
    (they were canonical), every later one whose bytes show the same layout
    and the same height, round, type and `block_id` is read against them
    (`_votes_like`): no `Reader`, no `BlockID` and `PartSetHeader` of its
    own. Any other (a padded varint, another length of address or
    signature, a nil vote, a vote for another block or round, trailing
    or missing bytes) goes to `Vote.decode` as before, so its value, its
    kept bytes and its error are that function's. The choice is made from
    the bytes alone and the result equals a `Vote.decode` of each."""
    votes: list[Vote | None] = []
    like = None
    plain = 0
    for _ in range(n):
        b = r.bytes()
        if not b:
            votes.append(None)
            continue
        v = like(b) if like is not None else None
        if v is None:
            v = Vote.decode(b)
            if not plain and v._encoded is not None:
                like = _votes_like(v)
            plain += 1
        votes.append(v)
    shared = n - votes.count(None) - plain
    VOTE_WIRE_KEPT.inc(shared)
    _DECODED_SHARED.inc(shared)
    _DECODED_PLAIN.inc(plain)
    return votes
