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

from tendermint_tpu.codec import Reader, Writer, canonical_dumps
from tendermint_tpu.telemetry.metrics import VOTE_ENCODES, VOTE_WIRE_KEPT
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.errors import ValidationError

VOTE_TYPE_PREVOTE = 1
VOTE_TYPE_PRECOMMIT = 2


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
