"""Validators and the weighted-round-robin proposer rotation.

Reference: `types/validator_set.go` — sorted-by-address set, `IncrementAccum`
proposer selection (`:52-69`), Merkle hash of the set (`:145`), and the HOT
LOOP `VerifyCommit` (`:225-269`) which the reference runs as N sequential
ed25519 verifications. Here `verify_commit` routes through a `BatchVerifier`
(one device batch per commit) with the host loop as fallback.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from tendermint_tpu.codec import Writer
from tendermint_tpu.crypto import PubKey
from tendermint_tpu.merkle import simple_hash_from_byte_slices
from tendermint_tpu.telemetry.metrics import VALSET_HASHES
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.errors import (
    ErrCommitRefused,
    ErrTooMuchChange,
    ValidationError,
)
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT


@dataclass(frozen=True)
class Validator:
    address: bytes
    pub_key: PubKey
    voting_power: int
    accum: int = 0

    def encode(self) -> bytes:
        """Deterministic encoding hashed into the validator-set root."""
        return (
            Writer().bytes(self.address).bytes(self.pub_key.data).uvarint(self.voting_power).build()
        )

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher accum wins; ties break to the lower address
        (reference `Validator.CompareAccum`)."""
        if self.accum > other.accum:
            return self
        if self.accum < other.accum:
            return other
        return self if self.address < other.address else other


class ValidatorSet:
    def __init__(self, validators: list[Validator]):
        """A set from outside data (genesis, JSON, the wire): checked and
        sorted here. `copy()` does not come back through these checks, so
        whatever else changes membership or a power (`apply_changes`)
        keeps them itself."""
        seen: set[bytes] = set()
        for v in validators:
            if v.address in seen:
                raise ValidationError(f"duplicate validator address {v.address.hex()}")
            if v.voting_power < 0:
                raise ValidationError("negative voting power")
            seen.add(v.address)
        self.validators: list[Validator] = sorted(validators, key=lambda v: v.address)
        # What follows depends on membership, keys and powers alone, never
        # on accum, and a copy SHARES it with its source. So none of it is
        # ever mutated in place: each is built once and rebound (to a new
        # object or None) by whoever changes what it was built from, which
        # is apply_changes and nothing else.
        self._total = sum(v.voting_power for v in self.validators)
        self._addr_index: dict[bytes, int] | None = None
        self._hash: bytes | None = None
        self._json_static: tuple[str, ...] | None = None
        # rebound by every rotation as well
        self._proposer: Validator | None = None

    # -- basic accessors ---------------------------------------------------

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    @property
    def total_voting_power(self) -> int:
        return self._total

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        # amortized O(1): the address->index map is built once per
        # membership change (the reference's sort.Search is O(log n) per
        # call; per-precommit lookups in verify_commit_any make anything
        # worse than this quadratic at 10k validators)
        if self._addr_index is None:
            self._addr_index = {
                v.address: i for i, v in enumerate(self.validators)
            }
        i = self._addr_index.get(address, -1)
        if i < 0:
            return -1, None
        return i, self.validators[i]

    def get_by_index(self, index: int) -> Validator | None:
        if 0 <= index < len(self.validators):
            return self.validators[index]
        return None

    def has_address(self, address: bytes) -> bool:
        return self.get_by_address(address)[0] >= 0

    def copy(self) -> "ValidatorSet":
        """A set that rotates and changes apart from this one. Its list is
        its own; the (frozen) validators and everything derived from
        membership are this set's, not checked, sorted, summed or hashed
        again: a block rotates the set, it does not rebuild it."""
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = list(self.validators)
        vs._total = self._total
        vs._addr_index = self._addr_index
        vs._hash = self._hash
        vs._json_static = self._json_static
        vs._proposer = self._proposer
        return vs

    # -- proposer rotation -------------------------------------------------

    def increment_accum(self, times: int = 1) -> None:
        """Weighted round-robin (reference `IncrementAccum
        types/validator_set.go:52-69`): each step adds voting power to every
        accumulator, picks the max as proposer, subtracts total power from it.
        Higher accum wins and ties break to the lower address (reference
        `Validator.CompareAccum`): the list is sorted by address, so that is
        the first maximum. Only the accumulators move."""
        if times <= 0:
            return
        if not self.validators:
            raise ValidationError("empty validator set has no proposer")
        powers = [v.voting_power for v in self.validators]
        accums = [v.accum for v in self.validators]
        for _ in range(times):
            accums = [a + p for a, p in zip(accums, powers)]
            top = max(accums)
            idx = accums.index(top)
            accums[idx] = top - self._total
        self.validators = [
            Validator(v.address, v.pub_key, v.voting_power, a)
            for v, a in zip(self.validators, accums)
        ]
        self._proposer = self.validators[idx]

    @property
    def proposer(self) -> Validator:
        if not self.validators:
            raise ValidationError("empty validator set has no proposer")
        if self._proposer is None:
            p = self.validators[0]
            for v in self.validators[1:]:
                p = p.compare_proposer_priority(v)
            self._proposer = p
        return self._proposer

    # -- hashing -----------------------------------------------------------

    def hash(self) -> bytes:
        """Merkle root of the validator encodings (reference `Hash :145`).
        Kept, and handed on by copy(): the encoding covers
        address/pubkey/power only, so accum rotation (increment_accum)
        does not change it; membership/power changes drop it in
        apply_changes."""
        if self._hash is None:
            VALSET_HASHES.inc()
            self._hash = simple_hash_from_byte_slices(
                [v.encode() for v in self.validators]
            )
        return self._hash

    def to_json(self) -> str:
        """The set as the state document and the per-height validators
        rows carry it (`state/state.py`): byte for byte
        `json.dumps({"validators": [{"accum", "address", "pub_key",
        "voting_power"}, ...]}, sort_keys=True)`; `from_dict` reads it.
        A block moves only the accums, so the hex of an address and a key
        and the power are formatted once a membership (kept beside the
        root, by the root's rule) and only `accum` once a call."""
        static = self._json_static
        if static is None:
            static = self._json_static = tuple(
                ', "address": "%s", "pub_key": "%s", "voting_power": %d}'
                % (v.address.hex(), v.pub_key.data.hex(), v.voting_power)
                for v in self.validators
            )
        rows = ['{"accum": %d%s' % (v.accum, s) for v, s in zip(self.validators, static)]
        return '{"validators": [' + ", ".join(rows) + "]}"

    @classmethod
    def from_dict(cls, d: dict) -> "ValidatorSet":
        """The set `to_json` wrote, parsed: outside data, so checked."""
        return cls(
            [
                Validator(
                    address=bytes.fromhex(v["address"]),
                    pub_key=PubKey(bytes.fromhex(v["pub_key"])),
                    voting_power=v["voting_power"],
                    accum=v["accum"],
                )
                for v in d["validators"]
            ]
        )

    # -- membership changes (EndBlock diffs) --------------------------------

    def apply_changes(self, changes: list[Validator]) -> None:
        """Apply app-driven diffs: power 0 removes, new address adds, else
        updates (reference `updateValidators state/execution.go:120-159`).
        The one place that changes membership or a power, so the one place
        that drops what copies share: by rebinding, never in place (the
        source of this copy still holds the old index, root and total)."""
        for c in changes:
            if c.voting_power < 0:
                raise ValidationError("negative voting power")
            idx, existing = self.get_by_address(c.address)
            if c.voting_power == 0:
                if existing is None:
                    raise ValidationError("removing unknown validator")
                self.validators.pop(idx)
                # positions shifted: drop the cached address index so the
                # next lookup in this same batch rebuilds it
                self._addr_index = None
            elif existing is None:
                self.validators.append(replace(c, accum=0))
                # keep sorted so index order stays canonical for any
                # further change in this same batch
                self.validators.sort(key=lambda v: v.address)
                self._addr_index = None
            else:
                self.validators[idx] = replace(existing, voting_power=c.voting_power)
        self._total = sum(v.voting_power for v in self.validators)
        self._proposer = None
        self._addr_index = None
        self._hash = None
        self._json_static = None

    # -- commit verification (the hot loop) ---------------------------------

    def _collect_commit_sigs(
        self, chain_id: str, block_id: BlockID, height: int, commit
    ) -> tuple[list[tuple[bytes, bytes, bytes]], list[int]]:
        """Shared validation walk: returns (pubkey,msg,sig) triples and the
        vote indices they came from."""
        if len(self.validators) != len(commit.precommits):
            raise ValidationError(
                f"commit size {len(commit.precommits)} != valset size {len(self.validators)}"
            )
        if height != commit.height():
            raise ValidationError(f"commit height {commit.height()} != {height}")
        round_ = commit.round()
        triples: list[tuple[bytes, bytes, bytes]] = []
        indices: list[int] = []
        msgs = commit.vote_sign_bytes(chain_id)
        for idx, precommit in enumerate(commit.precommits):
            if precommit is None:
                continue
            if precommit.height != height:
                raise ValidationError(f"precommit height {precommit.height} != {height}")
            if precommit.round != round_:
                raise ValidationError(f"precommit round {precommit.round} != {round_}")
            if precommit.type != VOTE_TYPE_PRECOMMIT:
                raise ValidationError("commit vote is not a precommit")
            val = self.validators[idx]
            triples.append((val.pub_key.data, msgs[idx], precommit.signature))
            indices.append(idx)
        return triples, indices

    def verify_commit(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit,
        verifier=None,
        consumer: str = "default",
    ) -> None:
        """Raise unless >2/3 of this set's power signed block_id at height.

        Reference `VerifyCommit types/validator_set.go:225-269` — but instead
        of one ed25519 verify per iteration, all signatures flush as a single
        device batch when a `BatchVerifier` is supplied. The K=1 case of
        `verify_commit_batched`.
        """
        self.verify_commit_batched(
            chain_id, [(block_id, height, commit)], verifier, consumer=consumer
        )

    def verify_commit_batched(
        self,
        chain_id: str,
        entries: list[tuple[BlockID, int, "object"]],
        verifier=None,
        consumer: str = "default",
    ) -> None:
        """Verify K commits signed by THIS validator set as one device
        batch — the fast-sync window shape (BASELINE config 3; reference
        verifies one commit per loop iteration at
        `blockchain/reactor.go:259`). `entries` is a list of
        (block_id, height, commit). Raises naming the failing validator
        (and entry, when K > 1). Verifiers exposing `verify_commits`
        (the valset-table cache) get commits in validator-lane order so
        repeated commits of one valset hit cached per-validator comb
        tables; other verifiers get flat triple batches.

        Verifiers advertising the consumer-tag surface (the coalescing
        stack) are routed through the ASYNC handles and joined here —
        that is how blocking callers (the certifier walk, statesync
        trust anchoring) coalesce with concurrent consumers for free.
        """
        if verifier is None:
            from tendermint_tpu.services.verifier import default_verifier

            verifier = default_verifier()
        if getattr(verifier, "accepts_consumer", False):
            self.verify_commit_batched_async(
                chain_id, entries, verifier, consumer=consumer
            ).result()
            return
        collected = self._collect_entries(chain_id, entries)
        n = len(self.validators)
        if hasattr(verifier, "verify_commits") and any(
            triples for triples, _ in collected
        ):
            grid = verifier.verify_commits(
                [v.pub_key.data for v in self.validators],
                self._commit_lanes(collected, n),
            )
            ok_by_entry = self._grid_to_entry_oks(grid, collected)
        else:
            ok_by_entry = [
                _verify_triples(triples, verifier) for triples, _ in collected
            ]
        self._tally_commit_verdicts(entries, collected, ok_by_entry)

    def verify_commit_batched_async(
        self,
        chain_id: str,
        entries: list[tuple[BlockID, int, "object"]],
        verifier=None,
        queue=None,
        consumer: str = "default",
    ):
        """Pipelined `verify_commit_batched`: lane prep + device submit
        happen NOW (the caller's host-prep stage), the quorum tally —
        and any ValidationError — at the returned handle's `.result()`.

        Malformed commits (size/height/round mismatches) still raise
        synchronously here, before anything is launched: the fast-sync
        pipeline treats that exactly like a failed verdict. Verifiers
        without an async surface verify inline and hand back an
        already-resolved handle, so callers stay uniform.
        """
        if verifier is None:
            from tendermint_tpu.services.verifier import default_verifier

            verifier = default_verifier()
        collected = self._collect_entries(chain_id, entries)
        n = len(self.validators)

        from tendermint_tpu.services.batcher import consumer_kwargs

        kw = consumer_kwargs(verifier, consumer)
        if hasattr(verifier, "verify_commits_async") and any(
            triples for triples, _ in collected
        ):
            handle = verifier.verify_commits_async(
                [v.pub_key.data for v in self.validators],
                self._commit_lanes(collected, n),
                queue=queue,
                **kw,
            )

            def _tally_grid(grid):
                self._tally_commit_verdicts(
                    entries, collected, self._grid_to_entry_oks(grid, collected)
                )
                return True

            return handle.then(_tally_grid)
        if hasattr(verifier, "verify_batch_async"):
            flat = [t for triples, _ in collected for t in triples]
            handle = verifier.verify_batch_async(flat, queue=queue, **kw)

            def _tally_flat(mask):
                ok_by_entry, at = [], 0
                for triples, _ in collected:
                    ok_by_entry.append(
                        [bool(v) for v in mask[at : at + len(triples)]]
                    )
                    at += len(triples)
                self._tally_commit_verdicts(entries, collected, ok_by_entry)
                return True

            return handle.then(_tally_flat)
        from tendermint_tpu.services.dispatch import CompletedHandle

        try:
            self.verify_commit_batched(chain_id, entries, verifier)
        except ValidationError as e:
            return CompletedHandle(exc=e)
        return CompletedHandle(True)

    @staticmethod
    def _commit_lanes(collected, n: int) -> list[tuple[list, list]]:
        """Triples+indices -> validator-index-aligned (msgs, sigs) lanes
        for the commit-grid verifiers (cached comb tables)."""
        lanes: list[tuple[list, list]] = []
        for triples, indices in collected:
            msgs: list[bytes | None] = [None] * n
            sigs: list[bytes | None] = [None] * n
            for (pk, msg, sig), idx in zip(triples, indices):
                msgs[idx], sigs[idx] = msg, sig
            lanes.append((msgs, sigs))
        return lanes

    @staticmethod
    def _grid_to_entry_oks(grid, collected) -> list[list[bool]]:
        return [
            [bool(grid[ei][i]) for i in indices]
            for ei, (_, indices) in enumerate(collected)
        ]

    def _collect_entries(self, chain_id: str, entries) -> list:
        """`_collect_commit_sigs` over a batch; a malformed commit is
        refused by its place in the batch, as a failed verdict is."""
        collected = []
        for ei, (block_id, height, commit) in enumerate(entries):
            try:
                collected.append(
                    self._collect_commit_sigs(chain_id, block_id, height, commit)
                )
            except ValidationError as e:
                raise ErrCommitRefused(str(e), entry=ei, height=height) from e
        return collected

    def _tally_commit_verdicts(self, entries, collected, ok_by_entry) -> None:
        """Shared quorum walk, in the batch's order: raises at the first
        entry that fails, naming it (`ErrCommitRefused`: its index, its
        height and, for a bad signature, the validator; in the message
        the entry when K > 1), else requires >2/3 power per entry. So
        every entry before the one named is verified."""
        for ei, ((block_id, height, commit), (_, indices), oks) in enumerate(
            zip(entries, collected, ok_by_entry)
        ):
            tallied = 0
            for ok, idx in zip(oks, indices):
                if not ok:
                    raise ErrCommitRefused(
                        f"invalid commit signature from validator {idx}"
                        + _where(entries, ei, height),
                        entry=ei,
                        height=height,
                        validator=idx,
                        prefix_verified=True,
                    )
                if commit.precommits[idx].block_id == block_id:
                    tallied += self.validators[idx].voting_power
            if not tallied * 3 > self._total * 2:
                raise ErrCommitRefused(
                    f"insufficient voting power: {tallied} of {self._total}"
                    + _where(entries, ei, height),
                    entry=ei,
                    height=height,
                    prefix_verified=True,
                )

    def verify_commit_any(
        self,
        new_set: "ValidatorSet",
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit,
        verifier=None,
        consumer: str = "default",
    ) -> None:
        """Light-client rule (reference `VerifyCommitAny
        types/validator_set.go:284-349`): enough of the OLD set (this one,
        >2/3) must have signed the commit produced under `new_set`, matching
        validators by address across the two sets."""
        if len(new_set.validators) != len(commit.precommits):
            raise ValidationError("commit size != new valset size")
        if height != commit.height():
            raise ValidationError("commit height mismatch")
        round_ = commit.round()
        triples: list[tuple[bytes, bytes, bytes]] = []
        old_powers: list[int] = []
        new_powers: list[int] = []
        seen: set[bytes] = set()
        msgs = commit.vote_sign_bytes(chain_id)
        for idx, precommit in enumerate(commit.precommits):
            if precommit is None:
                continue
            # Every non-nil precommit must be well-formed, even ones for other
            # blocks (matches verify_commit; reference validates all votes).
            if precommit.height != height or precommit.round != round_:
                raise ValidationError("commit vote height/round mismatch")
            if precommit.type != VOTE_TYPE_PRECOMMIT:
                raise ValidationError("commit vote is not a precommit")
            if precommit.block_id != block_id:
                continue
            new_val = new_set.validators[idx]
            _, old_val = self.get_by_address(new_val.address)
            if old_val is None or old_val.address in seen:
                continue
            seen.add(old_val.address)
            triples.append((old_val.pub_key.data, msgs[idx], precommit.signature))
            old_powers.append(old_val.voting_power)
            new_powers.append(new_val.voting_power)
        ok_mask = _verify_triples(triples, verifier, consumer=consumer)
        old_tallied = 0
        new_tallied = 0
        for ok, op, np_ in zip(ok_mask, old_powers, new_powers):
            if not ok:
                raise ValidationError("invalid commit signature (old set)")
            old_tallied += op
            new_tallied += np_
        # BOTH quorums must hold: >2/3 of the old (trusted) set AND >2/3 of the
        # new set — otherwise a grown set could be "committed" by a minority of
        # its power (reference validator_set.go:340-346). The old-quorum
        # failure is typed so the light client can trigger bisection.
        if not old_tallied * 3 > self._total * 2:
            raise ErrTooMuchChange(
                f"insufficient old voting power: {old_tallied} of {self._total}"
            )
        if not new_tallied * 3 > new_set.total_voting_power * 2:
            raise ValidationError(
                f"insufficient new voting power: {new_tallied} of {new_set.total_voting_power}"
            )

    def __iter__(self):
        return iter(self.validators)

    def __repr__(self) -> str:
        return f"ValidatorSet(n={len(self.validators)}, power={self._total})"


def _where(entries, ei: int, height: int) -> str:
    """A refused commit's place in its batch, for the message (K > 1)."""
    return f" (batch entry {ei}, height {height})" if len(entries) > 1 else ""


def _verify_triples(
    triples: list[tuple[bytes, bytes, bytes]], verifier, consumer: str = "default"
) -> list[bool]:
    """Verify (pubkey,msg,sig) triples as one batch through the given
    BatchVerifier, defaulting to the process-wide verifier (device-backed
    when an accelerator is present). Tagged verifiers (the coalescing
    stack) route through an async handle joined here, so blocking
    callers — `verify_commit_any` in the certifier walk — still merge
    into coalesced launches."""
    if not triples:
        return []
    if verifier is None:
        from tendermint_tpu.services.verifier import default_verifier

        verifier = default_verifier()
    if getattr(verifier, "accepts_consumer", False) and hasattr(
        verifier, "verify_batch_async"
    ):
        return list(
            verifier.verify_batch_async(triples, consumer=consumer).result()
        )
    return list(verifier.verify_batch(triples))
