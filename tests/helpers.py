"""Shared test fixtures: deterministic validators, votes, commits.

Mirrors the role of the reference's `consensus/common_test.go` +
`types/vote_set_test.go` fixture helpers.
"""

from __future__ import annotations

import time

from tendermint_tpu.crypto import PrivKey
from tendermint_tpu.types import (
    VOTE_TYPE_PRECOMMIT,
    BlockID,
    Commit,
    PartSetHeader,
    PrivValidator,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
)

CHAIN_ID = "test-chain"


def _thread_clock_step() -> float:
    """The smallest rise of a thread's CPU clock seen while spinning
    20 ms (`get_clock_info("thread_time").resolution` says 1 ns where the
    kernel keeps the clock in scheduler ticks of 10 ms, as a TPU host
    does); a clock that did not move in that time steps by more."""
    seen, end = set(), time.perf_counter() + 0.02
    while time.perf_counter() < end:
        seen.add(time.thread_time_ns())
    readings = sorted(seen)
    if len(readings) < 2:
        return 0.02
    return min(b - a for a, b in zip(readings, readings[1:])) * 1e-9


THREAD_CLOCK_STEP_S = _thread_clock_step()
# a stage's CPU is sure to have risen only where its clock steps by less
# than the stage is long: assert `> 0` under this, `>= 0` otherwise
THREAD_CLOCK_IS_FINE = THREAD_CLOCK_STEP_S < 1e-4


def cpu_slack(stretches: int = 1) -> float:
    """Seconds by which the CPU of `stretches` `Stage`s may read over
    their wall: a step of the thread's clock each, and the reading a
    stage may share with the boundary before it (`CPU_SHARE_NS`)."""
    from tendermint_tpu.telemetry.tracer import CPU_SHARE_NS

    return stretches * (THREAD_CLOCK_STEP_S + CPU_SHARE_NS * 1e-9)


def det_priv_keys(n: int) -> list[PrivKey]:
    return [PrivKey(i.to_bytes(32, "little")) for i in range(1, n + 1)]


def make_validators(n: int, power: int = 10) -> tuple[ValidatorSet, list[PrivValidator]]:
    """N deterministic validators with equal power; privs index-aligned with
    the sorted validator set."""
    privs = [PrivValidator(k) for k in det_priv_keys(n)]
    vals = [
        Validator(address=p.address, pub_key=p.pub_key, voting_power=power) for p in privs
    ]
    vs = ValidatorSet(vals)
    privs_by_addr = {p.address: p for p in privs}
    ordered = [privs_by_addr[v.address] for v in vs.validators]
    return vs, ordered


def pad_varint(wire: bytes, span: tuple[int, int], pad: int) -> bytes:
    """`wire` with the varint at `span` (start, end) made `pad` bytes
    longer and worth the same: the continue bit set on its last byte,
    then `0x80` fillers and a closing `0x00`. What a decoder accepts and
    no encoder writes."""
    _start, end = span
    filler = bytes([wire[end - 1] | 0x80]) + b"\x80" * (pad - 1) + b"\x00"
    return wire[: end - 1] + filler + wire[end:]


def make_block_id(seed: bytes = b"blk") -> BlockID:
    import hashlib

    h = hashlib.sha256(seed).digest()
    return BlockID(hash=h, parts_header=PartSetHeader(total=1, hash=h[:20]))


def signed_vote(
    priv: PrivValidator,
    index: int,
    height: int,
    round_: int,
    type_: int,
    block_id: BlockID,
    chain_id: str = CHAIN_ID,
    timestamp: int | None = None,
) -> Vote:
    vote = Vote(
        validator_address=priv.address,
        validator_index=index,
        height=height,
        round=round_,
        timestamp=timestamp if timestamp is not None else time.time_ns(),
        type=type_,
        block_id=block_id,
    )
    return priv.sign_vote(chain_id, vote)


def byzantine_signed_vote(
    priv: PrivValidator,
    index: int,
    height: int,
    round_: int,
    type_: int,
    block_id: BlockID,
    chain_id: str = CHAIN_ID,
    timestamp: int = 1000,
) -> Vote:
    """Sign bypassing the double-sign guard (Byzantine test behavior —
    the reference's ByzantinePrivValidator role)."""
    vote = Vote(
        validator_address=priv.address,
        validator_index=index,
        height=height,
        round=round_,
        timestamp=timestamp,
        type=type_,
        block_id=block_id,
    )
    sig = priv._signer.sign(vote.sign_bytes(chain_id))
    return vote.with_signature(sig)


def make_commit(
    val_set: ValidatorSet,
    privs: list[PrivValidator],
    height: int,
    round_: int,
    block_id: BlockID,
    chain_id: str = CHAIN_ID,
    n_sign: int | None = None,
) -> Commit:
    """Build a commit by running the real VoteSet quorum machinery."""
    vote_set = VoteSet(chain_id, height, round_, VOTE_TYPE_PRECOMMIT, val_set)
    n = n_sign if n_sign is not None else len(privs)
    for i in range(n):
        vote_set.add_vote(
            signed_vote(privs[i], i, height, round_, VOTE_TYPE_PRECOMMIT, block_id, chain_id)
        )
    return vote_set.make_commit()


def make_genesis(n_vals: int = 4, power: int = 10, chain_id: str = CHAIN_ID):
    """GenesisDoc + index-aligned priv validators."""
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    vs, privs = make_validators(n_vals, power)
    gen = GenesisDoc(
        chain_id=chain_id,
        genesis_time=1_700_000_000_000_000_000,
        validators=[
            GenesisValidator(pub_key=v.pub_key, power=v.voting_power) for v in vs.validators
        ],
    )
    return gen, privs


class ChainSim:
    """Drive a real State + app through heights with real commits.

    The make-block -> sign-precommits -> apply_block loop every
    storage/sync/consensus test needs (role of the reference's
    `state/execution_test.go` + `consensus/common_test.go` chain makers).
    """

    def __init__(
        self, n_vals: int = 4, app=None, db=None, chain_id: str = CHAIN_ID, hasher=None
    ):
        from tendermint_tpu.abci.apps import KVStoreApp
        from tendermint_tpu.abci.client import local_client_creator
        from tendermint_tpu.db.kv import MemDB
        from tendermint_tpu.state import make_genesis_state

        self.chain_id = chain_id
        self.hasher = hasher
        self.db = db if db is not None else MemDB()
        self.genesis, self.privs = make_genesis(n_vals, chain_id=chain_id)
        self.state = make_genesis_state(self.db, self.genesis)
        self.state.save()  # node startup persists genesis state (validators@1)
        self.app = app if app is not None else KVStoreApp()
        self.conns = local_client_creator(self.app)()
        self.blocks = []
        self.commits = []

    def _commit_for(self, block, part_set):
        from tendermint_tpu.types import BlockID

        block_id = BlockID(block.hash(), part_set.header)
        return make_commit(
            self.state.validators,
            self._privs_in_valset_order(),
            block.header.height,
            0,
            block_id,
            self.chain_id,
        )

    def _privs_in_valset_order(self):
        by_addr = {p.address: p for p in self.privs}
        return [by_addr[v.address] for v in self.state.validators.validators]

    def make_next_block(self, txs=None, evidence=None):
        from tendermint_tpu.types import Commit, Txs
        from tendermint_tpu.types.block import Block

        height = self.state.last_block_height + 1
        last_commit = self.commits[-1] if self.commits else Commit.empty()
        block = Block.make_block(
            height=height,
            chain_id=self.chain_id,
            txs=Txs(txs or []),
            last_commit=last_commit,
            last_block_id=self.state.last_block_id,
            time=self.genesis.genesis_time + height * 1_000_000_000,
            validators_hash=self.state.validators.hash(),
            app_hash=self.state.app_hash,
            hasher=self.hasher,
            evidence=evidence,
        )
        return block, block.make_part_set(hasher=self.hasher)

    def advance(self, txs=None, **apply_kwargs):
        """Build, commit-sign, and apply one block; returns the block."""
        from tendermint_tpu.state import apply_block

        block, part_set = self.make_next_block(txs)
        commit = self._commit_for(block, part_set)
        apply_kwargs.setdefault("hasher", self.hasher)
        apply_block(self.state, block, part_set.header, self.conns.consensus, **apply_kwargs)
        self.blocks.append(block)
        self.commits.append(commit)
        return block
