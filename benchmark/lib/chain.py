"""Seeded chain generator (a copy of `chip_smoke.build_chain`, generalised).

What is generalised: the validator count and the power rule come from the
deployment's file, the txs of a block from the traffic mix's file, the
length from the cell; signing is spread over `signer.SignerPool`; and a
commit's encoding is built once from per-block templates instead of five
times through `Vote.encode` (the first vote of every block is checked
against the program's own encoder, so the two cannot drift).

Host crypto only, and no store or state of the program: the blocks go
into one file as the bytes the serving peer sends, the kvstore app is
driven directly for the app hashes, and the chain's record (`Record`)
goes into one JSON file beside them, which is all the benchmark's parent
reads. Nothing here starts a JAX backend.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import os
import random
import time
from dataclasses import dataclass, field

from . import signer

CHAIN_ID = "benchmark"
GENESIS_TIME = 1_700_000_000_000_000_000
FAULT_WINDOW = 16  # commits in the window the planted fault goes into
TX_RULES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tx_rules")


def powers(rule: dict, n: int) -> list[int]:
    """Voting power by rank (rank 1 first) under the deployment's rule."""
    kind = rule["kind"]
    if kind == "linear":
        # the reference's `ValKeys.ToValidators(init, inc)`: init + i * inc
        return [int(rule["init"]) + i * int(rule["inc"]) for i in range(n)]
    raise ValueError(f"unknown power rule {kind!r}")


@functools.cache
def tx_rule(kind: str):
    """The tx rule `kind`: `tx_rules/<kind>.py`, found by its name as
    `run.py` finds a layer metric's reader, so a new mix's rule is a new
    file and no edit here."""
    path = os.path.join(TX_RULES, kind + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown tx rule {kind!r}: no file {path}")
    spec = importlib.util.spec_from_file_location("benchmark_tx_rule_" + kind, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.txs


def block_txs(mix: dict, height: int) -> list[bytes]:
    """The txs of the block at `height` under the mix's tx rule."""
    rule = mix["txs"]
    return tx_rule(rule["kind"])(rule, height)


@dataclass
class Record:
    """What the generator knows about the chain it made, per height h at
    index h - 1, as hex strings; plus the encoded blocks of the last
    `FAULT_WINDOW` + 1 heights (their commits feed the planted-fault
    check, and the node never reaches them inside a run)."""

    chain_id: str
    seed: int
    n_blocks: int
    validators_hash: str
    pubkeys: list[str]  # validator order (sorted by address)
    powers: list[int]  # validator order
    block_hash: list[str] = field(default_factory=list)
    parts_total: list[int] = field(default_factory=list)
    parts_hash: list[str] = field(default_factory=list)
    data_hash: list[str] = field(default_factory=list)
    app_hash: list[str] = field(default_factory=list)  # after applying h
    last_write: list[list[str]] = field(default_factory=list)  # [key, value] hex of h's last tx
    tail_blocks: list[str] = field(default_factory=list)
    build_seconds: float = 0.0

    def save(self, path: str) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.__dict__, f)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Record":
        with open(path) as f:
            return cls(**json.load(f))

    def tail_entries(self) -> list:
        """(block_id, height, commit) of the last FAULT_WINDOW heights that
        have a commit in a later block: what
        `ValidatorSet.verify_commit_batched` takes."""
        from tendermint_tpu.types import BlockID
        from tendermint_tpu.types.block import Block
        from tendermint_tpu.types.part_set import PartSetHeader

        blocks = [Block.decode(bytes.fromhex(b)) for b in self.tail_blocks]
        out = []
        for blk, nxt in zip(blocks, blocks[1:]):
            h = blk.header.height
            bid = BlockID(
                bytes.fromhex(self.block_hash[h - 1]),
                PartSetHeader(self.parts_total[h - 1], bytes.fromhex(self.parts_hash[h - 1])),
            )
            out.append((bid, h, nxt.last_commit))
        return out


def _cached_types():
    """Subclasses of the program's Commit and Block whose `encode()` and
    `hash()` run once: the generator never mutates them after building."""
    from tendermint_tpu.types import Commit
    from tendermint_tpu.types.block import Block, Data

    class CachedData(Data):
        _hash = None

        def hash(self, hasher=None) -> bytes:
            if self._hash is None:
                self._hash = super().hash(hasher)
            return self._hash

    class CachedCommit(Commit):
        _enc = None
        _hash = None

        def encode(self) -> bytes:
            if self._enc is None:
                self._enc = super().encode()
            return self._enc

        def hash(self) -> bytes:
            if self._hash is None:
                self._hash = super().hash()
            return self._hash

    class CachedBlock(Block):
        _enc = None

        def fill_header(self, hasher=None) -> None:
            self.data = CachedData(txs=self.data.txs)
            super().fill_header(hasher)

        def encode(self) -> bytes:
            if self._enc is None:
                self._enc = super().encode()
            return self._enc

    return CachedCommit, CachedBlock


def build_chain(
    config: dict, mix: dict, seed: int, n_blocks: int, home: str, workers: int
) -> Record:
    """Generate `n_blocks` committed blocks from `seed` into directory
    `home`: `genesis.json`, and `blocks.bin` holding every block's encoding
    (each behind its 4-byte length), which is what the serving peer sends.
    Returns the record. The kvstore app is driven directly for the app
    hashes; the node under test checks every header field itself."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.codec.binary import encode_bytes, encode_svarint, encode_uvarint
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.merkle.simple import simple_hash_from_byte_slices
    from tendermint_tpu.types import BlockID, Commit, Txs, Validator, ValidatorSet
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

    t0 = time.monotonic()
    CachedCommit, CachedBlock = _cached_types()
    n_vals = int(config["validators"])
    rng = random.Random(seed)
    pubs = [PubKey(signer.public_bytes(signer.private_key(seed, i))) for i in range(n_vals)]
    power_by_rank = powers(config["power"], n_vals)
    valset = ValidatorSet(
        [
            Validator(address=p.address, pub_key=p, voting_power=w)
            for p, w in zip(pubs, power_by_rank)
        ]
    )
    rank_of = {p.address: i for i, p in enumerate(pubs)}
    # key index (= rank) of the validator in lane i
    lane_key = [rank_of[v.address] for v in valset.validators]
    total_power = sum(power_by_rank)
    valset_root = valset.hash()
    genesis = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=GENESIS_TIME,
        validators=[
            GenesisValidator(pub_key=v.pub_key, power=v.voting_power)
            for v in valset.validators
        ],
    )
    os.makedirs(home, exist_ok=True)
    genesis.save_as(os.path.join(home, "genesis.json"))
    app = KVStoreApp()
    record = Record(
        chain_id=CHAIN_ID,
        seed=seed,
        n_blocks=n_blocks,
        validators_hash=valset_root.hex(),
        pubkeys=[v.pub_key.data.hex() for v in valset.validators],
        powers=[v.voting_power for v in valset.validators],
    )
    absent_n = int(config["absent_votes"])
    lane_prefix = [
        encode_bytes(v.address) + encode_uvarint(i)
        for i, v in enumerate(valset.validators)
    ]
    sig_len = encode_uvarint(64)
    last_commit = Commit.empty()
    last_block_id = BlockID.zero()
    app_hash = b""
    tail_from = n_blocks - FAULT_WINDOW
    with signer.SignerPool(seed, list(range(n_vals)), workers) as pool, open(
        os.path.join(home, "blocks.bin"), "wb"
    ) as out:
        for height in range(1, n_blocks + 1):
            txs = block_txs(mix, height)
            stamp = GENESIS_TIME + height * 1_000_000_000
            block = CachedBlock.make_block(
                height=height,
                chain_id=CHAIN_ID,
                txs=Txs(txs),
                last_commit=last_commit,
                last_block_id=last_block_id,
                time=stamp,
                validators_hash=valset_root,
                app_hash=app_hash,
            )
            parts = block.make_part_set()
            block_id = BlockID(block.hash(), parts.header)
            # never enough absent power to cost the quorum: drawn again
            # if the draw would
            while True:
                absent = set(rng.sample(range(n_vals), absent_n))
                gone = sum(valset.validators[i].voting_power for i in absent)
                if 3 * (total_power - gone) > 2 * total_power:
                    break
            proto = Vote(
                validator_address=b"",
                validator_index=0,
                height=height,
                round=0,
                timestamp=stamp,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            sigs = pool.sign(proto.sign_bytes(CHAIN_ID))
            mid = (
                encode_uvarint(height)
                + encode_uvarint(0)
                + encode_svarint(stamp)
                + encode_uvarint(VOTE_TYPE_PRECOMMIT)
                + block_id.encode()
                + sig_len
            )
            precommits: list = []
            encs: list[bytes] = []
            for i, v in enumerate(valset.validators):
                if i in absent:
                    precommits.append(None)
                    encs.append(b"")
                    continue
                sig = sigs[lane_key[i]]
                precommits.append(
                    Vote(v.address, i, height, 0, stamp, VOTE_TYPE_PRECOMMIT, block_id, sig)
                )
                encs.append(lane_prefix[i] + mid + sig)
            first = next(v for v in precommits if v is not None)
            if first.encode() != encs[first.validator_index]:
                raise AssertionError("the generator's vote encoding drifted from Vote.encode")
            commit = CachedCommit(block_id=block_id, precommits=precommits)
            w = [block_id.encode(), encode_uvarint(n_vals)]
            w.extend(encode_bytes(e) for e in encs)
            commit._enc = b"".join(w)
            commit._hash = simple_hash_from_byte_slices(encs)
            for tx in txs:
                app.deliver_tx(tx)
            app.end_block(height)
            app_hash = app.commit().data
            encoded = block.encode()
            out.write(len(encoded).to_bytes(4, "big"))
            out.write(encoded)
            record.block_hash.append(block_id.hash.hex())
            record.parts_total.append(parts.header.total)
            record.parts_hash.append(parts.header.hash.hex())
            record.data_hash.append(block.header.data_hash.hex())
            record.app_hash.append(app_hash.hex())
            key, _, value = txs[-1].partition(b"=")
            record.last_write.append([key.hex(), value.hex()])
            if height >= tail_from:
                record.tail_blocks.append(encoded.hex())
            last_commit = commit
            last_block_id = block_id
    record.build_seconds = time.monotonic() - t0
    return record


def read_blocks(home: str) -> list[bytes]:
    """Every block's encoding, in height order, from `blocks.bin`."""
    with open(os.path.join(home, "blocks.bin"), "rb") as f:
        blob = f.read()
    out, at = [], 0
    while at < len(blob):
        n = int.from_bytes(blob[at : at + 4], "big")
        out.append(blob[at + 4 : at + 4 + n])
        at += 4 + n
    return out


def chain_key(config_name: str, mix_name: str, seed: int, n_blocks: int) -> str:
    return f"{config_name}.{mix_name}.{seed}.{n_blocks}"


def tamper(commit, seed: int):
    """A copy of `commit` with one seeded bit of one present signature's R
    (the first 32 bytes: the curve check has to catch it, not the host's
    S < L precheck) flipped. Returns (commit, validator index)."""
    from tendermint_tpu.types import Commit

    rng = random.Random(seed ^ 0x5EED)
    present = [i for i, v in enumerate(commit.precommits) if v is not None]
    idx = rng.choice(present)
    sig = bytearray(commit.precommits[idx].signature)
    sig[rng.randrange(31)] ^= 1 << rng.randrange(8)
    votes = list(commit.precommits)
    votes[idx] = votes[idx].with_signature(bytes(sig))
    return Commit(block_id=commit.block_id, precommits=votes), idx


def digest(record: Record) -> str:
    """A short fingerprint of a chain, for logs."""
    return hashlib.sha256("".join(record.block_hash).encode()).hexdigest()[:16]
