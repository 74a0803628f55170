"""Seeded chain generator (a copy of `chip_smoke.build_chain`, generalised).

What is generalised: the validator count, the power rule and the app come
from the deployment's file, the txs of a block and the changes to the
validator set from the traffic mix's file (a rule each, found by its name:
`tx_rules/<kind>.py`, `valset_rules/<kind>.py`), the length from the cell;
signing is spread over `signer.SignerPool`; and a commit's encoding is
built once from per-block templates instead of five times through
`Vote.encode` (the first vote of every block is checked against the
program's own encoder, so the two cannot drift).

Host crypto only, and no store or state of the program: the blocks go
into one file as the bytes the serving peer sends, the deployment's app is
driven directly for the app hashes and the set's changes, and the chain's
record (`Record`) goes into one JSON file beside them, which is all the
benchmark's parent reads. Nothing here starts a JAX backend.
"""

from __future__ import annotations

import bisect
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
# heights at the chain's end that a run never applies (the driver's "chain
# exhausted"): the planted fault's window, the warm shapes' and two more
QUIET_TAIL = 2 * FAULT_WINDOW + 2
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TX_RULES = os.path.join(BENCH, "tx_rules")
VALSET_RULES = os.path.join(BENCH, "valset_rules")


def powers(rule: dict, n: int) -> list[int]:
    """Voting power by rank (rank 1 first) under the deployment's rule."""
    kind = rule["kind"]
    if kind == "linear":
        # the reference's `ValKeys.ToValidators(init, inc)`: init + i * inc
        return [int(rule["init"]) + i * int(rule["inc"]) for i in range(n)]
    raise ValueError(f"unknown power rule {kind!r}")


@functools.cache
def _rule(what: str, directory: str, kind: str, function: str):
    """`<directory>/<kind>.py`'s `function`: a rule is found by its name
    as `run.py` finds a layer metric's reader, so a new mix's rule is a
    new file and no edit here."""
    path = os.path.join(directory, kind + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown {what} {kind!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{os.path.basename(directory)}_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, function)


def tx_rule(kind: str):
    """The tx rule `kind`: `tx_rules/<kind>.py`'s `txs(rule, height)`."""
    return _rule("tx rule", TX_RULES, kind, "txs")


def block_txs(mix: dict, height: int) -> list[bytes]:
    """The kv txs of the block at `height` under the mix's tx rule."""
    rule = mix["txs"]
    return tx_rule(rule["kind"])(rule, height)


def valset_changes(config: dict, mix: dict, n_blocks: int) -> dict[int, list[tuple[int, int]]]:
    """height -> the (key rank, power) changes its block carries as `val:`
    txs, for every height that carries any, under the mix's optional
    `valset` rule: `valset_rules/<kind>.py`'s `changes(rule, height,
    n_vals, n_blocks)`. Key ranks index the seed's keys
    (`signer.private_key`): 0 to n_vals - 1 the genesis set, n_vals + j
    the j-th standby key. The rule reads the mix's `valset` object over
    the deployment's own numbers (its powers are the deployment's) and
    `quiet_tail`. A mix without `valset` changes nothing."""
    spec = mix.get("valset")
    if spec is None:
        return {}
    rule = {k: v for k, v in config.items() if type(v) in (int, float)}
    rule.update(spec, quiet_tail=QUIET_TAIL)
    changes = _rule("validator-set rule", VALSET_RULES, spec["kind"], "changes")
    n_vals = int(config["validators"])
    out = {}
    for height in range(1, n_blocks + 1):
        got = [(int(rank), int(power)) for rank, power in changes(rule, height, n_vals, n_blocks)]
        if got:
            out[height] = got
    return out


def make_app(name: str, db_path: str | None = None):
    """The deployment's `app` by the reference's names (`proxy/client.go`:
    `dummy` is this repo's `kvstore`, `persistent_dummy` its
    `persistent_kvstore`, which changes the validator set by
    `val:<pubkey hex>/<power>` txs and writes its state whole at every
    commit: to a `SQLiteDB` at `db_path`, or to memory without one, as
    the generator drives it)."""
    from tendermint_tpu.abci.apps import KVStoreApp, PersistentKVStoreApp

    if name == "kvstore":
        return KVStoreApp()
    if name == "persistent_kvstore":
        if db_path is None:
            return PersistentKVStoreApp()
        from tendermint_tpu.db.kv import SQLiteDB

        return PersistentKVStoreApp(SQLiteDB(db_path))
    raise ValueError(f"unknown app {name!r}: a deployment's app is kvstore or persistent_kvstore")


@dataclass
class Record:
    """What the generator knows about the chain it made, per height h at
    index h - 1, as hex strings; plus the encoded blocks of the last
    `FAULT_WINDOW` + 1 heights (their commits feed the planted-fault
    check, and the node never reaches them inside a run).
    `validators_hash`, `pubkeys` and `powers` are the genesis set's;
    `valsets` holds one entry a stretch of heights under one set
    (`from_height`, `validators_hash`, `pubkeys`, `powers`, the first the
    genesis set from height 1) and `addresses` every key's address by its
    hex, the reference's input where it orders a set."""

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
    valsets: list[dict] = field(default_factory=list)
    addresses: dict[str, str] = field(default_factory=dict)

    def save(self, path: str) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.__dict__, f)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Record":
        with open(path) as f:
            return cls(**json.load(f))

    def set_at(self, height: int) -> dict:
        """The stretch of `valsets` whose set header `height` carries and
        commit `height` is signed by (a record from before `valsets`: the
        genesis set at every height)."""
        if not self.valsets:
            return {
                "from_height": 1, "validators_hash": self.validators_hash,
                "pubkeys": self.pubkeys, "powers": self.powers,
            }
        at = bisect.bisect_right([s["from_height"] for s in self.valsets], height)
        return self.valsets[max(at, 1) - 1]

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


class _Stretch:
    """What the generator holds of one validator set, built when the set
    changes and only then: the program's `ValidatorSet` over the members
    (key rank -> power), its root and total power, the key rank behind
    each lane, and the front of each lane's vote encoding."""

    def __init__(self, members: dict[int, int], pubs: dict) -> None:
        from tendermint_tpu.codec.binary import encode_bytes, encode_uvarint
        from tendermint_tpu.types import Validator, ValidatorSet

        valset = ValidatorSet(
            [
                Validator(address=pubs[r].address, pub_key=pubs[r], voting_power=w)
                for r, w in members.items()
            ]
        )
        rank_of = {pubs[r].address: r for r in members}
        self.validators = valset.validators
        # key index (= rank) of the validator in lane i
        self.lane_key = [rank_of[v.address] for v in self.validators]
        self.total_power = sum(members.values())
        self.root = valset.hash()
        self.lane_prefix = [
            encode_bytes(v.address) + encode_uvarint(i) for i, v in enumerate(self.validators)
        ]
        self.lanes = encode_uvarint(len(self.validators))

    def entry(self, from_height: int) -> dict:
        return {
            "from_height": from_height, "validators_hash": self.root.hex(),
            "pubkeys": [v.pub_key.data.hex() for v in self.validators],
            "powers": [v.voting_power for v in self.validators],
        }


def build_chain(
    config: dict, mix: dict, seed: int, n_blocks: int, home: str, workers: int
) -> Record:
    """Generate `n_blocks` committed blocks from `seed` into directory
    `home`: `genesis.json`, and `blocks.bin` holding every block's encoding
    (each behind its 4-byte length), which is what the serving peer sends.
    Returns the record. The deployment's app is driven directly for the app
    hashes; the node under test checks every header field itself.

    A block's `val:<pubkey hex>/<power>` txs (the mix's `valset` rule) go
    in front of its kv txs, so its last tx stays the write
    `checks.check_last_write` reads back. What the app's `end_block(h)`
    returns is applied to the set of height h + 1, as `state/state.go:238-265`
    does: header h + 1 carries the new `validators_hash`, commit h + 1 is
    signed by the new set, and block h + 1's `last_commit` is still the old
    set's. The set is built anew from its members (key rank -> power), not
    through the program's `apply_changes`."""
    from tendermint_tpu.codec.binary import encode_bytes, encode_svarint, encode_uvarint
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.merkle.simple import simple_hash_from_byte_slices
    from tendermint_tpu.types import BlockID, Commit, Txs
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

    t0 = time.monotonic()
    CachedCommit, CachedBlock = _cached_types()
    n_vals = int(config["validators"])
    rng = random.Random(seed)
    changes = valset_changes(config, mix, n_blocks)
    ranks = sorted({*range(n_vals), *(r for step in changes.values() for r, _w in step)})
    keys = {r: signer.private_key(seed, r) for r in ranks if r >= n_vals}  # the standby keys sign here
    pubs = {r: PubKey(signer.public_bytes(keys.get(r) or signer.private_key(seed, r))) for r in ranks}
    rank_of_pub = {p.data: r for r, p in pubs.items()}
    members = dict(zip(range(n_vals), powers(config["power"], n_vals)))
    cur = _Stretch(members, pubs)
    genesis = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=GENESIS_TIME,
        validators=[
            GenesisValidator(pub_key=v.pub_key, power=v.voting_power)
            for v in cur.validators
        ],
    )
    os.makedirs(home, exist_ok=True)
    genesis.save_as(os.path.join(home, "genesis.json"))
    app = make_app(config["app"])
    genesis_set = cur.entry(1)
    record = Record(
        chain_id=CHAIN_ID,
        seed=seed,
        n_blocks=n_blocks,
        validators_hash=genesis_set["validators_hash"],
        pubkeys=genesis_set["pubkeys"],
        powers=genesis_set["powers"],
        valsets=[genesis_set],
        addresses={p.data.hex(): p.address.hex() for p in pubs.values()},
    )
    absent_n = int(config["absent_votes"])
    sig_len = encode_uvarint(64)
    last_commit = Commit.empty()
    last_block_id = BlockID.zero()
    app_hash = b""
    tail_from = n_blocks - FAULT_WINDOW
    with signer.SignerPool(seed, list(range(n_vals)), workers) as pool, open(
        os.path.join(home, "blocks.bin"), "wb"
    ) as out:
        for height in range(1, n_blocks + 1):
            txs = [
                b"val:%s/%d" % (pubs[r].data.hex().encode(), w) for r, w in changes.get(height, ())
            ] + block_txs(mix, height)
            stamp = GENESIS_TIME + height * 1_000_000_000
            block = CachedBlock.make_block(
                height=height,
                chain_id=CHAIN_ID,
                txs=Txs(txs),
                last_commit=last_commit,
                last_block_id=last_block_id,
                time=stamp,
                validators_hash=cur.root,
                app_hash=app_hash,
            )
            parts = block.make_part_set()
            block_id = BlockID(block.hash(), parts.header)
            # never enough absent power to cost the quorum: drawn again
            # if the draw would
            while True:
                absent = set(rng.sample(range(len(cur.validators)), absent_n))
                gone = sum(cur.validators[i].voting_power for i in absent)
                if 3 * (cur.total_power - gone) > 2 * cur.total_power:
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
            msg = proto.sign_bytes(CHAIN_ID)
            sigs = pool.sign(msg)
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
            for i, v in enumerate(cur.validators):
                if i in absent:
                    precommits.append(None)
                    encs.append(b"")
                    continue
                rank = cur.lane_key[i]
                sig = sigs[rank] if rank in sigs else keys[rank].sign(msg)
                precommits.append(
                    Vote(v.address, i, height, 0, stamp, VOTE_TYPE_PRECOMMIT, block_id, sig)
                )
                encs.append(cur.lane_prefix[i] + mid + sig)
            first = next(v for v in precommits if v is not None)
            if first.encode() != encs[first.validator_index]:
                raise AssertionError("the generator's vote encoding drifted from Vote.encode")
            commit = CachedCommit(block_id=block_id, precommits=precommits)
            w = [block_id.encode(), cur.lanes]
            w.extend(encode_bytes(e) for e in encs)
            commit._enc = b"".join(w)
            commit._hash = simple_hash_from_byte_slices(encs)
            for tx in txs:
                app.deliver_tx(tx)
            diffs = app.end_block(height)
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
            if len(diffs) != len(changes.get(height, ())):
                raise ValueError(
                    f"block {height} carries {len(changes.get(height, ()))} val: txs and the app "
                    f"{config['app']!r} returned {len(diffs)} changes: a mix that changes the "
                    "validator set needs a deployment whose app does"
                )
            if diffs:
                # replace on an equal key, drop on power 0, insert
                for d in diffs:
                    rank = rank_of_pub[bytes(d.pub_key)]
                    if d.power:
                        members[rank] = d.power
                    elif members.pop(rank, None) is None:
                        raise ValueError(f"block {height} removes key rank {rank}, which is in no set")
                cur = _Stretch(members, pubs)
                record.valsets.append(cur.entry(height + 1))
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
