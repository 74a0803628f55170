"""The plain reference: the same semantics with `hashlib` and the host
ed25519 library, and nothing of the program.

What a fast-synced node must hold for every applied height, checked from
what it serves over RPC against what the benchmark itself knows from the
seed (validator keys and powers: `signer.private_key`, `chain.powers`):

* the block's `data_hash` is the SimpleMerkle root of its txs
  (leaf = SHA-256(0x00 || tx), inner = SHA-256(0x01 || left || right), split
  at the largest power of two below n);
* the commit that sealed it carries valid ed25519 signatures, over the
  canonical sign-bytes of a precommit for that block id, from validators
  holding more than 2/3 of the voting power of the set of THAT height;
* the header's `validators_hash` is the SimpleMerkle root of that set,
  which the plain set arithmetic below derives from the genesis set and
  the `(key, power)` changes the chain's blocks carry: replace on an equal
  key, drop on power 0, insert, order by address. A key's address is an
  input (the generator's record has it): how the program derives one is
  not what is compared. So `ValidatorSet.apply_changes` and
  `ValidatorSet.hash` are held to something they did not produce.
"""

from __future__ import annotations

import hashlib
import json

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey


def merkle_root(items: list[bytes]) -> bytes:
    if not items:
        return b""
    level = [hashlib.sha256(b"\x00" + x).digest() for x in items]

    def root(hs: list[bytes]) -> bytes:
        if len(hs) == 1:
            return hs[0]
        k = 1
        while k * 2 < len(hs):
            k *= 2
        return hashlib.sha256(b"\x01" + root(hs[:k]) + root(hs[k:])).digest()

    return root(level)


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def validators_hash(validators: list[tuple[bytes, bytes, int]]) -> bytes:
    """The root over a set's (address, pubkey, power) rows in address
    order. A validator's leaf is its address and its key, each behind its
    length as a uvarint, then its power as a uvarint."""
    return merkle_root(
        [
            uvarint(len(address)) + address + uvarint(len(pubkey)) + pubkey + uvarint(power)
            for address, pubkey, power in validators
        ]
    )


def validator_sets(
    genesis: list[tuple[bytes, int]], changes: dict[int, list[tuple[bytes, int]]],
    address_of: dict[bytes, bytes],
) -> list[dict]:
    """The chain's validator sets, one entry a stretch of heights, oldest
    first: `from_height`, `pubkeys` and `powers` in address order, and the
    `validators_hash` every header of the stretch carries. `genesis` is the
    (pubkey, power) list of height 1; `changes[h]` are the (pubkey, power)
    changes block h carries, which take effect at height h + 1."""
    members = dict(genesis)

    def entry(from_height: int) -> dict:
        rows = sorted((address_of[k], k, w) for k, w in members.items())
        return {
            "from_height": from_height, "pubkeys": [k for _a, k, _w in rows],
            "powers": [w for _a, _k, w in rows], "validators_hash": validators_hash(rows),
        }

    sets = [entry(1)]
    for height in sorted(changes):
        for key, power in changes[height]:
            if power < 0:
                raise ValueError(f"block {height}: negative power")
            if power:
                members[key] = power
            elif members.pop(key, None) is None:
                raise ValueError(f"block {height} removes a key that is in no set")
        sets.append(entry(height + 1))
    return sets


def set_at(sets: list[dict], height: int) -> dict:
    """The set that header `height` names and commit `height` is signed by."""
    found = sets[0]
    for s in sets:
        if s["from_height"] > height:
            break
        found = s
    return found


def sign_bytes(chain_id: str, vote: dict) -> bytes:
    """Canonical JSON (sorted keys, compact, bytes as upper-case hex) of a
    vote as the `/commit` route serves it."""
    doc = {
        "chain_id": chain_id,
        "vote": {
            "block_id": {
                "hash": vote["block_id"]["hash"].upper(),
                "parts": {
                    "hash": vote["block_id"]["parts"]["hash"].upper(),
                    "total": vote["block_id"]["parts"]["total"],
                },
            },
            "height": vote["height"],
            "round": vote["round"],
            "timestamp": vote["timestamp"],
            "type": vote["type"],
        },
    }
    return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode("ascii")


def check_block(served: dict) -> list[str]:
    """`served` is the `block` object of `/block`."""
    txs = [bytes.fromhex(t) for t in served["txs"]]
    if merkle_root(txs).hex() != served["header"]["data_hash"]:
        return [f"block {served['header']['height']}: data_hash is not the root of its txs"]
    return []


def check_commit(
    chain_id: str, height: int, block_hash: str, commit: dict,
    pubkeys: list[bytes], powers: list[int],
) -> list[str]:
    """`commit` is the `commit` object of `/commit?height=`; `pubkeys` and
    `powers` in validator order. More than 2/3 of the power must have
    signed a precommit for `block_hash` at `height`."""
    bad: list[str] = []
    votes = commit["precommits"]
    if len(votes) != len(pubkeys):
        return [f"commit {height}: {len(votes)} lanes for {len(pubkeys)} validators"]
    tallied = 0
    for i, vote in enumerate(votes):
        if vote is None:
            continue
        if vote["validator_index"] != i or vote["height"] != height:
            bad.append(f"commit {height}: lane {i} holds another vote")
            continue
        try:
            Ed25519PublicKey.from_public_bytes(pubkeys[i]).verify(
                bytes.fromhex(vote["signature"]), sign_bytes(chain_id, vote)
            )
        except InvalidSignature:
            bad.append(f"commit {height}: signature of validator {i} is not valid")
            continue
        if vote["block_id"]["hash"] == block_hash:
            tallied += powers[i]
    if not 3 * tallied > 2 * sum(powers):
        bad.append(f"commit {height}: {tallied} of {sum(powers)} power signed the block")
    return bad
