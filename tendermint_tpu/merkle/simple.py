"""SimpleMerkle tree with inclusion proofs (host reference implementation).

DELIBERATE DEVIATIONS from the reference's SimpleTree
(`docs/specification/merkle.rst:52-90`), both chosen TPU-first:

* **Split rule.** The reference splits leaves in half ("both sides of
  the tree the same size, but the left side may be one greater" — its
  6-leaf diagram splits 3/3). We split at the **largest power of two
  strictly less than n** (the RFC 6962 / Certificate Transparency
  rule). The two rules produce different shapes from 5 leaves up
  (reference 5 -> 3/2; ours 5 -> 4/2). Ours is exactly equivalent to
  bottom-up adjacent pairing with promotion of an unpaired trailing
  node, which is what the device kernel vectorizes as log2(N) batched
  levels; the reference's ceil-split tree has no such level-parallel
  form.
* **Domain separation.** The reference hashes raw concatenation of
  wire-encoded children; we prefix leaf = H(0x00||data) and inner =
  H(0x01||L||R) (RFC 6962 style), closing leaf/inner second-preimage
  attacks.

Roots are therefore NOT bit-compatible with reference roots (the
domain separation alone guarantees that); within this framework, host
(`merkle.simple`) and device (`ops/merkle_kernel.py`) trees implement
the identical rule and are bit-equal — asserted by tests and by
`chip_smoke.py`'s device-vs-host root check.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from tendermint_tpu.crypto.hashing import DEFAULT_ALGO, tmhash

LEAF_PREFIX = b"\x00"
INNER_PREFIX = b"\x01"


def leaf_hash(data: bytes, algo: str = DEFAULT_ALGO) -> bytes:
    return tmhash(LEAF_PREFIX + data, algo)


def inner_hash(left: bytes, right: bytes, algo: str = DEFAULT_ALGO) -> bytes:
    return tmhash(INNER_PREFIX + left + right, algo)


def _split_point(n: int) -> int:
    """Largest power of two strictly less than n (RFC 6962 split rule —
    a deliberate deviation from the reference's ceil(n/2) split; see the
    module docstring)."""
    if n < 2:
        raise ValueError("split requires n >= 2")
    k = 1
    while k * 2 < n:
        k *= 2
    return k


def simple_hash_from_hashes(hashes: list[bytes], algo: str = DEFAULT_ALGO) -> bytes:
    """Root from precomputed *leaf* hashes (already leaf-prefixed)."""
    n = len(hashes)
    if n == 0:
        return b""
    if n == 1:
        return hashes[0]
    k = _split_point(n)
    left = simple_hash_from_hashes(hashes[:k], algo)
    right = simple_hash_from_hashes(hashes[k:], algo)
    return inner_hash(left, right, algo)


def simple_hash_from_byte_slices(items: list[bytes], algo: str = DEFAULT_ALGO) -> bytes:
    """Root over raw byte slices (each hashed as a domain-separated leaf)."""
    return simple_hash_from_hashes([leaf_hash(x, algo) for x in items], algo)


@dataclass
class SimpleProof:
    """Inclusion proof: aunt hashes bottom-up (reference: merkle SimpleProof)."""

    index: int
    total: int
    leaf: bytes  # leaf hash (prefixed)
    aunts: list[bytes] = field(default_factory=list)

    def root(self, algo: str = DEFAULT_ALGO) -> bytes:
        return _root_from_aunts(self.index, self.total, self.leaf, self.aunts, algo)

    def encode(self) -> bytes:
        from tendermint_tpu.codec import Writer

        w = Writer().uvarint(self.index).uvarint(self.total).bytes(self.leaf)
        w.uvarint(len(self.aunts))
        for a in self.aunts:
            w.bytes(a)
        return w.build()

    @classmethod
    def decode(cls, data: bytes) -> "SimpleProof":
        from tendermint_tpu.codec import Reader

        r = Reader(data)
        index, total, leaf = r.uvarint(), r.uvarint(), r.bytes()
        aunts = [r.bytes() for _ in range(r.uvarint())]
        return cls(index=index, total=total, leaf=leaf, aunts=aunts)


def _root_from_aunts(
    index: int, total: int, leaf: bytes, aunts: list[bytes], algo: str
) -> bytes:
    if total == 0 or not (0 <= index < total):
        raise ValueError("invalid proof shape")
    if total == 1:
        if aunts:
            raise ValueError("unexpected aunts for single leaf")
        return leaf
    k = _split_point(total)
    if not aunts:
        raise ValueError("missing aunts")
    if index < k:
        left = _root_from_aunts(index, k, leaf, aunts[:-1], algo)
        return inner_hash(left, aunts[-1], algo)
    right = _root_from_aunts(index - k, total - k, leaf, aunts[:-1], algo)
    return inner_hash(aunts[-1], right, algo)


def _proofs(hashes: list[bytes], algo: str) -> tuple[bytes, list[list[bytes]]]:
    n = len(hashes)
    if n == 1:
        return hashes[0], [[]]
    k = _split_point(n)
    lroot, lproofs = _proofs(hashes[:k], algo)
    rroot, rproofs = _proofs(hashes[k:], algo)
    root = inner_hash(lroot, rroot, algo)
    return root, [p + [rroot] for p in lproofs] + [p + [lroot] for p in rproofs]


def simple_proofs_from_byte_slices(
    items: list[bytes], algo: str = DEFAULT_ALGO
) -> tuple[bytes, list[SimpleProof]]:
    """Root + per-item inclusion proofs (reference: SimpleProofsFromHashers)."""
    if not items:
        return b"", []
    leaves = [leaf_hash(x, algo) for x in items]
    root, aunt_lists = _proofs(leaves, algo)
    total = len(items)
    proofs = [
        SimpleProof(index=i, total=total, leaf=leaves[i], aunts=aunts)
        for i, aunts in enumerate(aunt_lists)
    ]
    return root, proofs


def simple_hash_from_map(kvs: dict[str, bytes], algo: str = DEFAULT_ALGO) -> bytes:
    """Root over a string->bytes map, keys sorted (reference: SimpleHashFromMap,
    used for the block header hash at `types/block.go:173-188`)."""
    from tendermint_tpu.codec import encode_bytes, encode_string

    items = [
        encode_string(k) + encode_bytes(v) for k, v in sorted(kvs.items())
    ]
    return simple_hash_from_byte_slices(items, algo)


def verify_proof(
    root: bytes, item: bytes, proof: SimpleProof, algo: str = DEFAULT_ALGO
) -> bool:
    """Check an item's inclusion proof against a known root
    (reference: `types/part_set.go:188-214` AddPart proof check)."""
    if proof.leaf != leaf_hash(item, algo):
        return False
    try:
        return proof.root(algo) == root
    except ValueError:
        return False
