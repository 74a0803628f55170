"""Device Merkle tree reduction (log-depth, batched SHA-256 inner nodes).

Computes the same root as `tendermint_tpu.merkle.simple` (RFC 6962
largest-power-of-two split rule — the documented deviation from the
reference's ceil-split; see `merkle/simple.py` module docstring) via an
equivalent level-by-level pairing: at each level adjacent
nodes pair into an inner hash and an unpaired trailing node is promoted
unchanged. Each level is one batched 2-block SHA-256 over all pairs — the
whole tree is log2(N) kernel steps (reference hot spots: `types/block.go:177`,
`types/tx.go:33-46`, `types/part_set.go:95-122`).

Inner-node messages (0x01 || left32 || right32 = 65 bytes) are assembled
directly in u32 registers (byte-shift composition), so no host round-trip
happens between levels.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.merkle.simple import INNER_PREFIX, LEAF_PREFIX
from tendermint_tpu.ops.sha256_kernel import sha256_fixed2_from_words

_B8 = np.uint32(8)
_B24 = np.uint32(24)
# INNER_PREFIX byte placed in the top byte of the first message word.
_INNER_PREFIX_WORD = np.uint32(INNER_PREFIX[0] << 24)


def _inner_node_words(L, R):
    """Build the two 16-word SHA-256 blocks for H(INNER_PREFIX || L || R).

    L, R: (B, 8) u32 big-endian digest words. The 1-byte domain prefix shifts
    every digest byte by one, so each message word mixes two source words.
    """
    w0 = []
    w0.append(jnp.uint32(_INNER_PREFIX_WORD) | (L[:, 0] >> _B8))
    for i in range(1, 8):
        w0.append((L[:, i - 1] << _B24) | (L[:, i] >> _B8))
    w0.append((L[:, 7] << _B24) | (R[:, 0] >> _B8))
    for i in range(1, 8):
        w0.append((R[:, i - 1] << _B24) | (R[:, i] >> _B8))
    block0 = jnp.stack(w0, axis=1)

    B = L.shape[0]
    zero = jnp.zeros((B,), dtype=jnp.uint32)
    w1 = [(R[:, 7] << _B24) | jnp.uint32(0x00800000)]
    w1 += [zero] * 14
    w1.append(jnp.full((B,), np.uint32(65 * 8), dtype=jnp.uint32))
    block1 = jnp.stack(w1, axis=1)
    return block0, block1


def inner_hash_device(L, R):
    """(B,8),(B,8) -> (B,8): batched domain-separated inner-node hash."""
    b0, b1 = _inner_node_words(L, R)
    return sha256_fixed2_from_words(b0, b1)


# numpy scalars, NOT jnp: module-level jnp calls initialize the XLA
# backend at import, which breaks jax.distributed.initialize for every
# later importer (multi-host workers must init before any backend use)
_B8_LE = np.uint32(8)
_B24_LE = np.uint32(24)


def _inner_node_words_ripemd(L, R):
    """(B,5),(B,5) u32 LE digests -> (B,16) u32 LE single-block message
    for H(0x01 || L20 || R20) = 41 bytes (fits one RIPEMD-160 block:
    0x80 at byte 41, bit length 328 LE at words 14-15)."""
    b = L.shape[0]
    w = [np.uint32(INNER_PREFIX[0]) | (L[:, 0] << _B8_LE)]
    for i in range(1, 5):
        w.append((L[:, i - 1] >> _B24_LE) | (L[:, i] << _B8_LE))
    w.append((L[:, 4] >> _B24_LE) | (R[:, 0] << _B8_LE))
    for i in range(1, 5):
        w.append((R[:, i - 1] >> _B24_LE) | (R[:, i] << _B8_LE))
    w.append((R[:, 4] >> _B24_LE) | jnp.uint32(0x80 << 8))
    zero = jnp.zeros((b,), dtype=jnp.uint32)
    w += [zero, zero, zero]
    w.append(jnp.full((b,), np.uint32(41 * 8), dtype=jnp.uint32))
    w.append(zero)
    return jnp.stack(w, axis=1)


def ripemd_inner_hash_device(L, R):
    """(B,5),(B,5) -> (B,5): batched RIPEMD-160 inner-node hash."""
    from tendermint_tpu.ops.ripemd160_kernel import _ripemd160_masked

    block = _inner_node_words_ripemd(L, R)
    ones = jnp.ones((L.shape[0],), dtype=jnp.int32)
    return _ripemd160_masked(block[:, None, :], ones, 1)


_ALGOS = {
    # algo -> (digest words, inner-node hash)
    "sha256": (8, inner_hash_device),
    "ripemd160": (5, ripemd_inner_hash_device),
}


@jax.named_scope("merkle.tree_reduce")
def _forest_levels(nodes, cnt, levels: int, algo: str = "sha256"):
    """Shared level reduction: nodes (T, P, W) u32, cnt (T,) i32 valid leaf
    prefixes, P = 2**levels. Returns (T, W) root words. A pair exists only
    if its right child is inside the valid prefix; an unpaired trailing
    node is promoted (== left child unchanged)."""
    width, inner = _ALGOS[algo]
    t = nodes.shape[0]
    for _ in range(levels):
        left = nodes[:, 0::2]
        right = nodes[:, 1::2]
        half = left.shape[1]
        paired = inner(
            left.reshape(t * half, width), right.reshape(t * half, width)
        ).reshape(t, half, width)
        idx = jnp.arange(half, dtype=jnp.int32)
        nodes = jnp.where(
            (2 * idx[None, :] + 1 < cnt[:, None])[..., None], paired, left
        )
        cnt = (cnt + 1) // 2
    return nodes[:, 0]


@partial(jax.jit, static_argnames=("levels", "algo"))
def _tree_reduce(leaves, count, levels: int, algo: str = "sha256"):
    """leaves: (P, W) u32 with P = 2**levels; count: traced i32 valid prefix.
    Returns (W,) root words. The T=1 case of `_forest_levels`."""
    return _forest_levels(leaves[None], jnp.asarray(count)[None], levels, algo)[0]


def merkle_root_from_leaf_words(leaf_digests, count=None, algo: str = "sha256"):
    """Root from device leaf hashes.

    leaf_digests: (N, W) u32 (already leaf-prefixed hashes; W = 8 for
    sha256 BE words, 5 for ripemd160 LE words). N is padded up to the
    next power of two internally; `count` defaults to N.
    """
    width = _ALGOS[algo][0]
    leaf_digests = jnp.asarray(leaf_digests, dtype=jnp.uint32)
    n = leaf_digests.shape[0]
    if n == 0:
        raise ValueError(
            "empty leaf batch has no root (host simple_hash_from_hashes([]) is b'')"
        )
    if leaf_digests.shape[1] != width:
        raise ValueError(
            f"{algo} leaf digests must be (N, {width}) words, got {leaf_digests.shape}"
        )
    if count is None:
        count = n
    P = 1
    while P < n:
        P *= 2
    if P != n:
        pad = jnp.zeros((P - n, width), dtype=jnp.uint32)
        leaf_digests = jnp.concatenate([leaf_digests, pad], axis=0)
    levels = P.bit_length() - 1
    return _tree_reduce(
        leaf_digests, jnp.asarray(count, dtype=jnp.int32), levels, algo
    )


@partial(jax.jit, static_argnames=("max_blocks", "levels", "algo"))
def _leafhash_and_reduce(
    blocks, n_blocks, counts, max_blocks: int, levels: int, algo: str = "sha256"
):
    """Fused leaf hashing + forest reduction: ONE device launch.

    blocks:   (T, P, max_blocks, 16) u32 padded leaf messages, P = 2**levels
    n_blocks: (T, P) i32 per-leaf block counts (0 for pad rows)
    counts:   (T,) i32 valid leaf prefix per tree
    -> (T, 8) u32 root words.

    The leaf SHA-256 pass and all log2(P) tree levels ship as a single
    executable rather than one call per stage, so a tree pays one
    launch's fixed cost (its size is not measured on v5e).
    """
    t, p = blocks.shape[0], blocks.shape[1]
    flat = blocks.reshape(t * p, max_blocks, 16)
    if algo == "ripemd160":
        from tendermint_tpu.ops.ripemd160_kernel import _ripemd160_masked

        digs = _ripemd160_masked(flat, n_blocks.reshape(-1), max_blocks)
    else:
        from tendermint_tpu.ops.sha256_kernel import _sha256_masked

        digs = _sha256_masked(flat, n_blocks.reshape(-1), max_blocks)
    width = _ALGOS[algo][0]
    return _forest_levels(digs.reshape(t, p, width), counts, levels, algo)


def merkle_roots_forest(
    trees: list[list[bytes]], algo: str = "sha256"
) -> list[bytes]:
    """Batched device tree build: one root per item list, ONE device call.

    All trees pad to a common (P, max_blocks) shape — the fast-sync /
    mempool-flood shape (BASELINE config 4: batched Txs.Hash + PartSet
    roots) where many blocks' trees build concurrently. Bit-equal to
    `merkle.simple.simple_hash_from_byte_slices` per tree; `algo` picks
    sha256 (the framework's target variant) or ripemd160 (the
    reference's bit-compat variant, `docs/specification/merkle.rst`).
    """
    from tendermint_tpu.ops.padding import (
        bucket_blocks,
        digests_to_bytes_be,
        digests_to_bytes_le,
        pad_ripemd160_prefixed,
        pad_sha256_prefixed,
    )

    t = len(trees)
    if t == 0:
        return []
    counts = np.array([len(items) for items in trees], dtype=np.int32)
    if (counts == 0).any():
        raise ValueError("empty tree in forest (host root of [] is b'')")
    n_max = int(counts.max())
    p = 1
    while p < n_max:
        p *= 2
    levels = p.bit_length() - 1
    flat = [x for items in trees for x in items]
    if algo == "ripemd160":
        blocks, n_blocks = pad_ripemd160_prefixed(flat, LEAF_PREFIX)
        to_bytes = digests_to_bytes_le
    else:
        blocks, n_blocks = pad_sha256_prefixed(flat, LEAF_PREFIX)
        to_bytes = digests_to_bytes_be
    mb = blocks.shape[1]
    # bucket the forest size so varying tree counts reuse compiled shapes
    # (pad trees are all-masked rows; their garbage roots are sliced off)
    t_pad = bucket_blocks(t)
    all_blocks = np.zeros((t_pad, p, mb, 16), dtype=np.uint32)
    all_nblocks = np.zeros((t_pad, p), dtype=np.int32)
    all_counts = np.ones(t_pad, dtype=np.int32)
    all_counts[:t] = counts
    off = 0
    for i, c in enumerate(counts):
        all_blocks[i, :c] = blocks[off : off + c]
        all_nblocks[i, :c] = n_blocks[off : off + c]
        off += c
    roots = _leafhash_and_reduce(
        all_blocks, all_nblocks, all_counts, mb, levels, algo
    )
    return to_bytes(np.asarray(roots)[:t])


def merkle_root_device(items: list[bytes], algo: str = "sha256") -> bytes:
    """Host convenience: full device tree build over raw byte items.

    Bit-equal to `merkle.simple.simple_hash_from_byte_slices`.
    """
    if not items:
        return b""
    return merkle_roots_forest([items], algo)[0]


def leaf_hashes_sharded(items: list[bytes], algo: str, manager) -> list[bytes]:
    """`leaf_hashes_device` over a device mesh: the padded leaf messages
    are zero-row-padded to a multiple of the active mesh size and each
    chip hashes its shard in one launch (`parallel.mesh.MeshManager`
    owns compilation, shard-fault detection, and survivor re-mesh).
    Verdict-identical to the single-device lane; pad rows carry
    n_blocks=0 and are sliced off before returning.
    """
    from tendermint_tpu.ops.padding import (
        digests_to_bytes_be,
        digests_to_bytes_le,
        pad_ripemd160_prefixed,
        pad_rows_to_multiple,
        pad_sha256_prefixed,
    )
    from tendermint_tpu.utils.fail import ShardDeviceFault

    if not items:
        return []
    if algo == "ripemd160":
        blocks, n_blocks = pad_ripemd160_prefixed(items, LEAF_PREFIX)
        to_bytes = digests_to_bytes_le
    else:
        blocks, n_blocks = pad_sha256_prefixed(items, LEAF_PREFIX)
        to_bytes = digests_to_bytes_be
    manager.maybe_reprobe()
    while True:
        if manager.n_active == 0:
            from tendermint_tpu.parallel.mesh import MeshExhaustedError

            raise MeshExhaustedError(
                f"all {manager.n_total} mesh devices faulted"
            )
        try:
            manager.check_shard_faults()
            if manager.executor == "host":
                # choreography stand-in (CPU CI): identical outputs via
                # the host leaf hash, same fault/re-mesh cycle as above
                from tendermint_tpu.merkle import simple as host_merkle

                return [host_merkle.leaf_hash(x, algo) for x in items]
            (b_pad, n_pad), n = pad_rows_to_multiple(
                [blocks, n_blocks], manager.n_active
            )
            step = manager.leaf_hash_step(algo, blocks.shape[1])
            digs = step(b_pad, n_pad)
            # device observatory: the leaf lane's pad geometry +
            # shipped block bytes on the successful attempt only (a
            # shard-fault retry must not double-count)
            from tendermint_tpu.telemetry import launchlog as _launchlog

            _launchlog.annotate(
                _additive=True, rows_padded=b_pad.shape[0] - n
            )
            _launchlog.annotate(mesh_width=manager.n_active)
            _launchlog.add_transfer(b_pad.nbytes + n_pad.nbytes)
            return to_bytes(np.asarray(digs)[:n])
        except ShardDeviceFault as e:
            if not manager.record_shard_fault(e.shard):
                raise


def leaf_hashes_device(items: list[bytes], algo: str = "sha256") -> list[bytes]:
    """Domain-separated leaf hashes for every item in ONE batched device
    launch (bit-equal to `merkle.simple.leaf_hash` per item). The
    state-sync chunk verifier uses this to check received chunk windows
    against a manifest's hash list without a per-chunk host hash loop.
    """
    from tendermint_tpu.ops.padding import (
        digests_to_bytes_be,
        digests_to_bytes_le,
        pad_ripemd160_prefixed,
        pad_sha256_prefixed,
    )

    if not items:
        return []
    if algo == "ripemd160":
        from tendermint_tpu.ops.ripemd160_kernel import _ripemd160_masked

        blocks, n_blocks = pad_ripemd160_prefixed(items, LEAF_PREFIX)
        digs = _ripemd160_masked(blocks, n_blocks, blocks.shape[1])
        return digests_to_bytes_le(np.asarray(digs))
    from tendermint_tpu.ops.sha256_kernel import _sha256_masked

    blocks, n_blocks = pad_sha256_prefixed(items, LEAF_PREFIX)
    digs = _sha256_masked(blocks, n_blocks, blocks.shape[1])
    return digests_to_bytes_be(np.asarray(digs))
