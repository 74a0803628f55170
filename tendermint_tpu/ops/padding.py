"""Host-side message padding into fixed-shape u32 block arrays.

Variable-length sign-bytes/leaves are padded to bucketed block counts so the
device kernels see only static shapes (bucketing avoids one XLA recompile per
message length — SURVEY.md §7 hard part 2). All functions return numpy arrays
ready to ship to device.
"""

from __future__ import annotations

import numpy as np

SHA256_BLOCK_BYTES = 64


def bucket_blocks(n: int, buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32)) -> int:
    """Smallest bucket >= n (shape-stable compilation)."""
    for b in buckets:
        if n <= b:
            return b
    # beyond the largest bucket: round up to a multiple of it
    top = buckets[-1]
    return ((n + top - 1) // top) * top


def _md_pad(msg: bytes, block: int, length_bytes: int, length_le: bool) -> bytes:
    """Merkle-Damgård padding: 0x80, zeros, message bit-length."""
    bitlen = len(msg) * 8
    padded = msg + b"\x80"
    rem = (len(padded) + length_bytes) % block
    if rem:
        padded += b"\x00" * (block - rem)
    if length_le:
        padded += bitlen.to_bytes(length_bytes, "little")
    else:
        padded += bitlen.to_bytes(length_bytes, "big")
    return padded


def pad_sha256(msgs: list[bytes], max_blocks: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """-> (blocks[B, max_blocks, 16] u32 big-endian words, n_blocks[B] i32)."""
    return pad_sha256_prefixed(msgs, b"", max_blocks)


def pad_sha256_prefixed(
    msgs: list[bytes], prefix: bytes = b"", max_blocks: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized SHA-256 padding of `prefix || msg` for every message.

    -> (blocks[B, max_blocks, 16] u32 big-endian words, n_blocks[B] i32).

    Bulk numpy packing grouped by message length: per-item Python work is
    one len() only, so 65k+ Merkle leaves pack as a handful of C-speed
    array writes instead of 65k bytes concatenations (the round-2 ingest
    bottleneck at `merkle_kernel.py:113`).
    """
    n = len(msgs)
    plen = len(prefix)
    if n == 0:
        return np.zeros((0, max_blocks or 1, 16), dtype=np.uint32), np.zeros(
            0, dtype=np.int32
        )
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n) + plen
    # Merkle-Damgård: msg || 0x80 || zeros || 8-byte bit length
    counts = ((lens + 9 + 63) // 64).astype(np.int32)
    mb = max_blocks if max_blocks is not None else bucket_blocks(int(counts.max()))
    buf = np.zeros((n, mb * 64), dtype=np.uint8)
    prefix_arr = (
        np.frombuffer(prefix, dtype=np.uint8) if plen else None
    )
    # group by length in one O(n log n) pass (per-unique-length rescans
    # would be quadratic for mostly-distinct lengths)
    order = np.argsort(lens, kind="stable")
    sorted_lens = lens[order]
    run_starts = np.nonzero(np.diff(sorted_lens))[0] + 1
    for idx in np.split(order, run_starts):
        total = int(lens[idx[0]])
        body = total - plen
        if body:
            raw = np.frombuffer(
                b"".join(msgs[i] for i in idx), dtype=np.uint8
            ).reshape(len(idx), body)
            buf[idx, plen:total] = raw
        if plen:
            buf[idx, :plen] = prefix_arr
        buf[idx, total] = 0x80
        c = int((total + 9 + 63) // 64)
        length_be = np.frombuffer(
            int(total * 8).to_bytes(8, "big"), dtype=np.uint8
        )
        buf[idx, c * 64 - 8 : c * 64] = length_be
    blocks = (
        buf.view(">u4").astype(np.uint32).reshape(n, mb, 16)
    )
    return blocks, counts


def pad_ripemd160(msgs: list[bytes], max_blocks: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """-> (blocks[B, max_blocks, 16] u32 little-endian words, n_blocks[B] i32)."""
    return pad_ripemd160_prefixed(msgs, b"", max_blocks)


def pad_ripemd160_prefixed(
    msgs: list[bytes], prefix: bytes = b"", max_blocks: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """RIPEMD-160 padding of `prefix || msg` (LE words, LE bit length)."""
    padded = [_md_pad(prefix + m, 64, 8, length_le=True) for m in msgs]
    counts = np.array([len(p) // 64 for p in padded], dtype=np.int32)
    mb = max_blocks if max_blocks is not None else bucket_blocks(int(counts.max(initial=1)))
    out = np.zeros((len(msgs), mb, 16), dtype=np.uint32)
    for i, p in enumerate(padded):
        words = np.frombuffer(p, dtype="<u4").astype(np.uint32)
        out[i, : counts[i]] = words.reshape(-1, 16)
    return out, counts


def pad_rows_to(arrays: list[np.ndarray], size: int) -> list[np.ndarray]:
    """Zero-pad every array's leading (row) axis up to exactly `size`.

    The mesh shard path pads lane batches to a per-chip-bucket multiple
    of the mesh size with zero rows: a zero ed25519 row verifies False
    on device (see `parallel.mesh.pad_to_multiple`) and a zero leaf row
    carries n_blocks=0, so pad rows can never leak a True verdict or a
    real digest — callers slice outputs back to the true row count.
    """
    out = []
    for a in arrays:
        n = a.shape[0]
        if n == size:
            out.append(a)
            continue
        if n > size:
            raise ValueError(f"cannot pad {n} rows down to {size}")
        pad = np.zeros((size - n,) + a.shape[1:], dtype=a.dtype)
        out.append(np.concatenate([a, pad]))
    return out


def pad_rows_to_multiple(
    arrays: list[np.ndarray], multiple: int
) -> tuple[list[np.ndarray], int]:
    """Zero-pad row counts up to the next multiple of `multiple`;
    returns (padded arrays, true row count) for post-kernel slicing."""
    n = arrays[0].shape[0]
    size = ((n + multiple - 1) // multiple) * multiple
    return pad_rows_to(arrays, size), n


def digests_to_bytes_be(digests: np.ndarray) -> list[bytes]:
    """(B, W) u32 big-endian word digests -> list of byte digests."""
    arr = np.asarray(digests, dtype=np.uint32)
    return [w.astype(">u4").tobytes() for w in arr]


def digests_to_bytes_le(digests: np.ndarray) -> list[bytes]:
    """(B, W) u32 little-endian word digests (RIPEMD-160) -> bytes."""
    arr = np.asarray(digests, dtype=np.uint32)
    return [w.astype("<u4").tobytes() for w in arr]
