"""Device hash kernels cross-validated bit-exactly against hashlib."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.merkle import simple_hash_from_byte_slices
from tendermint_tpu.ops import (
    merkle_root_device,
    ripemd160_batch_jax,
    sha256_batch_jax,
    sha256_digest_bytes,
)
from tendermint_tpu.ops.padding import (
    digests_to_bytes_be,
    digests_to_bytes_le,
    pad_ripemd160,
    pad_sha256,
)

# Device-kernel compiles dominate runtime (~minutes per bucket shape);
# excluded from the default selection (pytest.ini addopts) — run with
#   pytest -m kernel
# kernel suites are also 'slow': tier-1 CI selects -m 'not slow' (which
# overrides the ini's 'not kernel' default), and these compile device
# kernels on XLA:CPU for minutes. 'pytest -m kernel' still runs them.
pytestmark = [pytest.mark.kernel, pytest.mark.slow]

LENGTHS = [0, 1, 3, 31, 32, 55, 56, 63, 64, 65, 111, 112, 127, 128, 129, 200, 300]


def msgs_of_lengths():
    rng = np.random.RandomState(7)
    return [rng.bytes(n) for n in LENGTHS]


def test_sha256_matches_hashlib():
    msgs = msgs_of_lengths()
    blocks, counts = pad_sha256(msgs)
    got = digests_to_bytes_be(np.asarray(sha256_batch_jax(blocks, counts)))
    want = [hashlib.sha256(m).digest() for m in msgs]
    assert got == want


def test_sha256_convenience_api():
    msgs = [b"", b"abc", b"x" * 1000]
    assert sha256_digest_bytes(msgs) == [hashlib.sha256(m).digest() for m in msgs]


def test_ripemd160_matches_hashlib():
    msgs = msgs_of_lengths()
    blocks, counts = pad_ripemd160(msgs)
    out = np.asarray(ripemd160_batch_jax(blocks, counts))
    got = digests_to_bytes_le(out)
    want = []
    for m in msgs:
        h = hashlib.new("ripemd160")
        h.update(m)
        want.append(h.digest())
    assert got == want


def test_mixed_length_bucketing_masks_correctly():
    # same batch, very different block counts: masking must freeze short msgs
    msgs = [b"a", b"b" * 500, b"c" * 10, b"d" * 250]
    blocks, counts = pad_sha256(msgs, max_blocks=16)
    got = digests_to_bytes_be(np.asarray(sha256_batch_jax(blocks, counts)))
    assert got == [hashlib.sha256(m).digest() for m in msgs]


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 9, 11, 16, 17, 33, 100, 255, 256])
def test_merkle_root_device_matches_host(n):
    items = [f"leaf-{i}".encode() * (i % 5 + 1) for i in range(n)]
    assert merkle_root_device(items) == simple_hash_from_byte_slices(items)


def test_merkle_empty():
    assert merkle_root_device([]) == b""


def test_merkle_device_large_pow2():
    items = [i.to_bytes(8, "big") for i in range(1024)]
    assert merkle_root_device(items) == simple_hash_from_byte_slices(items)


def test_merkle_forest_mixed_tree_sizes():
    # one launch, trees of different leaf counts and leaf lengths
    from tendermint_tpu.ops.merkle_kernel import merkle_roots_forest

    trees = [
        [b"a", b"bb", b"ccc"],
        [f"x{i}".encode() * (i % 3 + 1) for i in range(17)],
        [b"solo"],
        [i.to_bytes(4, "big") for i in range(64)],
    ]
    got = merkle_roots_forest(trees)
    assert got == [simple_hash_from_byte_slices(t) for t in trees]


def test_65k_tx_block_data_hash_from_device_tree():
    """BASELINE config 4 as a production path: a 65k-tx block built through
    the device TreeHasher gets a data_hash bit-identical to the host tree
    (reference hot spot `types/tx.go:33-46` via `types/block.go:173-188`)."""
    from tendermint_tpu.services.hasher import TreeHasher
    from tendermint_tpu.types import BlockID, Txs
    from tendermint_tpu.types.block import Block, Commit

    txs = Txs(b"tx-%06d" % i for i in range(65536))
    dev = TreeHasher(backend="device")  # 65k clears the default threshold
    block = Block.make_block(
        height=1,
        chain_id="kernel-chain",
        txs=txs,
        last_commit=Commit.empty(),
        last_block_id=BlockID.zero(),
        time=1,
        validators_hash=b"\x01" * 20,
        app_hash=b"",
        hasher=dev,
    )
    assert block.header.data_hash == simple_hash_from_byte_slices(list(txs))
    # and the validation side accepts it through the same device path
    block.validate_basic(dev)


@pytest.mark.parametrize("n", [1, 2, 3, 7, 16, 33, 100])
def test_ripemd_merkle_tree_matches_host(n):
    """Device RIPEMD-160 tree (the reference's bit-compat variant,
    `docs/specification/merkle.rst:52-90`) vs the host tree."""
    items = [f"rleaf-{i}".encode() * (i % 4 + 1) for i in range(n)]
    assert merkle_root_device(items, "ripemd160") == simple_hash_from_byte_slices(
        items, "ripemd160"
    )


def test_ripemd_forest_mixed_tree_sizes():
    from tendermint_tpu.ops.merkle_kernel import merkle_roots_forest

    trees = [
        [b"a", b"bb", b"ccc"],
        [f"r{i}".encode() * (i % 3 + 1) for i in range(9)],
        [b"solo"],
    ]
    got = merkle_roots_forest(trees, "ripemd160")
    assert got == [simple_hash_from_byte_slices(t, "ripemd160") for t in trees]
