"""Device kernels: the hot numeric plane (SURVEY.md §2b).

Everything here is pure, fixed-shape, integer-only JAX — deterministic by
construction (no floats), batched over the leading dimension, shardable over a
`jax.sharding.Mesh`. These kernels replace the reference's pure-Go crypto
libraries (ed25519, sha256, ripemd160) at the `BatchVerifier`/`TreeHasher`
seams.
"""

from tendermint_tpu.ops.sha256_kernel import sha256_batch_jax, sha256_digest_bytes
from tendermint_tpu.ops.ripemd160_kernel import ripemd160_batch_jax
from tendermint_tpu.ops.merkle_kernel import merkle_root_device, merkle_root_from_leaf_words

__all__ = [
    "sha256_batch_jax",
    "sha256_digest_bytes",
    "ripemd160_batch_jax",
    "merkle_root_device",
    "merkle_root_from_leaf_words",
]
