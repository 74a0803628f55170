"""TreeHasher: batched Merkle tree construction service.

Fills the `tmlibs/merkle.SimpleHash*` slot (reference call sites:
`types/block.go:177`, `types/tx.go:33-46`, `types/part_set.go:95-122`).
The device backend hashes all leaves as one batched SHA-256 kernel call
and reduces the tree in log2(N) fused levels entirely on device; the
host backend is the bit-identical sequential reference.

The reference's tree uses RIPEMD-160 (`docs/specification/merkle.rst`);
this framework's target variant is SHA-256 (BASELINE.md north star).
Device trees support BOTH variants (raw-leaf builds and
already-hashed-leaf aggregation).
"""

from __future__ import annotations

import time

import numpy as np

from tendermint_tpu.merkle import simple as host_merkle
from tendermint_tpu.telemetry import launchlog as _launchlog
from tendermint_tpu.telemetry import metrics as _metrics


def _observe_hash(
    backend: str, leaves: int, seconds: float, kind: str = "hash"
) -> None:
    _metrics.HASH_BATCH_LEAVES.labels(backend=backend).observe(leaves)
    _metrics.HASH_SECONDS.labels(backend=backend).observe(seconds)
    # device-observatory seam: closes/annotates the ambient launch
    # record (host micro-roots outside a dispatch handle record nothing)
    _launchlog.observe(kind, backend, leaves, seconds)

# Below this leaf count host hashlib answers; the device tree is for big
# blocks (BASELINE config 4 is 65k leaves). The value is not measured on
# v5e (ROADMAP Queue 1 item 5).
DEVICE_MIN_LEAVES = 8192


class TreeHasher:
    """Merkle root/proof builder with host and device backends."""

    def __init__(
        self,
        backend: str = "device",
        algo: str = "sha256",
        min_device_leaves: int | None = None,
        mesh=None,
    ) -> None:
        if backend not in ("device", "host"):
            raise ValueError(f"unknown backend {backend!r}")
        if algo not in ("sha256", "ripemd160"):
            raise ValueError(f"unknown algo {algo!r}")
        self.algo = algo
        # device trees support both variants: sha256 (the framework's
        # target) and ripemd160 (the reference's bit-compat tree,
        # `docs/specification/merkle.rst`)
        self.backend = backend
        self.min_device_leaves = (
            DEVICE_MIN_LEAVES if min_device_leaves is None else min_device_leaves
        )
        # Optional `parallel.mesh.MeshManager`: the LEAF hashing lane
        # (the O(N) term — statesync chunk gates, big-block leaf
        # passes) shards over the mesh; tree reduction stays
        # single-device (inner levels halve too fast to amortize
        # collectives). None = single-device legacy.
        self.mesh = mesh

    def _use_device(self, n: int) -> bool:
        return self.backend == "device" and n >= max(2, self.min_device_leaves)

    def root_from_items(self, items: list[bytes]) -> bytes:
        """SimpleMerkle root over raw byte leaves (leaf-prefixed hashes)."""
        t0 = time.perf_counter()
        if self._use_device(len(items)):
            from tendermint_tpu.ops.merkle_kernel import merkle_root_device

            out = merkle_root_device(items, self.algo)
            _observe_hash("device", len(items), time.perf_counter() - t0)
            return out
        out = host_merkle.simple_hash_from_byte_slices(items, self.algo)
        _observe_hash("host", len(items), time.perf_counter() - t0)
        return out

    def root_from_hashes(self, hashes: list[bytes]) -> bytes:
        """Root over already-hashed leaves (PartSet/Commit aggregation)."""
        t0 = time.perf_counter()
        if self._use_device(len(hashes)):
            from tendermint_tpu.ops.merkle_kernel import merkle_root_from_leaf_words
            from tendermint_tpu.ops.padding import (
                digests_to_bytes_be,
                digests_to_bytes_le,
            )

            # sha256 digests are big-endian words; ripemd160 little-endian
            dt, to_bytes = (
                (">u4", digests_to_bytes_be)
                if self.algo == "sha256"
                else ("<u4", digests_to_bytes_le)
            )
            words = (
                np.frombuffer(b"".join(hashes), dtype=dt)
                .astype(np.uint32)
                .reshape(len(hashes), -1)
            )
            root = merkle_root_from_leaf_words(words, algo=self.algo)
            out = to_bytes(np.asarray(root)[None, :])[0]
            _observe_hash("device", len(hashes), time.perf_counter() - t0)
            return out
        out = host_merkle.simple_hash_from_hashes(hashes, self.algo)
        _observe_hash("host", len(hashes), time.perf_counter() - t0)
        return out

    def leaf_hashes(self, items: list[bytes]) -> list[bytes]:
        """Per-item domain-separated leaf hashes (state-sync chunk
        verification) — one batched device launch above the threshold
        (sharded over every active mesh chip when a mesh is attached),
        host hashlib below it."""
        t0 = time.perf_counter()
        if self._use_device(len(items)):
            if self.mesh is not None and self.mesh.n_total > 1:
                from tendermint_tpu.ops.merkle_kernel import leaf_hashes_sharded

                out = leaf_hashes_sharded(items, self.algo, self.mesh)
                _observe_hash(
                    "mesh", len(items), time.perf_counter() - t0,
                    kind="leaf_hashes",
                )
                return out
            from tendermint_tpu.ops.merkle_kernel import leaf_hashes_device

            out = leaf_hashes_device(items, self.algo)
            _observe_hash(
                "device", len(items), time.perf_counter() - t0,
                kind="leaf_hashes",
            )
            return out
        out = [host_merkle.leaf_hash(x, self.algo) for x in items]
        _observe_hash(
            "host", len(items), time.perf_counter() - t0, kind="leaf_hashes"
        )
        return out

    def leaf_hashes_async(self, items: list[bytes], queue=None):
        """`leaf_hashes` through a `DispatchQueue` handle — the chunk-
        verify gate submits the whole-set hash and overlaps payload
        decode while it runs (statesync/snapshot.py). The resilient
        wrapper overrides this with the breaker-guarded version."""
        from tendermint_tpu.services.dispatch import default_dispatch_queue

        q = queue if queue is not None else default_dispatch_queue()
        return q.submit(lambda: self.leaf_hashes(items), kind="hash")

    def proofs(self, items: list[bytes]):
        """Merkle proofs stay on host: O(N log N) pointer work, tiny data."""
        return host_merkle.simple_proofs_from_byte_slices(items, self.algo)


_DEFAULT: TreeHasher | None = None


def default_hasher() -> TreeHasher:
    global _DEFAULT
    if _DEFAULT is None:
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        _DEFAULT = ResilientTreeHasher(TreeHasher())
    return _DEFAULT


def auto_hasher() -> TreeHasher:
    """Device-backed hasher iff a TPU backend is actually up.

    The node composition root calls this once at start so block production
    (`types/tx.go:33-46` analog) rides the device tree on TPU while CPU-only
    runs (tests, dev) never pay an XLA compile for host-sized work.

    Device trees come wrapped in `ResilientTreeHasher` — a device fault
    degrades block hashing to host hashlib behind a circuit breaker
    instead of failing block production (`services/resilient.py`). Host
    runs get the wrapper too when fault injection is armed, so chaos
    tests drive the same dispatch path on CPU CI.
    """
    import jax

    from tendermint_tpu.services.verifier import _mesh_opt_in_cpu
    from tendermint_tpu.utils.fail import device_faults_armed

    if jax.default_backend() == "tpu":
        from tendermint_tpu.parallel.mesh import (
            default_mesh_manager,
            mesh_device_count,
        )
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        mesh = default_mesh_manager() if mesh_device_count() > 1 else None
        return ResilientTreeHasher(TreeHasher(backend="device", mesh=mesh))
    if _mesh_opt_in_cpu():
        # the CPU virtual-device recipe (docs/PLATFORM_NOTES.md): the
        # leaf lane shards over the forced mesh, breaker-wrapped like
        # the TPU composition so chaos tests drive the same path
        from tendermint_tpu.parallel.mesh import default_mesh_manager
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        return ResilientTreeHasher(
            TreeHasher(backend="device", mesh=default_mesh_manager()),
            TreeHasher(backend="host"),
        )
    if device_faults_armed():
        from tendermint_tpu.services.resilient import ResilientTreeHasher

        return ResilientTreeHasher(
            TreeHasher(backend="device"), TreeHasher(backend="host")
        )
    return TreeHasher(backend="host")
