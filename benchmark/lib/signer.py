"""Signing workers for the chain generator.

Every present validator signs the same precommit sign-bytes for a block,
so a block is one message and N signatures. Each worker holds a slice of
the validators' keys (derived from the seed, never sent over the pipe) and
answers one message with that slice's signatures, concatenated. The
worker imports the `cryptography` library and nothing of the program, so
a spawned worker starts in a fraction of a second and never sees JAX.
"""

from __future__ import annotations

import hashlib
import multiprocessing as mp

from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey


def key_seed(seed: int, index: int) -> bytes:
    """The 32-byte ed25519 seed of validator `index` under run seed `seed`."""
    return hashlib.sha256(b"benchmark/%d/val/%d" % (seed, index)).digest()


def private_key(seed: int, index: int) -> Ed25519PrivateKey:
    return Ed25519PrivateKey.from_private_bytes(key_seed(seed, index))


def public_bytes(key: Ed25519PrivateKey) -> bytes:
    return key.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw
    )


def _worker(conn, seed: int, indices: list[int]) -> None:
    keys = [private_key(seed, i) for i in indices]
    try:
        while True:
            msg = conn.recv_bytes()
            if not msg:
                return
            conn.send_bytes(b"".join(k.sign(msg) for k in keys))
    except (EOFError, KeyboardInterrupt):
        return
    finally:
        conn.close()


class SignerPool:
    """`sign(msg)` -> {key index: signature} for every key index given.

    `workers` processes (spawned, never forked: the caller may hold
    threads) share the indices round-robin; with `workers` 0 the caller's
    own process signs."""

    def __init__(self, seed: int, indices: list[int], workers: int) -> None:
        self._slices: list[list[int]] = []
        self._conns = []
        self._procs = []
        self._local = None
        if workers <= 0 or len(indices) < 64:
            self._local = [(i, private_key(seed, i)) for i in indices]
            return
        ctx = mp.get_context("spawn")
        for w in range(workers):
            part = indices[w::workers]
            if not part:
                continue
            parent, child = ctx.Pipe()
            proc = ctx.Process(target=_worker, args=(child, seed, part), daemon=True)
            proc.start()
            child.close()
            self._slices.append(part)
            self._conns.append(parent)
            self._procs.append(proc)

    def sign(self, msg: bytes) -> dict[int, bytes]:
        if self._local is not None:
            return {i: k.sign(msg) for i, k in self._local}
        for conn in self._conns:
            conn.send_bytes(msg)
        out: dict[int, bytes] = {}
        for part, conn in zip(self._slices, self._conns):
            blob = conn.recv_bytes()
            for j, i in enumerate(part):
                out[i] = blob[64 * j : 64 * j + 64]
        return out

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.send_bytes(b"")
            except OSError:
                pass
            conn.close()
        for proc in self._procs:
            proc.join(10)
            if proc.is_alive():
                proc.kill()
                proc.join(5)
        self._conns, self._procs = [], []

    def __enter__(self) -> "SignerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
