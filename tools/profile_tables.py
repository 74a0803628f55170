"""Stage-by-stage profile of the ed25519 tables verify path on the bench
device. Timing is value-independent (fixed shapes, integer ops), so tables
and lane inputs are random with in-range limb magnitudes — no 65s build.

Stages, for B = K*N lanes:
  sel    : _select_entries                (table -> (96, B, 60) entries)
  chain  : _sum_entries_pallas            (entries -> extended point)
  finish : batched invert + encode + cmp  (point -> verdict)
  full   : verify_tables_kernel
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops.ed25519_kernel import NLIMBS, fe_canon, fe_carry, fe_mul, fe_to_bytes
from tendermint_tpu.ops.ed25519_tables import (
    _select_entries,
    _sum_entries_pallas,
    fe_batch_invert,
    verify_tables_kernel,
)

N = 10_240


def timeit(fn, *args, reps=3):
    """fn must return a SMALL array; sync point is the d2h fetch."""
    np.asarray(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        np.asarray(fn(*args))
        best = min(best, time.time() - t0)
    return best


def finish(x, y, z, r):
    zinv = fe_batch_invert(fe_carry(z))
    x_aff = fe_canon(fe_mul(x, zinv))
    y_bytes = fe_to_bytes(fe_mul(y, zinv))
    parity = x_aff[..., 0] & 1
    sign = (r[..., 31] >> 7) & 1
    r_clean = r.at[..., 31].set(r[..., 31] & 0x7F)
    return jnp.all(y_bytes == r_clean, axis=-1) & (parity == sign)


def main():
    rng = np.random.default_rng(0)
    tbl = jnp.asarray(rng.integers(0, 8192, size=(64, 16, 60, N), dtype=np.int16))
    # each stage reduced to a scalar on device so the d2h sync is tiny
    sel_small = jax.jit(lambda t, s, h: _select_entries(t, s, h).sum())
    sel_j = jax.jit(_select_entries)
    chain_small = jax.jit(lambda e: sum(c.sum() for c in _sum_entries_pallas(e)))
    fin_j = jax.jit(finish)

    for k in (4, 16):
        b = k * N
        s = jnp.asarray(rng.integers(0, 256, size=(b, 32), dtype=np.int32).astype(np.uint8))
        h = jnp.asarray(rng.integers(0, 256, size=(b, 32), dtype=np.int32).astype(np.uint8))
        r = jnp.asarray(rng.integers(0, 256, size=(b, 32), dtype=np.int32).astype(np.uint8))
        si = s.astype(jnp.int32)
        hi = h.astype(jnp.int32)
        ri = r.astype(jnp.int32)

        t_full = timeit(verify_tables_kernel, tbl, s, h, r)
        print(f"K={k} B={b}: full={t_full*1e3:.1f}ms -> {b/t_full:,.0f}/s", flush=True)
        t_sel = timeit(sel_small, tbl, si, hi)
        print(f"K={k} B={b}: sel={t_sel*1e3:.1f}ms", flush=True)
        ent = sel_j(tbl, si, hi)
        t_chain = timeit(chain_small, ent)
        print(f"K={k} B={b}: chain={t_chain*1e3:.1f}ms", flush=True)
        x, y, z, _t = jax.jit(_sum_entries_pallas)(ent)
        t_fin = timeit(fin_j, x, y, z, ri)
        print(f"K={k} B={b}: finish={t_fin*1e3:.1f}ms", flush=True)


if __name__ == "__main__":
    main()
