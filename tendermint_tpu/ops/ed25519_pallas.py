"""The table path's Pallas kernels: plane arithmetic and `pallas_call`s.

This file's lines are in every verify executable's compile-cache key: a
Mosaic kernel's serialized body names the file and line of each op.
A change here is a cold set-up in every cell; write everything else elsewhere.

Layout: the batch is tiled into (8, 128) VPU tiles; every field-element
limb is a separate (8, 128) plane so each vector op runs at full lane
occupancy. The accumulator lives in a VMEM scratch (80 planes = X,Y,Z,T
x 20 limbs) that persists across the minor grid steps; operands stream
in as blocks double-buffered by the Pallas pipeline. HBM traffic is one
read of the operands and one write of the final accumulator: the XLA
scan's per-step carry round-trips are gone.

Two kernels: the materialized-entries madd chain (`_sum_entries_pallas`:
entries selected by XLA, one stack of lanes) and the fused
select+accumulate kernel (`_fused_chain_pallas`, the production path on
the TPU; `ed25519_tables.py` says when each is picked and why). The
generic ladder's kernel (`ed25519_ladder_pallas.py`) shares the planes.
Step counts and tile widths come from the operands' shapes; nothing is
imported from the modules that call this one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops.ed25519_kernel import NLIMBS

_LANES = 1024  # 8 x 128 batch elements per grid tile of the madd chain


def _carry_planes(t):
    """fe_carry on a list of 20 (8,128) planes (3 rounds, like fe_carry)."""
    for _ in range(3):
        c = [v >> 13 for v in t]
        r = [v & 8191 for v in t]
        t = [r[0] + 608 * c[-1]] + [r[i] + c[i - 1] for i in range(1, 20)]
    return t


def _mul_planes(a, b):
    """fe_mul on lists of 20 (8,128) planes (mirrors fe_mul exactly)."""
    cols = []
    for k in range(39):
        lo, hi = max(0, k - 19), min(k, 19)
        t = a[lo] * b[k - lo]
        for i in range(lo + 1, hi + 1):
            t = t + a[i] * b[k - i]
        cols.append(t)
    c = [v >> 13 for v in cols]
    r = [v & 8191 for v in cols]
    out = [r[0]] + [r[i] + c[i - 1] for i in range(1, 39)]
    lo_ = out[:20]
    hi_ = out[20:] + [c[-1]]
    return _carry_planes([lo_[i] + 608 * hi_[i] for i in range(20)])


def _sub_planes(a, b):
    d = [x - y for x, y in zip(a, b)]
    return _carry_planes(_carry_planes(d))


def _addc_planes(a, b):
    return _carry_planes([x + y for x, y in zip(a, b)])


def _madd_planes(acc, ypx, ymx, t2d):
    x1, y1, z1, t1 = acc
    a = _mul_planes(_sub_planes(y1, x1), ymx)
    b = _mul_planes(_addc_planes(y1, x1), ypx)
    c = _mul_planes(t1, t2d)
    d = _carry_planes([v + v for v in z1])
    e = _sub_planes(b, a)
    f = _sub_planes(d, c)
    g = _addc_planes(d, c)
    h = _addc_planes(b, a)
    return (
        _mul_planes(e, f),
        _mul_planes(g, h),
        _mul_planes(f, g),
        _mul_planes(e, h),
    )


# ---- materialized-entries madd chain ------------------------------------------


def _madd_chain_kernel(ent_ref, out_ref, acc_ref, *, nsteps):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        # identity (0, 1, 1, 0): Y limb 0 and Z limb 0 are 1 (scatter is
        # not lowerable in pallas, so build via an iota select)
        rows = jax.lax.broadcasted_iota(jnp.int32, (80, 8, 128), 0)
        acc_ref[:] = jnp.where((rows == 20) | (rows == 40), 1, 0)

    ent = ent_ref[0, 0]  # (60, 8, 128)
    acc = tuple(
        [acc_ref[20 * ci + i] for i in range(20)] for ci in range(4)
    )
    ypx = [ent[i] for i in range(20)]
    ymx = [ent[20 + i] for i in range(20)]
    t2d = [ent[40 + i] for i in range(20)]
    nxt = _madd_planes(acc, ypx, ymx, t2d)
    acc_ref[:] = jnp.stack([p for coord in nxt for p in coord])

    @pl.when(t == nsteps - 1)
    def _():
        out_ref[0] = acc_ref[:]


def _sum_entries_pallas(ent):
    """ent (nsteps, B, 60) -> extended acc, B a multiple of 1024 lanes."""
    nsteps, bsz = ent.shape[:2]
    tiles = bsz // _LANES
    # (nsteps, B, 60) -> (tiles, nsteps, 60, 8, 128)
    e = ent.reshape(nsteps, tiles, 8, 128, 60)
    e = jnp.transpose(e, (1, 0, 4, 2, 3))
    out = pl.pallas_call(
        partial(_madd_chain_kernel, nsteps=nsteps),
        grid=(tiles, nsteps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, 60, 8, 128),
                lambda i, t: (i, t, 0, 0, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 80, 8, 128), lambda i, t: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(
            (tiles, 80, 8, 128), jnp.int32, vma=jax.typeof(e).vma
        ),
        scratch_shapes=[pltpu.VMEM((80, 8, 128), jnp.int32)],
    )(e)
    # (tiles, 80, 8, 128) -> 4 coords of (B, 20)
    coords = out.reshape(tiles, 4, 20, 8, 128)
    coords = jnp.transpose(coords, (1, 0, 3, 4, 2)).reshape(4, bsz, NLIMBS)
    return coords[0], coords[1], coords[2], coords[3]


# ---- fused select+accumulate ----------------------------------------------------
#
# Each grid step selects its operands INSIDE the kernel from the (int16,
# read-once) table block and feeds them straight to the VMEM-resident
# mixed-add accumulator. Both combs are w=4, so every step's selection
# is the same 16-way masked sum: steps 0..a_start-1 take the shared
# fixed-base table `sb`, the rest this tile's validators' columns.
#
# Lane geometry: one grid tile covers `v_tile` validators x ALL
# `c_tile` stacked commits (lane planes are (8, v_tile * c_tile / 8): the
# plane width scales with the stack instead of adding commit-blocks to
# the grid), so each table block serves every lane that ever needs it
# and the full table is read EXACTLY ONCE per launch.


def _make_fused_kernel(v_tile: int, c_tile: int, a_start: int, nsteps: int):
    w = v_tile * c_tile // 8  # lane plane shape (8, w)

    def kernel(sb_ref, atab_ref, dig_ref, out_ref, acc_ref, ent_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            rows = jax.lax.broadcasted_iota(jnp.int32, (80, 8, w), 0)
            acc_ref[:] = jnp.where((rows == 20) | (rows == 40), 1, 0)

        dig = dig_ref[0, 0]  # (8, w) int32 nibbles for this step
        masks = [dig == d for d in range(16)]

        @pl.when(t < a_start)
        def _():
            sb = sb_ref[0]  # (16, 60) int32 — shared by every lane
            planes = []
            for limb in range(60):
                acc = jnp.zeros((8, w), jnp.int32)
                for d in range(16):
                    acc = acc + jnp.where(masks[d], sb[d, limb], 0)
                planes.append(acc)
            ent_ref[:] = jnp.stack(planes)

        @pl.when(t >= a_start)
        def _():
            at = atab_ref[0].astype(jnp.int32)  # (16, 60, v_tile)
            reps = w // v_tile  # commits per plane row (= c_tile/8)
            planes = []
            for limb in range(60):
                acc = jnp.zeros((8, w), jnp.int32)
                for d in range(16):
                    col = at[d, limb]  # (v_tile,) — this tile's validators
                    # lanes are commit-major (lane = c*128 + v), so the
                    # column expands by row-splat + minor concat — the
                    # only vector reshapes Mosaic supports here
                    bv = jnp.broadcast_to(col[None, :], (8, v_tile))
                    if reps > 1:
                        bv = jnp.concatenate([bv] * reps, axis=1)
                    acc = acc + jnp.where(masks[d], bv, 0)
                planes.append(acc)
            ent_ref[:] = jnp.stack(planes)

        ent = ent_ref[:]
        acc = tuple(
            [acc_ref[20 * ci + i] for i in range(20)] for ci in range(4)
        )
        ypx = [ent[i] for i in range(20)]
        ymx = [ent[20 + i] for i in range(20)]
        t2d = [ent[40 + i] for i in range(20)]
        nxt = _madd_planes(acc, ypx, ymx, t2d)
        acc_ref[:] = jnp.stack([p for coord in nxt for p in coord])

        @pl.when(t == nsteps - 1)
        def _():
            out_ref[0] = acc_ref[:]

    return kernel


def _fused_chain_pallas(sb, a_tables, digits, v_tile, c_tile, interpret=False):
    """sb (S,16,60) int32 fixed-base comb, a_tables (A,16,60,N) int16,
    digits (B, S+A) int32 kernel-order -> extended acc coords, each
    (B, 20) int32 kernel-order."""
    a_start = sb.shape[0]
    bsz, nsteps = digits.shape
    lanes_per_tile = v_tile * c_tile
    tiles = bsz // lanes_per_tile  # == N / v_tile validator blocks
    w = lanes_per_tile // 8
    # digits -> (tiles, nsteps, 8, w) step-major planes so the
    # pipeline hands each step its (8, w) nibble plane directly
    dig = digits.reshape(tiles, 8, w, nsteps)
    dig = jnp.transpose(dig, (0, 3, 1, 2))

    out = pl.pallas_call(
        _make_fused_kernel(v_tile, c_tile, a_start, nsteps),
        grid=(tiles, nsteps),
        in_specs=[
            pl.BlockSpec(
                (1, 16, 60),
                lambda i, t: (jnp.minimum(t, a_start - 1), 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 16, 60, v_tile),
                lambda i, t: (jnp.maximum(t - a_start, 0), 0, 0, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, 8, w),
                lambda i, t: (i, t, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 80, 8, w), lambda i, t: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(
            (tiles, 80, 8, w), jnp.int32, vma=jax.typeof(dig).vma
        ),
        scratch_shapes=[
            pltpu.VMEM((80, 8, w), jnp.int32),
            pltpu.VMEM((60, 8, w), jnp.int32),
        ],
        interpret=interpret,
    )(sb, a_tables, dig)
    coords = out.reshape(tiles, 4, 20, 8, w)
    coords = jnp.transpose(coords, (1, 0, 3, 4, 2)).reshape(4, bsz, NLIMBS)
    return coords[0], coords[1], coords[2], coords[3]
