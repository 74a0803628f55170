"""Pallas fast path for the GENERIC double-scalar ladder.

The ad-hoc verify path (no cached valset tables — light-client first
contact, valset turnover beyond the cache, mixed-key batches; reference
`types/validator_set.go:284-349` VerifyCommitAny and every per-vote
check before tables exist) runs `ed25519_kernel.verify_kernel`'s
253-step Shamir ladder as a lax.scan, which round-trips the
4-coordinate accumulator through HBM on every step. This module runs
the same ladder as a Pallas kernel with the accumulator resident in
VMEM — the treatment that took the table path to 1.45M verifies/s
(`_fused_chain_pallas`), applied to the generic case.

Shape of the computation per lane:
  table = {O, B, -A, B-A} in affine ypx/ymx/t2d precomp form (built
  once per lane by XLA: one decompress + one point add + one batched
  inversion), then 253 identical steps of
      acc = madd(double(acc), table[s_bit + 2*h_bit])
  msb-first. Identity is the precomp (1, 1, 0), so selection is a
  4-way masked sum and every step is branch-free. The verdict is the
  table path's encode-and-compare (`_finish_encode_compare`) — R is
  never decompressed, halving the XLA prologue's sequential field work.

A width-2 windowed variant (127 steps, 16-entry table) was tried and
dropped: its 16-way masked-sum select + int16 conversions cost more
than the madds it saves (not re-measured on v5e). Bit-serial with a
4-way select is the keeper.

Tiles are as wide as VMEM allows (up to 4096 lanes -> (8, 512) planes):
fewer, fatter grid steps amortize Mosaic's per-step overhead the same
way the fused table kernel scales plane width with the commit stack.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from tendermint_tpu.ops.ed25519_kernel import (
    BX,
    BY,
    D2,
    NLIMBS,
    P,
    SCALAR_BITS,
    _int_to_limbs,
    fe_canon,
    fe_carry,
    fe_mul,
    fe_sub,
    pt_add,
    pt_decompress,
    pt_neg,
)
from tendermint_tpu.ops.ed25519_pallas import (
    _addc_planes,
    _carry_planes,
    _madd_planes,
    _mul_planes,
    _sub_planes,
)
from tendermint_tpu.ops.ed25519_tables import (
    _finish_encode_compare,
    fe_batch_invert,
)

# base-point precomp constants (host python ints -> limb arrays)
_YPX_B = _int_to_limbs((BY + BX) % P)
_YMX_B = _int_to_limbs((BY - BX) % P)
_T2D_B = _int_to_limbs(D2 * BX * BY % P)
_ONE = _int_to_limbs(1)

# widest tile whose working set (4x60-plane table + 80-plane acc +
# out block, int32) stays well inside ~16 MB VMEM: 4096 lanes ->
# (8, 512) planes -> ~4 MB table + ~2.6 MB scratch
MAX_TILE_LANES = 4096
MIN_LANES = 1024  # smallest plane geometry (8, 128)


def use_pallas_ladder(padded_size: int) -> bool:
    """THE routing rule for generic verifies — shared by batch_verify
    and `chip_smoke.py` so they can't drift: pallas ladder iff the padded
    bucket clears the plane geometry and a TPU is the backend."""
    import jax

    return padded_size >= MIN_LANES and jax.default_backend() == "tpu"


def _sq_planes(a):
    return _mul_planes(a, a)


def _double_planes(acc):
    """dbl-2008-hwcd (a=-1) on plane lists — mirrors pt_double exactly."""
    x1, y1, z1, _t1 = acc
    a = _sq_planes(x1)
    b = _sq_planes(y1)
    c = _carry_planes([2 * v for v in _sq_planes(z1)])
    h = _addc_planes(a, b)
    e = _sub_planes(h, _sq_planes(_addc_planes(x1, y1)))
    g = _sub_planes(a, b)
    f = _addc_planes(c, g)
    return (
        _mul_planes(e, f),
        _mul_planes(g, h),
        _mul_planes(f, g),
        _mul_planes(e, h),
    )


def _make_ladder_kernel(w: int):
    from jax.experimental import pallas as pl

    def kernel(gtab_ref, dig_ref, out_ref, acc_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            # extended identity (0, 1, 1, 0): Y limb 0 and Z limb 0 are 1
            rows = jax.lax.broadcasted_iota(jnp.int32, (80, 8, w), 0)
            acc_ref[:] = jnp.where((rows == 20) | (rows == 40), 1, 0)

        acc = tuple(
            [acc_ref[20 * ci + i] for i in range(20)] for ci in range(4)
        )
        acc = _double_planes(acc)

        dig = dig_ref[0, 0]  # (8, w) int32 in {0..3}, this step's selector
        gt = gtab_ref[0]  # (4, 60, 8, w) — this tile's per-lane entries
        masks = [dig == d for d in range(4)]
        ent = []
        for limb in range(60):
            v = jnp.where(masks[0], gt[0, limb], 0)
            for d in range(1, 4):
                v = v + jnp.where(masks[d], gt[d, limb], 0)
            ent.append(v)
        nxt = _madd_planes(acc, ent[:20], ent[20:40], ent[40:])
        acc_ref[:] = jnp.stack([p for coord in nxt for p in coord])

        @pl.when(t == SCALAR_BITS - 1)
        def _():
            out_ref[0] = acc_ref[:]

    return kernel


def _tile_lanes(bsz: int) -> int:
    t = MAX_TILE_LANES
    while bsz % t != 0:
        t //= 2
    if t < MIN_LANES:
        raise ValueError(f"batch {bsz} must be a multiple of {MIN_LANES}")
    return t


def _ladder_pallas(gtab, digits, w, interpret=False):
    """gtab (tiles, 4, 60, 8, w) int32, digits (tiles, 253, 8, w) int32
    -> extended acc coords, each (B, 20) int32 (lane-major)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tiles = gtab.shape[0]
    out = pl.pallas_call(
        _make_ladder_kernel(w),
        grid=(tiles, SCALAR_BITS),
        in_specs=[
            pl.BlockSpec(
                (1, 4, 60, 8, w),
                lambda i, t: (i, 0, 0, 0, 0),  # resident across all steps
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
        out_shape=jax.ShapeDtypeStruct((tiles, 80, 8, w), jnp.int32),
        scratch_shapes=[pltpu.VMEM((80, 8, w), jnp.int32)],
        interpret=interpret,
    )(gtab, digits)
    coords = out.reshape(tiles, 4, 20, 8, w)
    coords = jnp.transpose(coords, (1, 0, 3, 4, 2)).reshape(4, -1, NLIMBS)
    return coords[0], coords[1], coords[2], coords[3]


def _affine_precomp(x, y):
    """Affine (x, y) -> (ypx, ymx, t2d) limbs, each (B, 20) carried."""
    ypx = fe_canon(fe_carry(y + x))
    ymx = fe_canon(fe_sub(y, x))
    t2d = fe_canon(fe_mul(fe_mul(x, y), jnp.asarray(_int_to_limbs(D2))))
    return ypx, ymx, t2d


def _ladder_digits(s_bytes, h_bytes):
    """(B, 32) uint8 LE scalars -> (B, 253) int32 selectors, msb-first:
    column t is s_bit(252-t) + 2*h_bit(252-t) — step t of the ladder
    adds table entry [selector_t] after the doubling."""
    s = s_bytes.astype(jnp.int32)
    h = h_bytes.astype(jnp.int32)
    cols = []
    for t in range(SCALAR_BITS):
        j = SCALAR_BITS - 1 - t
        sb = (s[:, j // 8] >> (j % 8)) & 1
        hb = (h[:, j // 8] >> (j % 8)) & 1
        cols.append(sb + 2 * hb)
    return jnp.stack(cols, axis=-1)


def _build_inputs(pub_bytes, s_bytes, h_bytes, tile):
    """XLA prologue: per-lane precomp tables + selection digits.

    Returns (gtab (tiles, 4, 60, 8, w) int32, dig (tiles, 253, 8, w)
    int32, a_ok (B,) bool). Unjitted-callable so tests can gate the
    ladder algorithm eagerly without tracing 253 unrolled steps."""
    bsz = pub_bytes.shape[0]
    w = tile // 8
    a_pt, a_ok = pt_decompress(pub_bytes)

    # per-lane table entries in affine precomp form
    neg_a = pt_neg(a_pt)  # Z = 1: already affine
    e2 = _affine_precomp(neg_a[0], neg_a[1])
    shape = pub_bytes.shape[:-1] + (NLIMBS,)
    b_pt = (
        jnp.broadcast_to(jnp.asarray(_int_to_limbs(BX)), shape).astype(jnp.int32),
        jnp.broadcast_to(jnp.asarray(_int_to_limbs(BY)), shape).astype(jnp.int32),
        jnp.broadcast_to(jnp.asarray(_ONE), shape).astype(jnp.int32),
        jnp.broadcast_to(
            jnp.asarray(_int_to_limbs(BX * BY % P)), shape
        ).astype(jnp.int32),
    )
    t3 = pt_add(b_pt, neg_a)  # B - A, projective
    zinv = fe_batch_invert(fe_carry(t3[2]))
    e3 = _affine_precomp(fe_mul(t3[0], zinv), fe_mul(t3[1], zinv))
    e1 = tuple(
        jnp.broadcast_to(jnp.asarray(c), shape).astype(jnp.int32)
        for c in (_YPX_B, _YMX_B, _T2D_B)
    )
    zero = jnp.zeros(shape, dtype=jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(_ONE), shape).astype(jnp.int32)
    e0 = (one, one, zero)

    # (4, B, 60) entry-major -> (tiles, 4, 60, 8, w)
    gtab = jnp.stack(
        [jnp.concatenate(e, axis=-1) for e in (e0, e1, e2, e3)]
    )
    tiles = bsz // tile
    gtab = gtab.reshape(4, tiles, 8, w, 60)
    gtab = jnp.transpose(gtab, (1, 0, 4, 2, 3))

    dig = _ladder_digits(s_bytes, h_bytes)  # (B, 253)
    dig = dig.reshape(tiles, 8, w, SCALAR_BITS)
    dig = jnp.transpose(dig, (0, 3, 1, 2))
    return gtab, dig, a_ok


@partial(jax.jit, static_argnames=("interpret",))
def verify_kernel_pallas(pub_bytes, r_bytes, s_bytes, h_bytes, interpret=False):
    """Drop-in for `ed25519_kernel.verify_kernel` (B % 1024 == 0):
    same inputs, same cofactorless [S]B + [h](-A) == R verdicts (via
    byte-compare against the R encoding, so R is never decompressed)."""
    tile = _tile_lanes(pub_bytes.shape[0])
    gtab, dig, a_ok = _build_inputs(pub_bytes, s_bytes, h_bytes, tile)
    x, y, z, _t = _ladder_pallas(gtab, dig, tile // 8, interpret=interpret)
    return _finish_encode_compare(x, y, z, r_bytes.astype(jnp.int32)) & a_ok
