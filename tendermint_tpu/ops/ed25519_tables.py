"""Table-driven batched ed25519 verification: the steady-state fast path.

The round-1 kernel (`ed25519_kernel.verify_kernel`) runs a generic
253-step Shamir ladder per signature (~4.8k field muls). But consensus,
fast-sync and the light client verify commits signed by a KNOWN validator
set that changes rarely (reference hot loops: `types/validator_set.go:
236-261`, `types/vote_set.go:137-196`; SURVEY.md §7 hard part 4 calls for
pre-staged validator-set device arrays cached by valset hash). This module
exploits that:

* **[S]B — fixed-base comb.** B is a compile-time constant: a w=8 table
  (32 windows x 256 entries = 8192 precomputed points, ~2.6 MB) turns
  [S]B into 32 mixed adds with zero doublings.
* **[h]A — cached per-validator window tables.** A per-validator w=4
  table (64 windows x 16 entries) is built ON DEVICE once per validator
  set, then every verification of that validator is 64 mixed adds with
  zero doublings and no point decompression.
* **No R decompression.** Like the reference's verifier (Go ed25519
  computes R' = [S]B - [h]A and byte-compares with sig[:32]), we encode
  the computed point and compare bytes. Affine normalization uses a
  log-depth batched tree inversion (~3 muls/signature amortized instead
  of ~265 for a per-lane inversion).
* Mixed additions use precomputed affine entries (y+x, y-x, 2d*x*y):
  7 field muls each (madd-2008-hwcd-3, a=-1) vs 9 for the unified add.

Net: ~0.7k field muls/signature vs ~4.8k for the generic ladder, with
identical per-signature verdict semantics (bad signatures localize).

Tables hold multiples of -A so the device accumulates
[S]B + [h](-A) and checks its encoding equals sig[:32].

Two device pipelines share these tables:

* the FUSED pallas kernel (production, TPU): selection happens inside
  the kernel from int16 table blocks, the accumulator lives in VMEM,
  and the table streams from HBM exactly once per launch — see
  `ed25519_pallas.py`, where every `pallas_call` of this path lives
  (rates on v5e: not measured; chip_smoke.py checks bits, not speed);
* the materialized-entries path (XLA scan or the earlier pallas madd
  chain): portable, used for shapes that don't tile the fused kernel
  (single commits, tiny valsets) and by the CPU test mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tendermint_tpu.ops.ed25519_kernel import (
    BX,
    BY,
    D,
    D2,
    NLIMBS,
    P,
    _D2_L,
    _ONE_L,
    _int_to_limbs,
    fe_canon,
    fe_carry,
    fe_invert,
    fe_mul,
    fe_sub,
    fe_to_bytes,
    pt_add,
    pt_decompress,
    pt_double,
    pt_neg,
)

A_WINDOW = 4  # per-validator tables: 64 windows x 16 entries
A_NWIN = 64
B_NWIN = 32  # fixed-base table: 32 windows x 256 entries (w=8, XLA path)
SB_NWIN = 64  # fixed-base table: 64 windows x 16 entries (w=4, fused path)


# -- host EC over Python ints (B-table build + tests) -------------------------


def _hadd(p, q):
    """Extended twisted-Edwards add (a=-1), Python ints."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = t1 * D2 % P * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


_H_IDENT = (0, 1, 1, 0)
_B_EXT = (BX, BY, 1, BX * BY % P)


def host_scalar_mul(k: int, p) -> tuple[int, int, int, int]:
    """[k]P by double-and-add over Python ints (tests / cross-checks)."""
    acc = _H_IDENT
    while k:
        if k & 1:
            acc = _hadd(acc, p)
        p = _hadd(p, p)
        k >>= 1
    return acc


def host_affine(p) -> tuple[int, int]:
    x, y, z, _ = p
    zi = pow(z, P - 2, P)
    return (x * zi % P, y * zi % P)


def _precomp_limbs(x: int, y: int) -> np.ndarray:
    """Affine point -> (3, 20) int32 precomp form (y+x, y-x, 2d*x*y)."""
    return np.stack(
        [
            _int_to_limbs((y + x) % P),
            _int_to_limbs((y - x) % P),
            _int_to_limbs(2 * D * x % P * y % P),
        ]
    )


def _host_decompress(pub: bytes) -> tuple[int, int] | None:
    """RFC 8032 point decoding over Python ints (host build path)."""
    from tendermint_tpu.ops.ed25519_kernel import SQRT_M1

    enc = int.from_bytes(pub, "little")
    sign = enc >> 255
    y = enc & ((1 << 255) - 1)
    if y >= P:
        return None
    y2 = y * y % P
    u = (y2 - 1) % P
    v = (D * y2 + 1) % P
    x = u * pow(v, 3, P) % P * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    if v * x * x % P != u:
        if v * x * x % P == (P - u) % P:
            x = x * SQRT_M1 % P
        else:
            return None
    if x == 0 and sign:
        return None
    if (x & 1) != sign:
        x = P - x
    return x, y


def host_build_key_tables(pubkeys) -> tuple[np.ndarray, np.ndarray]:
    """Python-int table build: same layout as build_key_tables
    ((64, 16, 60, N) int16 window/digit/limb/validator tables of -A
    multiples, (N,) ok) without compiling the device build kernel.
    Intended for small N (tests, the multichip dryrun); one Montgomery
    batched inversion per key normalizes all 960 entries.

    Invalid pubkey encodings get identity-entry columns and ok=False.
    An identity column degrades the check to encode([S]B) == R, which an
    attacker CAN satisfy — callers must AND key_ok into every verdict
    (the service layer and sharded step's lane_ok input both do)."""
    n = len(pubkeys)
    ok = np.zeros(n, dtype=bool)
    tbl = np.zeros((A_NWIN, 16, 3 * NLIMBS, n), dtype=np.int16)
    ident_entry = _precomp_limbs(0, 1).reshape(-1)
    for col, pk in enumerate(pubkeys):
        aff = _host_decompress(bytes(pk)) if len(pk) == 32 else None
        if aff is None:
            tbl[:, :, :, col] = ident_entry[None, None, :]
            continue
        ok[col] = True
        x, y = aff
        nx = (P - x) % P  # tables hold multiples of -A
        base = (nx, y, 1, nx * y % P)
        rows: list[tuple[int, int]] = []  # (window, digit) per entry
        entries: list[tuple[int, int, int, int]] = []
        for w in range(A_NWIN):
            e = _H_IDENT
            for d in range(16):
                if d == 0:
                    tbl[w, 0, :, col] = ident_entry
                else:
                    rows.append((w, d))
                    entries.append(e)
                e = _hadd(e, base)
            for _ in range(A_WINDOW):
                base = _hadd(base, base)
        # batched affine normalization (Montgomery trick): 1 modexp/key
        prefix = [1]
        for pt in entries:
            prefix.append(prefix[-1] * pt[2] % P)
        inv = pow(prefix[-1], P - 2, P)
        for i in reversed(range(len(entries))):
            zi = inv * prefix[i] % P
            inv = inv * entries[i][2] % P
            ex, ey = entries[i][0] * zi % P, entries[i][1] * zi % P
            w, d = rows[i]
            tbl[w, d, :, col] = _precomp_limbs(ex, ey).reshape(-1)
    return tbl, ok


_SB_TABLE: np.ndarray | None = None


def sb_table_w4() -> np.ndarray:
    """w=4 fixed-base comb: (64, 16, 60) int32; [w, j] holds
    j * 2^(4w) * B in affine precomp form (ypx|ymx|t2d). Used by the
    fused pallas path, whose per-step selection is a 16-way masked sum —
    w=4 for BOTH scalars makes every one of its 128 steps identical
    (the XLA path keeps the w=8 b_table and 96 steps instead)."""
    global _SB_TABLE
    if _SB_TABLE is not None:
        return _SB_TABLE
    entries = []
    base = _B_EXT
    for _ in range(SB_NWIN):
        e = _H_IDENT
        for _j in range(16):
            entries.append(e)
            e = _hadd(e, base)
        for _ in range(4):
            base = _hadd(base, base)
    prefix = [1]
    for pt in entries:
        prefix.append(prefix[-1] * pt[2] % P)
    inv = pow(prefix[-1], P - 2, P)
    out = np.zeros((len(entries), 3 * NLIMBS), dtype=np.int32)
    for i in reversed(range(len(entries))):
        zi = inv * prefix[i] % P
        inv = inv * entries[i][2] % P
        x, y = entries[i][0] * zi % P, entries[i][1] * zi % P
        out[i] = _precomp_limbs(x, y).reshape(-1)
    _SB_TABLE = out.reshape(SB_NWIN, 16, 3 * NLIMBS)
    return _SB_TABLE


_B_TABLE: np.ndarray | None = None


def b_table() -> np.ndarray:
    """Fixed-base table: (B_NWIN*256, 3, 20) int32; entry [w*256+j] holds
    j * 2^(8w) * B in affine precomp form. Built lazily once per process
    (~8k host point adds + one Montgomery batched inversion)."""
    global _B_TABLE
    if _B_TABLE is not None:
        return _B_TABLE
    entries = []  # extended points, Python ints
    base = _B_EXT
    for _ in range(B_NWIN):
        e = _H_IDENT
        for _j in range(256):
            entries.append(e)
            e = _hadd(e, base)
        for _ in range(8):
            base = _hadd(base, base)
    # batched affine normalization (Montgomery trick)
    zs = [p[2] for p in entries]
    prefix = [1]
    for z in zs:
        prefix.append(prefix[-1] * z % P)
    inv = pow(prefix[-1], P - 2, P)
    out = np.zeros((len(entries), 3, NLIMBS), dtype=np.int32)
    for i in reversed(range(len(entries))):
        zi = inv * prefix[i] % P
        inv = inv * zs[i] % P
        x, y = entries[i][0] * zi % P, entries[i][1] * zi % P
        out[i] = _precomp_limbs(x, y)
    _B_TABLE = out
    return out


# -- device primitives --------------------------------------------------------


def pt_madd(acc, entry):
    """Mixed add: extended acc + affine precomp entry (ypx, ymx, t2d).

    madd-2008-hwcd-3 with a=-1 and Z2=1: 7 muls. Entry limbs are
    canonical (< 2^13), acc limbs loose — both satisfy fe_mul's bound.
    """
    x1, y1, z1, t1 = acc
    ypx, ymx, t2d = entry
    a = fe_mul(fe_sub(y1, x1), ymx)
    b = fe_mul(fe_carry(y1 + x1), ypx)
    c = fe_mul(t1, t2d)
    d = fe_carry(z1 + z1)
    e = fe_sub(b, a)
    f = fe_sub(d, c)
    g = fe_carry(d + c)
    h = fe_carry(b + a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def fe_batch_invert(z):
    """Invert every row of z (M, 20) via a log-depth product tree:
    ~3 muls per element + ONE fe_invert total (vs ~265 muls per element
    for per-lane inversion). Non-power-of-two M is padded with ones (a
    fixed point of inversion) so the tree halves evenly. Zero inputs are
    the caller's responsibility (Z of a valid point is never 0)."""
    m = z.shape[0]
    padded = 1
    while padded < m:
        padded *= 2
    if padded != m:
        ones = jnp.broadcast_to(
            jnp.asarray(_ONE_L), (padded - m, NLIMBS)
        ).astype(z.dtype)
        z = jnp.concatenate([z, ones], axis=0)
    levels = []
    cur = z
    while cur.shape[0] > 1:
        levels.append(cur)
        cur = fe_mul(cur[0::2], cur[1::2])
    inv = fe_invert(cur)
    for lev in reversed(levels):
        left, right = lev[0::2], lev[1::2]
        inv_left = fe_mul(inv, right)
        inv_right = fe_mul(inv, left)
        inv = jnp.stack([inv_left, inv_right], axis=1).reshape(lev.shape)
    return inv[:m]


def _identity_like(ref):
    """Extended identity (0,1,1,0) built FROM an input array so the scan
    carry is device-varying under shard_map (same trick as the generic
    kernel's ladder)."""
    vzero = (ref[..., :1] * 0).astype(jnp.int32)
    zero = vzero + jnp.zeros(NLIMBS, dtype=jnp.int32)
    one = vzero + jnp.asarray(_ONE_L)
    return (zero, one, one, zero)


# encoding of the identity point (y=1, x=0): decompresses cleanly, used
# as padding so padded build lanes stay on-curve
_IDENT_PUB = np.zeros((1, 32), dtype=np.uint8)
_IDENT_PUB[0, 0] = 1


# -- table build (device) -----------------------------------------------------


@jax.jit
def _build_tables_kernel(pub_bytes):
    """(N, 32) uint8 pubkeys -> ((1024, N, 60) int32 tables, (N,) ok).

    Window-major layout: row (window*16 + digit) column n holds
    digit * 2^(4*window) * (-A_n) in affine precomp form (ypx|ymx|t2d
    flattened to 60 limbs). Window-major keeps per-window slices on the
    MAJOR axis so the gather-free selection pass never forces a padded
    transpose of the whole table (minor dims of 16 tile to 128 and would
    8x the table's footprint). N must be a power of two (callers pad) so
    the entry count feeds the inversion tree exactly.
    """
    a_pt, ok = pt_decompress(pub_bytes)
    w0 = pt_neg(a_pt)  # tables hold multiples of -A

    def outer(w, _):
        def add_step(e, _x):
            e2 = pt_add(e, w)
            return e2, e2

        ident = _identity_like(w[0])
        _, steps = lax.scan(add_step, ident, None, length=15)
        # entries: identity + the 15 partial sums -> (16, N, 20) per coord
        entries = tuple(
            jnp.concatenate([iv[None], st], axis=0)
            for iv, st in zip(ident, steps)
        )
        nxt = w
        for _i in range(A_WINDOW):
            nxt = pt_double(nxt)
        return nxt, entries

    _, ent = lax.scan(outer, w0, None, length=A_NWIN)
    # ent: 4 arrays of (64, 16, N, 20) -> flatten the entry dimension
    ex, ey, ez, _et = (e.reshape(-1, NLIMBS) for e in ent)
    zinv = fe_batch_invert(fe_carry(ez))
    ax = fe_mul(ex, zinv)
    ay = fe_mul(ey, zinv)
    ypx = fe_canon(fe_carry(ay + ax))
    ymx = fe_canon(fe_sub(ay, ax))
    t2d = fe_canon(fe_mul(fe_mul(ax, ay), jnp.asarray(_D2_L)))
    n = pub_bytes.shape[0]
    # (64*16*N, 20) each, in (window, digit, val) order -> (1024, N, 60)
    tbl = jnp.stack([ypx, ymx, t2d], axis=-2).reshape(
        A_NWIN * 16, n, 3 * NLIMBS
    )
    return tbl, ok


@jax.jit
def _to_fused_layout(tbl):
    """(1024, M, 60) int32 -> (64, 16, 60, M) int16 canonical table form.

    Canonical entry limbs are in [0, 2^13) so int16 is lossless; halving
    the bytes halves the fused kernel's dominant HBM stream (the table
    is read once per verify launch)."""
    m = tbl.shape[1]
    return jnp.transpose(
        tbl.reshape(A_NWIN, 16, m, 3 * NLIMBS), (0, 1, 3, 2)
    ).astype(jnp.int16)


def build_key_tables(pub_bytes: np.ndarray, chunk: int = 2048):
    """Build per-validator window tables on device, chunked to bound peak
    memory (each chunk materializes chunk*1024 extended points).

    pub_bytes: (N, 32) uint8. Returns (tables (64, 16, 60, N) int16 on
    device — window, digit, limb, validator — and ok (N,) bool on host).

    On TPU every chunk pads to the FULL chunk size so all builds of any
    N share ONE compiled executable — a fresh pow2 shape would pay its
    own compile, while the pad columns cost only device work. Off-TPU
    (tests) the pad stays at the next power of two."""
    n = pub_bytes.shape[0]
    on_tpu = jax.default_backend() == "tpu"
    tbls, oks = [], []
    for lo in range(0, n, chunk):
        part = np.asarray(pub_bytes[lo : lo + chunk], dtype=np.uint8)
        m = part.shape[0]
        if on_tpu:
            padded = chunk
        else:
            padded = 1
            while padded < m:
                padded *= 2
        if padded != m:
            part = np.concatenate(
                [part, np.tile(_IDENT_PUB, (padded - m, 1))], axis=0
            )
        t, ok = _build_tables_kernel(jnp.asarray(part))
        # layout-convert at the padded shape, slice after: slicing first
        # would give _to_fused_layout a fresh executable per residual m
        tbls.append(_to_fused_layout(t)[..., :m])
        oks.append(np.asarray(ok)[:m])
    return jnp.concatenate(tbls, axis=3), np.concatenate(oks)


# -- verification (device) ----------------------------------------------------
#
# TPU gathers are slow (measured ~60x the cost of the arithmetic they
# feed), so table entries are selected WITHOUT gathers: one-hot f32
# matmuls ride the MXU (table limbs < 2^13 and one-hot rows have a single
# nonzero, so f32 accumulation is exact). The 96 sequential mixed adds
# then run either as an XLA scan (portable; CPU tests) or as a Pallas
# kernel that keeps the accumulator in VMEM across all steps (TPU fast
# path — XLA's scan materializes the carry through HBM every step).
#
# `jax.named_scope` names the XLA stages in the profiler's trace
# (`ed25519.select_entries/while` where it said `while.249`). Never put
# one around a `pallas_call`: the name stack is in a Mosaic kernel's
# serialized body, and that body is in the executable's compile-cache
# key. (The table build and layout need no scope: each is an executable
# of its own, named in the trace's `XLA Modules` line.)

NSTEPS = B_NWIN + A_NWIN  # 96 mixed adds per signature


@jax.named_scope("ed25519.select_entries")
def _select_entries(a_tables, s, h):
    """Gather-free operand selection -> (NSTEPS, B, 60) int32.

    a_tables: (64, 16, 60, N) canonical form (converted to the
    window-major (1024, N, 60) int32 this path indexes); lane b uses
    table column (b mod N), so one validator set verifies K stacked
    commits with B = K*N lanes. Selection is 16 fused mask-multiplies
    per window — the whole table streams through the VPU exactly once
    (a true gather would be ~60x slower on TPU, measured).
    """
    bsz = s.shape[0]
    n_vals = a_tables.shape[3]
    reps = bsz // n_vals
    btab = jnp.asarray(b_table()).reshape(B_NWIN, 256, 60).astype(jnp.float32)
    outs = []
    for w in range(B_NWIN):
        oh = (s[:, w : w + 1] == jnp.arange(256)[None, :]).astype(jnp.float32)
        # precision=HIGHEST: TPU matmuls default to bf16 operand passes,
        # which truncates 13-bit table limbs and corrupts every entry
        # (one-hot selection needs the full f32 mantissa, which HIGHEST's
        # multi-pass f32 guarantees; accumulation of a single nonzero
        # term is then exact).
        outs.append(
            jnp.dot(
                oh,
                btab[w],
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST,
            ).astype(jnp.int32)
        )
    for w in range(A_NWIN):
        byte = h[:, w // 2]
        digit = (byte >> (4 * (w % 2))) & 0xF
        acc = None
        for d in range(16):
            # per-slice transpose+convert of the canonical int16 layout:
            # fuses into the consumer as strided reads — materializing a
            # whole int32 copy of the table cost ~33 ms at N=10k
            twd = jnp.transpose(a_tables[w, d]).astype(jnp.int32)  # (N, 60)
            if reps != 1:
                twd = jnp.broadcast_to(twd[None], (reps, n_vals, 60)).reshape(
                    bsz, 60
                )
            term = jnp.where((digit == d)[:, None], twd, 0)
            acc = term if acc is None else acc + term
        outs.append(acc)
    return jnp.stack(outs, axis=0)


@jax.named_scope("ed25519.madd_chain")
def _sum_entries_xla(ent):
    """Portable scan over the NSTEPS mixed adds; ent (NSTEPS, B, 60)."""
    acc = _identity_like(ent[0, :, :1])

    def step(a, e):
        e3 = e.reshape(e.shape[0], 3, NLIMBS)
        return pt_madd(a, (e3[:, 0], e3[:, 1], e3[:, 2])), None

    acc, _ = lax.scan(step, acc, ent)
    return acc


# ---- the fused path's geometry ------------------------------------------------
#
# The materialized-entries pipeline above streams a (96, B, 60) int32
# array through HBM twice (write at selection, read at accumulation) —
# 7.6 GB of traffic at K=16 x 10,240 validators. The fused kernel
# (`ed25519_pallas._fused_chain_pallas`) removes that array entirely:
# each grid step selects its operands INSIDE the kernel from the (int16,
# read-once) table block. To make every step's selection the same cheap
# 16-way masked sum, the S comb uses a w=4 fixed-base table too: 128
# identical steps (64 S windows + 64 h windows) instead of 96 asymmetric
# ones.
#
# One grid tile covers V_TILE=128 validators x ALL K stacked commits.
# 128 is the smallest validator block pallas can address on the table's
# minor axis, which maximizes how much stacking a given VMEM budget
# allows.

V_TILE = 128
MAX_FUSED_STACK = 64  # VMEM: acc+ent scratch = 140 * (8, 16K) planes


@jax.named_scope("ed25519.fused_digits")
def _digits_w4(s, h):
    """(B, 32) int32 byte arrays -> (B, 128) int32 nibble-per-step:
    steps 0..63 the S comb's, 64..127 the h comb's."""
    cols = []
    for i in range(SB_NWIN):
        cols.append((s[:, i // 2] >> (4 * (i % 2))) & 0xF)
    for i in range(SB_NWIN):
        cols.append((h[:, i // 2] >> (4 * (i % 2))) & 0xF)
    return jnp.stack(cols, axis=-1)


def _to_kernel_order(x, n_vals, v_tile, c_tile):
    """Commit-major lanes (lane = c*N + v) -> fused-tile order: tile vb
    holds its 128 validators' lanes COMMIT-major (lane = c*128 + v
    within the tile), so a table column broadcasts to lane planes as a
    native row-splat; pure reshape/transpose."""
    b = x.shape[0]
    k = b // n_vals
    y = x.reshape((k, n_vals // v_tile, v_tile) + x.shape[1:])
    y = jnp.transpose(y, (1, 0, 2) + tuple(range(3, y.ndim)))
    return y.reshape((b,) + x.shape[1:])


def _from_kernel_order(x, n_vals, v_tile, c_tile):
    """Inverse of _to_kernel_order."""
    b = x.shape[0]
    k = b // n_vals
    y = x.reshape((n_vals // v_tile, k, v_tile) + x.shape[1:])
    y = jnp.transpose(y, (1, 0, 2) + tuple(range(3, y.ndim)))
    return y.reshape((b,) + x.shape[1:])


def _fused_tile_geometry(bsz: int, n_vals: int):
    """(v_tile, c_tile) for the fused kernel, or None if the shape won't
    tile. One tile = 128 validators x all K commits; the lane planes are
    (8, 128*K/8), whose minor dim must stay a multiple of 128 — hence
    K % 8 == 0 — and whose VMEM scratch bounds K at MAX_FUSED_STACK."""
    k = bsz // n_vals
    if n_vals % V_TILE == 0 and k % 8 == 0 and 8 <= k <= MAX_FUSED_STACK:
        return V_TILE, k
    return None


@partial(jax.jit, static_argnames=("impl",))
def verify_tables_kernel(a_tables, s_bytes, h_bytes, r_bytes, impl="auto"):
    """Batched verify against cached tables.

    a_tables: (64, 16, 60, N) int16 from build_key_tables.
    s_bytes:  (B, 32) uint8, S little-endian (host-checked < L).
    h_bytes:  (B, 32) uint8, SHA512(R||A||M) mod L little-endian.
    r_bytes:  (B, 32) uint8, the signature's R encoding (sig[:32]).

    Lane b verifies against validator row (b mod N) — one commit is
    B == N lanes in validator order; fast-sync stacks K commits of the
    same valset as B = K*N. Returns (B,) bool:
    encode([S]B + [h](-A)) == r_bytes, the same cofactorless
    byte-compare the reference's ed25519 performs. B must be a multiple
    of N.

    impl: "auto" picks the fused select+accumulate pallas kernel on TPU
    whenever the (K, N) shape tiles (see _fused_tile_geometry), falling
    back to the materialized-entries XLA scan elsewhere; "fused" forces
    the fused kernel (interpreted off-TPU — slow, test-only); "pallas"
    forces the materialized-entries pallas chain; "xla" the portable
    scan.
    """
    # pallas takes a second to import, and only a trace needs it
    from tendermint_tpu.ops.ed25519_pallas import (
        _LANES,
        _fused_chain_pallas,
        _sum_entries_pallas,
    )

    s = s_bytes.astype(jnp.int32)
    h = h_bytes.astype(jnp.int32)
    r = r_bytes.astype(jnp.int32)
    bsz = s.shape[0]
    n_vals = a_tables.shape[3]
    on_tpu = jax.default_backend() == "tpu"
    geom = _fused_tile_geometry(bsz, n_vals)

    if impl == "fused" and geom is None:
        raise ValueError(
            f"impl='fused' but shape (B={bsz}, N={n_vals}) does not tile: "
            f"needs N % {V_TILE} == 0 and K % 8 == 0, 8 <= K <= "
            f"{MAX_FUSED_STACK}"
        )
    if geom is not None and (impl == "fused" or (impl == "auto" and on_tpu)):
        v_tile, c_tile = geom
        digits = _to_kernel_order(_digits_w4(s, h), n_vals, v_tile, c_tile)
        x, y, z, _t = _fused_chain_pallas(
            jnp.asarray(sb_table_w4()),
            a_tables,
            digits,
            v_tile,
            c_tile,
            interpret=not on_tpu,
        )
        r = _to_kernel_order(r, n_vals, v_tile, c_tile)
        verdict = _finish_encode_compare(x, y, z, r)
        return _from_kernel_order(verdict, n_vals, v_tile, c_tile)

    ent = _select_entries(a_tables, s, h)
    use_pallas = impl == "pallas" or (impl == "auto" and on_tpu)
    if use_pallas:
        if bsz % _LANES != 0:
            # pad lanes with the identity precomp entry (ypx=1, ymx=1,
            # t2d=0 — the affine point (0,1)); madd with it keeps the
            # accumulator on the same projective point with Z != 0, so
            # padded lanes are harmless through the batched inversion.
            pad = _LANES - bsz % _LANES
            ident = jnp.zeros((NSTEPS, pad, 3, NLIMBS), dtype=jnp.int32)
            ident = ident.at[:, :, 0, 0].set(1).at[:, :, 1, 0].set(1)
            ent = jnp.concatenate(
                [ent, ident.reshape(NSTEPS, pad, 3 * NLIMBS)], axis=1
            )
        x, y, z, _t = _sum_entries_pallas(ent)
        x, y, z = x[:bsz], y[:bsz], z[:bsz]
    else:
        x, y, z, _t = _sum_entries_xla(ent)
    return _finish_encode_compare(x, y, z, r)


@jax.named_scope("ed25519.tally")
def _finish_encode_compare(x, y, z, r):
    """Affine-normalize via one tree inversion, encode y, compare to R."""
    zinv = fe_batch_invert(fe_carry(z))
    x_aff = fe_canon(fe_mul(x, zinv))
    y_bytes = fe_to_bytes(fe_mul(y, zinv))
    parity = x_aff[..., 0] & 1
    sign = (r[..., 31] >> 7) & 1
    r_clean = r.at[..., 31].set(r[..., 31] & 0x7F)
    return jnp.all(y_bytes == r_clean, axis=-1) & (parity == sign)


# -- host-side lane prep ------------------------------------------------------


def prepare_commit_lanes(pubkeys, commits):
    """Host prep for K stacked commits over one N-validator set.

    pubkeys: N 32-byte pubkey encodings in validator order.
    commits: K pairs (msgs, sigs) — each a length-N sequence aligned to
    validator index, with None marking absent votes.

    Returns (s, h, r) uint8 arrays of shape (K*N, 32) and a (K*N,) bool
    precheck mask (False for absent lanes and host-detected malformed
    signatures: wrong length or non-canonical S >= L, the same strict-S
    rule as `ed25519_kernel.prepare_batch`). Lane k*N+i aligns with
    `verify_tables_kernel`'s b-mod-N column mapping.
    """
    import hashlib

    from tendermint_tpu.ops.ed25519_kernel import L as _L

    n = len(pubkeys)
    k = len(commits)
    s = np.zeros((k * n, 32), dtype=np.uint8)
    h = np.zeros((k * n, 32), dtype=np.uint8)
    r = np.zeros((k * n, 32), dtype=np.uint8)
    precheck = np.zeros(k * n, dtype=bool)
    # hot loop kept lean (10k+ lanes on the single-commit latency path):
    # one bytes-join + frombuffer per commit instead of per-lane array
    # writes, hashlib only on present lanes
    zero64 = b"\x00" * 64
    from_bytes = int.from_bytes
    sha512 = hashlib.sha512
    for ci, (msgs, sigs) in enumerate(commits):
        if len(msgs) != n or len(sigs) != n:
            raise ValueError(f"commit {ci}: expected {n} lanes")
        lanes = precheck[ci * n : (ci + 1) * n]
        hrows = []
        sig_blob = []
        for i in range(n):
            msg, sig = msgs[i], sigs[i]
            if (
                msg is None
                or sig is None
                or len(sig) != 64
                or len(pubkeys[i]) != 32
                or from_bytes(sig[32:], "little") >= _L
            ):
                sig_blob.append(zero64)
                continue
            lanes[i] = True
            sig_blob.append(sig)
            hh = sha512(sig[:32] + pubkeys[i] + msg).digest()
            hrows.append(
                (i, (from_bytes(hh, "little") % _L).to_bytes(32, "little"))
            )
        sig_arr = np.frombuffer(b"".join(sig_blob), dtype=np.uint8).reshape(n, 64)
        r[ci * n : (ci + 1) * n] = sig_arr[:, :32]
        s[ci * n : (ci + 1) * n] = sig_arr[:, 32:]
        if hrows:
            idx, blobs = zip(*hrows)
            h[ci * n + np.asarray(idx, dtype=np.intp)] = np.frombuffer(
                b"".join(blobs), dtype=np.uint8
            ).reshape(len(blobs), 32)
    return s, h, r, precheck
