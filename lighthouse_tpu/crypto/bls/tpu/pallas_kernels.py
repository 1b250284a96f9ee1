"""Pallas TPU kernels for the pairing hot path (optional fast path).

The XLA formulation in limbs.py/tower.py/pairing.py (Toeplitz gather +
dot_general + einsum folds) stays the default; this module provides the
same math as FUSED Pallas kernels -- product columns, carry rounds and
modular folds execute in a single VMEM residency per block instead of
XLA-scheduled HLO ops, which is the classic fusion win when HBM
bandwidth, not FLOPs, bounds the op.

Kernel inventory (all opt-in via LIGHTHOUSE_TPU_PALLAS=1):

  fp_mul               fused Fp multiply (limbs.mul switches over)
  fp_sq                fused Fp SQUARE: half the partial products
                       (limbs.sq switches over)
  fp6_mul / fp12_mul   fused tower multiplies (tower.py switches over)
  fp12_cyclotomic_sq   fused Granger-Scott square (the _pow_x_abs body)
  miller_dbl_step      fused Miller doubling: Jacobian dbl-2009-l + the
                       tangent line + f^2 + the sparse mul_by_line
                       update, one kernel per scan step
  miller_add_step      fused Miller addition: madd-2007-bl + chord line
                       + sparse mul_by_line

The CPU backend runs every kernel in interpreter mode, which the
differential tests use to pin bit-exactness against the XLA path; any
backend that is neither the CPU nor a TPU is refused (`_interpret`).

BIT-IDENTITY CONTRACT: the in-kernel field library below (`_k*` helpers)
transcribes the EXACT formula and reduction schedule of the lax path --
same column sums, same carry3 rounds, same fold matrix (threaded through
as an explicit kernel operand: Pallas requires captured constants to be
operands), same truncation. Every kernel output is bit-identical to the
corresponding limbs/tower/pairing composition; tests/test_pallas_*
asserts this on seeded matrices including all-limbs-maximal inputs.

The in-kernel helpers deliberately do NOT call limbs.mul/limbs.sq or any
tower.py function: under the env flag those are rebound to the Pallas
entry points themselves, and a pallas_call nested inside a kernel body is
illegal. Only the constant-free limbs reduction helpers (carry3) are
shared.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import limbs as L

W = L.W
BLOCK_ROWS = 256  # batch rows per VMEM block (256x35 int32 ~ 35 KB/operand)
# Fused tower/Miller kernels hold a full Fp12 working set per row; keep
# their blocks small so intermediates fit the 16 MiB of scoped VMEM (at
# 32 rows the v5e compiler refuses miller_add: 18.49 MiB on its stack).
FUSED_BLOCK_ROWS = 16


# --- in-kernel Fp library (mirrors limbs.py bit-for-bit) --------------------


def _fold_round(x, fold_r):
    """limbs._fold_round with the constant matrix passed as a kernel
    input (Pallas requires captured constants to be explicit operands).
    The contraction is a static unroll of VPU multiply-adds, one per
    high limb: v5e's MXU has no int32 matmul, so Mosaic refuses the
    einsum. Integer sums are exact, so the result is bit-identical."""
    lo = x[..., : L.NLIMBS]
    acc = lo
    for j in range(x.shape[-1] - L.NLIMBS):
        acc = acc + x[..., L.NLIMBS + j : L.NLIMBS + j + 1] * fold_r[
            j : j + 1, : L.NLIMBS
        ]
    return L.carry3(acc)


def _k_reduce(cols, fold_r):
    """limbs.reduce_columns: carry3 + two folds + truncate."""
    x = L.carry3(cols)
    x = _fold_round(x, fold_r)
    x = _fold_round(x, fold_r)
    return x[..., :W]


def _k_norm(x, fold_r):
    """limbs._norm: carry3 + one fold + truncate."""
    x = L.carry3(x)
    x = _fold_round(x, fold_r)
    return x[..., :W]


def _k_add(a, b, fold_r):
    return _fold_round(a + b, fold_r)


def _k_sub(a, b, fold_r):
    return _fold_round(a - b, fold_r)


def _k_neg(a, fold_r):
    return _fold_round(-a, fold_r)


def _k_lincomb(terms, fold_r):
    """limbs.lincomb: sum(k_i * a_i), one normalization, sum|k_i| <= 64."""
    acc = None
    total = 0
    for a, k in terms:
        total += abs(k)
        t = a * jnp.int32(k)
        acc = t if acc is None else acc + t
    assert total <= 64
    return _k_norm(acc, fold_r)


def _shift_cols(x, lo: int, width: int):
    """Place `x` at column offset `lo` of a zero row `width` wide: the
    shifted add of a schoolbook column sum, built from concatenate
    because Mosaic has no scatter-add."""
    parts = []
    if lo:
        parts.append(jnp.zeros(x.shape[:-1] + (lo,), x.dtype))
    parts.append(x)
    hi = width - lo - x.shape[-1]
    if hi:
        parts.append(jnp.zeros(x.shape[:-1] + (hi,), x.dtype))
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else x


def _k_mul_cols(a, b):
    """Schoolbook product columns: same integer column sums as
    limbs.mul_columns (the Toeplitz gather), as a static unroll of W
    shifted multiply-adds on the VPU."""
    a, b = jnp.broadcast_arrays(a, b)
    cols = None
    for i in range(W):
        t = _shift_cols(a[..., i : i + 1] * b, i, 2 * W - 1)
        cols = t if cols is None else cols + t
    return cols


def _k_sq_cols(a):
    """Squaring columns with HALF the partial products: one diagonal
    product plus doubled off-diagonal products per limb. Column sums are
    the exact integers of the generic a*a schoolbook (2 a_i a_j =
    a_i a_j + a_j a_i), so the reduced result is bit-identical to
    limbs.mul(a, a); per-entry intermediates stay < 2^25 << int32."""
    cols = None
    for i in range(W):
        # row i covers columns 2i .. i+W-1: a_i^2, then 2 a_i a_j (j > i)
        row = a[..., i : i + 1] * a[..., i:]
        if i + 1 < W:
            row = row + jnp.concatenate(
                [jnp.zeros_like(row[..., :1]), row[..., 1:]], axis=-1
            )
        t = _shift_cols(row, 2 * i, 2 * W - 1)
        cols = t if cols is None else cols + t
    return cols


def _k_mul(a, b, fold_r):
    """limbs.mul: columns + the full reduction."""
    return _k_reduce(_k_mul_cols(a, b), fold_r)


# --- in-kernel Fp2 (mirrors tower.py bit-for-bit) ---------------------------
# Layout (..., 2, W), exactly as on the host side.


def _k2_mul(a, b, fold_r):
    """tower.fp2_mul: Karatsuba with column-domain sharing, TWO shared
    reductions."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    b0, b1 = b[..., 0, :], b[..., 1, :]
    t0c = _k_mul_cols(a0, b0)
    t1c = _k_mul_cols(a1, b1)
    tkc = _k_mul_cols(_k_add(a0, a1, fold_r), _k_add(b0, b1, fold_r))
    c0 = _k_reduce(t0c - t1c, fold_r)
    c1 = _k_reduce(tkc - t0c - t1c, fold_r)
    return jnp.stack([c0, c1], axis=-2)


def _k2_sq(a, fold_r):
    """tower.fp2_sq: (a0+a1)(a0-a1) + 2 a0 a1 u."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    tc = _k_mul_cols(a0, a1)
    c0 = _k_reduce(
        _k_mul_cols(_k_add(a0, a1, fold_r), _k_sub(a0, a1, fold_r)), fold_r
    )
    return jnp.stack([c0, _k_reduce(tc + tc, fold_r)], axis=-2)


def _k2_mul_by_xi(a, fold_r):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return jnp.stack([_k_sub(a0, a1, fold_r), _k_add(a0, a1, fold_r)], axis=-2)


def _k2_mul_small(a, k, fold_r):
    assert abs(k) <= 64
    return _k_norm(a * jnp.int32(k), fold_r)


def _k2_mul_fp(a, s, fold_r):
    """tower.fp2_mul_fp: two plain Fp multiplies."""
    return jnp.stack(
        [_k_mul(a[..., 0, :], s, fold_r), _k_mul(a[..., 1, :], s, fold_r)],
        axis=-2,
    )


# --- in-kernel Fp6 / Fp12 (mirrors tower.py bit-for-bit) --------------------
# Fp6 layout (..., 3, 2, W); Fp12 layout (..., 2, 3, 2, W).


def _k6_mul(a, b, fold_r):
    a0, a1, a2 = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    b0, b1, b2 = b[..., 0, :, :], b[..., 1, :, :], b[..., 2, :, :]
    t0 = _k2_mul(a0, b0, fold_r)
    t1 = _k2_mul(a1, b1, fold_r)
    t2 = _k2_mul(a2, b2, fold_r)
    c0 = _k_add(
        _k2_mul_by_xi(
            _k_sub(
                _k_sub(
                    _k2_mul(
                        _k_add(a1, a2, fold_r), _k_add(b1, b2, fold_r), fold_r
                    ),
                    t1,
                    fold_r,
                ),
                t2,
                fold_r,
            ),
            fold_r,
        ),
        t0,
        fold_r,
    )
    c1 = _k_add(
        _k_sub(
            _k_sub(
                _k2_mul(_k_add(a0, a1, fold_r), _k_add(b0, b1, fold_r), fold_r),
                t0,
                fold_r,
            ),
            t1,
            fold_r,
        ),
        _k2_mul_by_xi(t2, fold_r),
        fold_r,
    )
    c2 = _k_add(
        _k_sub(
            _k_sub(
                _k2_mul(_k_add(a0, a2, fold_r), _k_add(b0, b2, fold_r), fold_r),
                t0,
                fold_r,
            ),
            t2,
            fold_r,
        ),
        t1,
        fold_r,
    )
    return jnp.stack([c0, c1, c2], axis=-3)


def _k6_mul_by_v(a, fold_r):
    return jnp.stack(
        [_k2_mul_by_xi(a[..., 2, :, :], fold_r), a[..., 0, :, :], a[..., 1, :, :]],
        axis=-3,
    )


def _k12_mul(a, b, fold_r):
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    b0, b1 = b[..., 0, :, :, :], b[..., 1, :, :, :]
    t0 = _k6_mul(a0, b0, fold_r)
    t1 = _k6_mul(a1, b1, fold_r)
    c1 = _k_sub(
        _k_sub(
            _k6_mul(_k_add(a0, a1, fold_r), _k_add(b0, b1, fold_r), fold_r),
            t0,
            fold_r,
        ),
        t1,
        fold_r,
    )
    c0 = _k_add(t0, _k6_mul_by_v(t1, fold_r), fold_r)
    return jnp.stack([c0, c1], axis=-4)


def _k12_sq(a, fold_r):
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    t = _k6_mul(a0, a1, fold_r)
    c0 = _k_sub(
        _k_sub(
            _k6_mul(
                _k_add(a0, a1, fold_r),
                _k_add(a0, _k6_mul_by_v(a1, fold_r), fold_r),
                fold_r,
            ),
            t,
            fold_r,
        ),
        _k6_mul_by_v(t, fold_r),
        fold_r,
    )
    return jnp.stack([c0, _k_add(t, t, fold_r)], axis=-4)


def _k12_cyclo_sq(a, fold_r):
    """tower.fp12_cyclotomic_sq: 9 Fp2 squarings in ONE stacked _k2_sq
    plus lincomb combines -- same schedule, bit-identical."""
    x00, x01, x02 = a[..., 0, 0, :, :], a[..., 0, 1, :, :], a[..., 0, 2, :, :]
    x10, x11, x12 = a[..., 1, 0, :, :], a[..., 1, 1, :, :], a[..., 1, 2, :, :]
    sq = _k2_sq(
        jnp.stack(
            [
                x11,
                x00,
                x02,
                x10,
                x12,
                x01,
                _k_add(x11, x00, fold_r),
                _k_add(x02, x10, fold_r),
                _k_add(x12, x01, fold_r),
            ],
            axis=0,
        ),
        fold_r,
    )
    t0, t1, t2, t3, t4, t5 = sq[0], sq[1], sq[2], sq[3], sq[4], sq[5]
    t6 = _k_sub(_k_sub(sq[6], t0, fold_r), t1, fold_r)
    t7 = _k_sub(_k_sub(sq[7], t2, fold_r), t3, fold_r)
    t8 = _k2_mul_by_xi(
        _k_sub(_k_sub(sq[8], t4, fold_r), t5, fold_r), fold_r
    )
    t0 = _k_add(_k2_mul_by_xi(t0, fold_r), t1, fold_r)
    t2 = _k_add(_k2_mul_by_xi(t2, fold_r), t3, fold_r)
    t4 = _k_add(_k2_mul_by_xi(t4, fold_r), t5, fold_r)

    def comb(t, x, sign):
        return _k_lincomb([(t, 3), (x, 2 * sign)], fold_r)

    return jnp.stack(
        [
            jnp.stack(
                [comb(t0, x00, -1), comb(t2, x01, -1), comb(t4, x02, -1)],
                axis=-3,
            ),
            jnp.stack(
                [comb(t8, x10, +1), comb(t6, x11, +1), comb(t7, x12, +1)],
                axis=-3,
            ),
        ],
        axis=-4,
    )


# --- in-kernel Miller step pieces (mirrors pairing.py bit-for-bit) ----------


def _k6_mul_s2(f6, a, b, fold_r):
    """pairing._fp6_mul_s2: Fp6 * (a + b v)."""
    d0, d1, d2 = f6[..., 0, :, :], f6[..., 1, :, :], f6[..., 2, :, :]
    r0 = _k_add(
        _k2_mul(d0, a, fold_r),
        _k2_mul_by_xi(_k2_mul(d2, b, fold_r), fold_r),
        fold_r,
    )
    r1 = _k_add(_k2_mul(d1, a, fold_r), _k2_mul(d0, b, fold_r), fold_r)
    r2 = _k_add(_k2_mul(d2, a, fold_r), _k2_mul(d1, b, fold_r), fold_r)
    return jnp.stack([r0, r1, r2], axis=-3)


def _k6_mul_s1(f6, c, fold_r):
    """pairing._fp6_mul_s1: Fp6 * (c v)."""
    d0, d1, d2 = f6[..., 0, :, :], f6[..., 1, :, :], f6[..., 2, :, :]
    return jnp.stack(
        [
            _k2_mul_by_xi(_k2_mul(d2, c, fold_r), fold_r),
            _k2_mul(d0, c, fold_r),
            _k2_mul(d1, c, fold_r),
        ],
        axis=-3,
    )


def _k_mul_by_line(f, line, fold_r):
    """pairing.mul_by_line: Karatsuba sparse multiply, 15 Fp2 muls."""
    c0, cv, cvw = line
    f0, f1 = f[..., 0, :, :, :], f[..., 1, :, :, :]
    t0 = _k6_mul_s2(f0, c0, cv, fold_r)
    t1 = _k6_mul_s1(f1, cvw, fold_r)
    s = _k6_mul_s2(
        _k_add(f0, f1, fold_r), c0, _k_add(cv, cvw, fold_r), fold_r
    )
    r0 = _k_add(t0, _k6_mul_by_v(t1, fold_r), fold_r)
    r1 = _k_sub(_k_sub(s, t0, fold_r), t1, fold_r)
    return jnp.stack([r0, r1], axis=-4)


def _k_jac_double(t, fold_r):
    """pairing._jac_double: dbl-2009-l."""
    x, y, z = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    a = _k2_sq(x, fold_r)
    b = _k2_sq(y, fold_r)
    c = _k2_sq(b, fold_r)
    d = _k2_mul_small(
        _k_sub(
            _k_sub(_k2_sq(_k_add(x, b, fold_r), fold_r), a, fold_r), c, fold_r
        ),
        2,
        fold_r,
    )
    e = _k2_mul_small(a, 3, fold_r)
    f = _k2_sq(e, fold_r)
    x3 = _k_sub(f, _k2_mul_small(d, 2, fold_r), fold_r)
    y3 = _k_sub(
        _k2_mul(e, _k_sub(d, x3, fold_r), fold_r),
        _k2_mul_small(c, 8, fold_r),
        fold_r,
    )
    z3 = _k2_mul(_k2_mul_small(y, 2, fold_r), z, fold_r)
    return jnp.stack([x3, y3, z3], axis=-3)


def _k_jac_madd(t, q_aff, fold_r):
    """pairing._jac_madd: madd-2007-bl."""
    x1, y1, z1 = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    x2, y2 = q_aff[..., 0, :, :], q_aff[..., 1, :, :]
    z1z1 = _k2_sq(z1, fold_r)
    u2 = _k2_mul(x2, z1z1, fold_r)
    s2 = _k2_mul(_k2_mul(y2, z1, fold_r), z1z1, fold_r)
    h = _k_sub(u2, x1, fold_r)
    hh = _k2_sq(h, fold_r)
    i = _k2_mul_small(hh, 4, fold_r)
    j = _k2_mul(h, i, fold_r)
    r = _k2_mul_small(_k_sub(s2, y1, fold_r), 2, fold_r)
    v = _k2_mul(x1, i, fold_r)
    x3 = _k_sub(
        _k_sub(_k2_sq(r, fold_r), j, fold_r),
        _k2_mul_small(v, 2, fold_r),
        fold_r,
    )
    y3 = _k_sub(
        _k2_mul(r, _k_sub(v, x3, fold_r), fold_r),
        _k2_mul_small(_k2_mul(y1, j, fold_r), 2, fold_r),
        fold_r,
    )
    z3 = _k_sub(
        _k_sub(_k2_sq(_k_add(z1, h, fold_r), fold_r), z1z1, fold_r),
        hh,
        fold_r,
    )
    return jnp.stack([x3, y3, z3], axis=-3)


def _k_dbl_step(t, xp, yp, fold_r):
    """pairing._dbl_step: 2T plus the tangent line at T evaluated at P."""
    x, y, z = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    x2 = _k2_sq(x, fold_r)
    y2 = _k2_sq(y, fold_r)
    z2 = _k2_sq(z, fold_r)
    x3 = _k2_mul(x2, x, fold_r)
    z3 = _k2_mul(z2, z, fold_r)
    c0 = _k_sub(
        _k2_mul_small(x3, 3, fold_r), _k2_mul_small(y2, 2, fold_r), fold_r
    )
    cv = _k2_mul_fp(
        _k2_mul_small(_k2_mul(x2, z2, fold_r), -3, fold_r), xp, fold_r
    )
    cvw = _k2_mul_fp(
        _k2_mul_small(_k2_mul(y, z3, fold_r), 2, fold_r), yp, fold_r
    )
    return _k_jac_double(t, fold_r), (c0, cv, cvw)


def _k_add_step(t, q_aff, xp, yp, fold_r):
    """pairing._add_step: T + Q plus the chord line through T, Q at P."""
    x, y, z = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    xq, yq = q_aff[..., 0, :, :], q_aff[..., 1, :, :]
    z2 = _k2_sq(z, fold_r)
    z3 = _k2_mul(z2, z, fold_r)
    n = _k_sub(y, _k2_mul(yq, z3, fold_r), fold_r)
    d = _k2_mul(z, _k_sub(x, _k2_mul(xq, z2, fold_r), fold_r), fold_r)
    c0 = _k_sub(_k2_mul(n, xq, fold_r), _k2_mul(d, yq, fold_r), fold_r)
    cv = _k_neg(_k2_mul_fp(n, xp, fold_r), fold_r)
    cvw = _k2_mul_fp(d, yp, fold_r)
    return _k_jac_madd(t, q_aff, fold_r), (c0, cv, cvw)


# --- plain Fp kernels (2D blocks) -------------------------------------------


def _mul_kernel(a_ref, b_ref, fold_ref, out_ref):
    """One block: (B, W) x (B, W) -> (B, W) lazy limbs, fully fused."""
    out_ref[:] = _k_mul(a_ref[:], b_ref[:], fold_ref[:])


def _sq_kernel(a_ref, fold_ref, out_ref):
    """One block: (B, W) -> (B, W), the dedicated squaring fold."""
    out_ref[:] = _k_reduce(_k_sq_cols(a_ref[:]), fold_ref[:])


@functools.lru_cache(maxsize=None)
def _mul_call(interpret: bool, block_rows: int):
    fold_shape = tuple(L.FOLD_R.shape)

    @jax.jit
    def call(a2: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
        n = a2.shape[0]
        grid = (n // block_rows,)
        return pl.pallas_call(
            _mul_kernel,
            out_shape=jax.ShapeDtypeStruct((n, W), jnp.int32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
                # the fold matrix: same full block for every grid step
                pl.BlockSpec(fold_shape, lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
            interpret=interpret,
        )(a2, b2, L.FOLD_R)

    return call


@functools.lru_cache(maxsize=None)
def _sq_call(interpret: bool, block_rows: int):
    fold_shape = tuple(L.FOLD_R.shape)

    @jax.jit
    def call(a2: jnp.ndarray) -> jnp.ndarray:
        n = a2.shape[0]
        grid = (n // block_rows,)
        return pl.pallas_call(
            _sq_kernel,
            out_shape=jax.ShapeDtypeStruct((n, W), jnp.int32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
                pl.BlockSpec(fold_shape, lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
            interpret=interpret,
        )(a2, L.FOLD_R)

    return call


def _interpret() -> bool:
    """Interpret mode on the CPU (the differential tests), compiled
    kernels on a TPU, and a refusal anywhere else: a backend that is
    neither must never run these kernels through the interpreter and
    pass that off as device work."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels need a TPU or the CPU, not {backend!r}")


def _block_rows(n: int, cap: int) -> int:
    """Size the block to the batch, rounded to the f32 sublane tile of 8,
    so a 5-row op is not padded to the cap."""
    return min(cap, -(-n // 8) * 8)


def _pad_rows(x: jnp.ndarray, padded: int) -> jnp.ndarray:
    n = x.shape[0]
    if padded == n:
        return x
    return jnp.pad(x, ((0, padded - n),) + ((0, 0),) * (x.ndim - 1))


def fp_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for limbs.mul: lazy limbs in, lazy limbs out, any leading
    batch shape. Rows are padded to the block size (pad rows are zeros:
    valid lazy limbs, discarded on return)."""
    a, b = jnp.broadcast_arrays(a, b)
    lead = a.shape[:-1]
    a2 = a.reshape(-1, W)
    b2 = b.reshape(-1, W)
    n = a2.shape[0]
    block_rows = _block_rows(n, BLOCK_ROWS)
    padded = -(-n // block_rows) * block_rows
    a2 = _pad_rows(a2, padded)
    b2 = _pad_rows(b2, padded)
    interpret = _interpret()
    out = _mul_call(interpret, block_rows)(a2, b2)
    return out[:n].reshape(*lead, W)


def fp_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for limbs.sq via the DEDICATED squaring kernel: half the
    partial products of the generic multiply, bit-identical output."""
    lead = a.shape[:-1]
    a2 = a.reshape(-1, W)
    n = a2.shape[0]
    block_rows = _block_rows(n, BLOCK_ROWS)
    padded = -(-n // block_rows) * block_rows
    a2 = _pad_rows(a2, padded)
    interpret = _interpret()
    out = _sq_call(interpret, block_rows)(a2)
    return out[:n].reshape(*lead, W)


# --- fused tower / Miller kernels (3D blocks: (rows, slots, W)) -------------
# Operands are flattened to (n, slots, W): Fp12 -> 12 slots, Fp6 -> 6,
# Jacobian G2 point -> 6, affine G2 point -> 4, plain Fp -> 1. Kernels
# reshape back to the structured layouts internally.


def _math_fp6_mul(ins, fold_r):
    a, b = ins
    rows = a.shape[0]
    a = a.reshape(rows, 3, 2, W)
    b = b.reshape(rows, 3, 2, W)
    return (_k6_mul(a, b, fold_r).reshape(rows, 6, W),)


def _math_fp12_mul(ins, fold_r):
    a, b = ins
    rows = a.shape[0]
    a = a.reshape(rows, 2, 3, 2, W)
    b = b.reshape(rows, 2, 3, 2, W)
    return (_k12_mul(a, b, fold_r).reshape(rows, 12, W),)


def _math_cyclo_sq(ins, fold_r):
    (a,) = ins
    rows = a.shape[0]
    a = a.reshape(rows, 2, 3, 2, W)
    return (_k12_cyclo_sq(a, fold_r).reshape(rows, 12, W),)


def _math_miller_dbl(ins, fold_r):
    f, t, xp, yp = ins
    rows = f.shape[0]
    f = f.reshape(rows, 2, 3, 2, W)
    t = t.reshape(rows, 3, 2, W)
    xp = xp[:, 0, :]
    yp = yp[:, 0, :]
    t2, line = _k_dbl_step(t, xp, yp, fold_r)
    f2 = _k_mul_by_line(_k12_sq(f, fold_r), line, fold_r)
    return (f2.reshape(rows, 12, W), t2.reshape(rows, 6, W))


def _math_miller_add(ins, fold_r):
    f, t, q, xp, yp = ins
    rows = f.shape[0]
    f = f.reshape(rows, 2, 3, 2, W)
    t = t.reshape(rows, 3, 2, W)
    q = q.reshape(rows, 2, 2, W)
    xp = xp[:, 0, :]
    yp = yp[:, 0, :]
    t2, line = _k_add_step(t, q, xp, yp, fold_r)
    f2 = _k_mul_by_line(f, line, fold_r)
    return (f2.reshape(rows, 12, W), t2.reshape(rows, 6, W))


# name -> (input slot dims, output slot dims, math fn)
_FUSED = {
    "fp6_mul": ((6, 6), (6,), _math_fp6_mul),
    "fp12_mul": ((12, 12), (12,), _math_fp12_mul),
    "cyclo_sq": ((12,), (12,), _math_cyclo_sq),
    "miller_dbl": ((12, 6, 1, 1), (12, 6), _math_miller_dbl),
    "miller_add": ((12, 6, 4, 1, 1), (12, 6), _math_miller_add),
}


def _make_fused_kernel(name):
    in_dims, _, math_fn = _FUSED[name]
    n_in = len(in_dims)

    def kernel(*refs):
        ins = [refs[i][:] for i in range(n_in)]
        fold_r = refs[n_in][:]
        outs = math_fn(ins, fold_r)
        for o_ref, o in zip(refs[n_in + 1 :], outs):
            o_ref[:] = o

    kernel.__name__ = f"_{name}_kernel"
    return kernel


@functools.lru_cache(maxsize=None)
def _fused_call(name: str, interpret: bool, block_rows: int):
    in_dims, out_dims, _ = _FUSED[name]
    kernel = _make_fused_kernel(name)
    fold_shape = tuple(L.FOLD_R.shape)

    @jax.jit
    def call(*ops):
        n = ops[0].shape[0]
        grid = (n // block_rows,)
        in_specs = [
            pl.BlockSpec((block_rows, d, W), lambda i: (i, 0, 0))
            for d in in_dims
        ]
        in_specs.append(pl.BlockSpec(fold_shape, lambda i: (0, 0)))
        out_specs = [
            pl.BlockSpec((block_rows, d, W), lambda i: (i, 0, 0))
            for d in out_dims
        ]
        out_shape = [
            jax.ShapeDtypeStruct((n, d, W), jnp.int32) for d in out_dims
        ]
        single = len(out_dims) == 1
        outs = pl.pallas_call(
            kernel,
            out_shape=out_shape[0] if single else out_shape,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs[0] if single else out_specs,
            interpret=interpret,
        )(*ops, L.FOLD_R)
        return (outs,) if single else tuple(outs)

    return call


def _run_fused(name: str, ins):
    """Pad flattened (n, slots, W) operands to a block multiple, run the
    fused kernel, slice the pads back off."""
    n = ins[0].shape[0]
    block_rows = _block_rows(n, FUSED_BLOCK_ROWS)
    padded = -(-n // block_rows) * block_rows
    ins = [_pad_rows(x, padded) for x in ins]
    interpret = _interpret()
    outs = _fused_call(name, interpret, block_rows)(*ins)
    return [o[:n] for o in outs]


def fp6_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for tower.fp6_mul, bit-identical."""
    a, b = jnp.broadcast_arrays(a, b)
    lead = a.shape[:-3]
    (out,) = _run_fused(
        "fp6_mul", [a.reshape(-1, 6, W), b.reshape(-1, 6, W)]
    )
    return out.reshape(*lead, 3, 2, W)


def fp12_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for tower.fp12_mul, bit-identical."""
    a, b = jnp.broadcast_arrays(a, b)
    lead = a.shape[:-4]
    (out,) = _run_fused(
        "fp12_mul", [a.reshape(-1, 12, W), b.reshape(-1, 12, W)]
    )
    return out.reshape(*lead, 2, 3, 2, W)


def fp12_cyclotomic_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for tower.fp12_cyclotomic_sq, bit-identical."""
    lead = a.shape[:-4]
    (out,) = _run_fused("cyclo_sq", [a.reshape(-1, 12, W)])
    return out.reshape(*lead, 2, 3, 2, W)


def miller_dbl_step(f, t, xp, yp):
    """Fused Miller doubling step: returns
    (mul_by_line(fp12_sq(f), line), 2T) bit-identical to the lax
    composition in pairing.py's scan body."""
    lead = f.shape[:-4]
    xp = jnp.broadcast_to(xp, lead + (W,))
    yp = jnp.broadcast_to(yp, lead + (W,))
    fo, to = _run_fused(
        "miller_dbl",
        [
            f.reshape(-1, 12, W),
            t.reshape(-1, 6, W),
            xp.reshape(-1, 1, W),
            yp.reshape(-1, 1, W),
        ],
    )
    return fo.reshape(*lead, 2, 3, 2, W), to.reshape(*lead, 3, 2, W)


def miller_add_step(f, t, q_aff, xp, yp):
    """Fused Miller addition step: returns
    (mul_by_line(f, line), T + Q) bit-identical to the lax composition."""
    lead = f.shape[:-4]
    q_aff = jnp.broadcast_to(q_aff, lead + (2, 2, W))
    xp = jnp.broadcast_to(xp, lead + (W,))
    yp = jnp.broadcast_to(yp, lead + (W,))
    fo, to = _run_fused(
        "miller_add",
        [
            f.reshape(-1, 12, W),
            t.reshape(-1, 6, W),
            q_aff.reshape(-1, 4, W),
            xp.reshape(-1, 1, W),
            yp.reshape(-1, 1, W),
        ],
    )
    return fo.reshape(*lead, 2, 3, 2, W), to.reshape(*lead, 3, 2, W)
