"""Batched G1/G2 point arithmetic on the TPU limb representation.

Replaces blst's POINTonE1/POINTonE2 C/assembly group law (the code behind
reference crypto/bls/src/impls/blst.rs aggregation at blst.rs:100-106 and the
subgroup checks at blst.rs:72-82) with branchless, batch-first kernels:

  * Points are stacked HOMOGENEOUS PROJECTIVE coordinate arrays -- G1:
    (..., 3, W), G2: (..., 3, 2, W) -- limbs last, batch axes leading.
    x = X/Z, y = Y/Z; infinity is (0, 1, 0).
  * The group law is the Renes-Costello-Batina COMPLETE addition for
    j-invariant-0 short Weierstrass curves (eprint 2015/1060, algorithms
    7/9 specialized to a = 0). BLS12-381's E(Fp) and E'(Fp2) both have odd
    order (cofactors 0x396c...aaab and 0x5d54...8e5 are odd), so the curves
    carry no 2-torsion and the formulas are complete for EVERY on-curve
    input pair, including infinity and P == +-Q. This removes all
    exceptional-case handling -- no exact zero-tests (canonicalization),
    no inlined doubling fallback, no selects -- from the group law, which
    is what makes the compiled program per add ~3x smaller than a complete
    Jacobian add and keeps ladder scan bodies compact.
  * One generic group law is instantiated over both fields through a tiny
    field-ops namespace (`FP`, `FP2`); no per-curve duplication.
  * Scalar multiplication is a `lax.scan` double-and-add over either a
    compile-time exponent (subgroup checks, cofactors) or runtime 64-bit
    scalars (the random-linear-combination weights of batch verification,
    reference blst.rs:45-57) -- constant program size, fully batched.
  * psi (untwist-Frobenius-twist) acts coordinate-wise, and homogeneous
    coordinates are scaling-invariant, so psi(X:Y:Z) = (cx conj(X) :
    cy conj(Y) : conj(Z)) needs no normalization; it feeds the fast G2
    subgroup check psi(P) == [x]P (blst's check; oracle cross-validated in
    curve_ref.g2_subgroup_check_psi).
  * Cross-set point sums (pubkey aggregation, the weighted-signature sum)
    use `sum_points`: a halving reduction expressed as ONE scanned
    body instead of log2(n) inlined tree levels, trading ~2x redundant
    adds (on infinity padding) for log-fold smaller programs.

Differentially tested against the pure-Python oracle (curve_ref.py) in
tests/test_tpu_curve.py.
"""

from __future__ import annotations

import os as _os

import numpy as np

import jax
import jax.numpy as jnp

from ..constants import BLS_X, G1_X, G1_Y, G2_X, G2_Y, P, R
from ..curve_ref import Point, _PSI_CX, _PSI_CY
from ..fields_ref import Fp, Fp2
from . import limbs as L
from . import tower as T

W = L.W


# --- field-ops namespaces ---------------------------------------------------


class FP:
    """Fp coordinate ops for stacked G1 points (..., 3, W)."""

    coord_ndim = 1  # trailing dims of one field element

    mul = staticmethod(L.mul)
    sq = staticmethod(L.sq)
    add = staticmethod(L.add)
    sub = staticmethod(L.sub)
    neg = staticmethod(L.neg)
    mul_small = staticmethod(L.mul_small)
    is_zero = staticmethod(L.is_zero)
    eq = staticmethod(L.eq)

    @staticmethod
    def mul_b3(a):
        """b3 = 3b = 12 for E: y^2 = x^3 + 4."""
        return L.mul_small(a, 12)

    @staticmethod
    def one(shape=()):
        return jnp.broadcast_to(L.ONE, shape + (W,))

    @staticmethod
    def zero(shape=()):
        return jnp.zeros(shape + (W,), jnp.int32)

    @staticmethod
    def select(cond, a, b):
        return jnp.where(cond[..., None], a, b)


class FP2:
    """Fp2 coordinate ops for stacked G2 points (..., 3, 2, W)."""

    coord_ndim = 2

    mul = staticmethod(T.fp2_mul)
    sq = staticmethod(T.fp2_sq)
    add = staticmethod(T.fp2_add)
    sub = staticmethod(T.fp2_sub)
    neg = staticmethod(T.fp2_neg)
    mul_small = staticmethod(T.fp2_mul_small)
    is_zero = staticmethod(T.fp2_is_zero)
    eq = staticmethod(T.fp2_eq)
    one = staticmethod(T.fp2_one)
    zero = staticmethod(T.fp2_zero)
    select = staticmethod(T.fp2_select)

    @staticmethod
    def mul_b3(a):
        """b3 = 3b = 12(1 + u) for E': y^2 = x^3 + 4(1 + u)."""
        return T.fp2_mul_by_xi(T.fp2_mul_small(a, 12))


def _coord(p, i, F):
    return p[(Ellipsis, i) + (slice(None),) * F.coord_ndim]


def _pack(x, y, z, F):
    return jnp.stack([x, y, z], axis=-(F.coord_ndim + 1))


def point_select(cond, a, b, F):
    return jnp.where(cond[(Ellipsis,) + (None,) * (F.coord_ndim + 1)], a, b)


def is_infinity(p, F):
    return F.is_zero(_coord(p, 2, F))


def infinity(F, shape=()):
    """Projective infinity (0, 1, 0)."""
    return _pack(F.zero(shape), F.one(shape), F.zero(shape), F)


# --- complete group law (RCB 2015, a = 0) ----------------------------------


def add(p, q, F):
    """Complete projective addition (RCB eprint 2015/1060, algorithm 7 for
    a = 0): 12M + 2 b3-mults, branchless, valid for every on-curve pair
    including infinity and P == +-Q (the curves have odd order)."""
    x1, y1, z1 = _coord(p, 0, F), _coord(p, 1, F), _coord(p, 2, F)
    x2, y2, z2 = _coord(q, 0, F), _coord(q, 1, F), _coord(q, 2, F)
    t0 = F.mul(x1, x2)
    t1 = F.mul(y1, y2)
    t2 = F.mul(z1, z2)
    t3 = F.mul(F.add(x1, y1), F.add(x2, y2))
    t3 = F.sub(t3, F.add(t0, t1))  # x1 y2 + x2 y1
    t4 = F.mul(F.add(y1, z1), F.add(y2, z2))
    t4 = F.sub(t4, F.add(t1, t2))  # y1 z2 + y2 z1
    x3 = F.mul(F.add(x1, z1), F.add(x2, z2))
    y3 = F.sub(x3, F.add(t0, t2))  # x1 z2 + x2 z1
    x3 = F.mul_small(t0, 3)  # 3 x1 x2, one normalization
    t2 = F.mul_b3(t2)
    z3 = F.add(t1, t2)
    t1 = F.sub(t1, t2)
    y3 = F.mul_b3(y3)
    x3_out = F.sub(F.mul(t3, t1), F.mul(t4, y3))
    y3_out = F.add(F.mul(y3, x3), F.mul(t1, z3))
    z3_out = F.add(F.mul(z3, t4), F.mul(x3, t3))
    return _pack(x3_out, y3_out, z3_out, F)


def double(p, F):
    """Complete projective doubling (RCB algorithm 9 for a = 0):
    6M + 2S + 1 b3-mult, branchless, handles infinity natively."""
    x, y, z = _coord(p, 0, F), _coord(p, 1, F), _coord(p, 2, F)
    t0 = F.sq(y)
    z3 = F.mul_small(t0, 8)  # 8 Y^2, one normalization
    t1 = F.mul(y, z)
    t2 = F.mul_b3(F.sq(z))
    x3 = F.mul(t2, z3)
    y3 = F.add(t0, t2)
    z3 = F.mul(t1, z3)
    t0 = F.sub(t0, F.mul_small(t2, 3))
    y3 = F.add(F.mul(t0, y3), x3)
    t1 = F.mul(t0, F.mul(x, y))
    x3 = F.add(t1, t1)
    return _pack(x3, y3, z3, F)


def add_mixed(p, q_aff, q_inf, F):
    """Projective + affine: q_aff = (x2, y2) stacked (..., 2, ...), q_inf a
    bool mask. Lifts q to projective and uses the complete law (the RCB
    mixed variant saves 1M but cannot represent affine infinity; the lift
    keeps completeness)."""
    return add(p, from_affine(q_aff, q_inf, F), F)


def neg(p, F):
    x, y, z = _coord(p, 0, F), _coord(p, 1, F), _coord(p, 2, F)
    return _pack(x, F.neg(y), z, F)


def eq(p, q, F):
    """Projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1, with
    infinity equal only to infinity."""
    x1, y1, z1 = _coord(p, 0, F), _coord(p, 1, F), _coord(p, 2, F)
    x2, y2, z2 = _coord(q, 0, F), _coord(q, 1, F), _coord(q, 2, F)
    same_x = F.eq(F.mul(x1, z2), F.mul(x2, z1))
    same_y = F.eq(F.mul(y1, z2), F.mul(y2, z1))
    p_inf, q_inf = is_infinity(p, F), is_infinity(q, F)
    return (p_inf & q_inf) | (~p_inf & ~q_inf & same_x & same_y)


# --- scalar multiplication --------------------------------------------------


# NOTE on windowed ladders: a 4-bit-window variant (precomputed 15-entry
# point tables + one table add per digit) was measured at +11% verifier
# throughput on the TPU, but its unrolled table construction and in-scan
# table gathers blew the 8-device SPMD compile up ~2.5x (387s -> >870s on
# the CPU mesh), busting the multichip-dryrun budget. Bit ladders stay
# until the compile cost is solved (e.g. building tables inside a scan).


def scalar_mul_static(p, e: int, F):
    """[e]P for a compile-time e >= 0: ONE lax.scan over the bits (MSB
    first), with the add under lax.cond so a clear bit costs only the
    doubling. The BLS parameter x has Hamming weight 6 over 64 bits, so
    the cofactor-clearing ladders execute 6 adds instead of 64 — the add
    is the expensive half of a ladder step (complete projective add ≈ 2x
    a double) — while the program still contains exactly one double body
    and one add body (per-shape compile cost is the scarce resource)."""
    if e == 0:
        return infinity(F, p.shape[: p.ndim - F.coord_ndim - 1])
    bits = jnp.asarray(np.array([int(b) for b in bin(e)[2:]], np.bool_))

    def body(acc, bit):
        acc = double(acc, F)
        acc = jax.lax.cond(
            bit, lambda a: add(a, p, F), lambda a: a, acc
        )
        return acc, None

    # seed with the MSB consumed: acc = P, scan the remaining bits
    out, _ = jax.lax.scan(body, p, bits[1:])
    return out


def scalar_mul_u64(p, scalars, F):
    """[s]P for runtime 64-bit scalars (the batch-verify random weights).

    scalars: (...,) uint64-valued array given as (..., 2) uint32 (hi, lo).
    Runs a 64-iteration MSB-first double-and-add ladder under lax.scan.
    """
    hi = scalars[..., 0]
    lo = scalars[..., 1]
    word = jnp.stack([hi, lo], axis=0)  # (2, ...)

    def bit_at(k):  # k in [0, 64), MSB first
        w = word[k // 32]
        return ((w >> jnp.uint32(31 - (k % 32))) & jnp.uint32(1)) != 0

    bits = jnp.stack([bit_at(k) for k in range(64)], axis=0)  # (64, ...)

    def body(acc, bit):
        acc = double(acc, F)
        return point_select(bit, add(acc, p, F), acc, F), None

    init = infinity(F, p.shape[: p.ndim - F.coord_ndim - 1])
    out, _ = jax.lax.scan(body, init, bits)
    return out


def scalar_mul_u64_windowed(p, scalars, F, window: int = 4):
    """[s]P via a fixed-window ladder: a 2^window-entry point table built
    once (2^window - 2 sequential adds), then 64/window scan steps of
    `window` doublings + ONE table-gathered add -- 16 adds instead of 64
    for the default 4-bit window.

    This is the ladder the NOTE above reverted from the default XLA path
    (commit 3ef20a6: table build + in-scan gathers blew the 8-device SPMD
    compile past the budget). It is re-tried ONLY under the Pallas flag,
    where the fused point/field kernels collapse each add/double to a
    handful of pallas_call ops and hand tiling, not XLA fusion search,
    controls program size. The complete projective group law makes the
    table's infinity entry and windows of zero digits exception-free, so
    no select is needed around the add."""
    batch = p.shape[: p.ndim - F.coord_ndim - 1]
    hi = scalars[..., 0]
    lo = scalars[..., 1]
    word = jnp.stack([hi, lo], axis=0)  # (2, ...)
    per_word = 32 // window
    ndigits = 64 // window
    assert 64 % window == 0 and 32 % window == 0

    def digit_at(k):  # k in [0, ndigits), MSB first
        w = word[k // per_word]
        shift = jnp.uint32(32 - window * (k % per_word + 1))
        return ((w >> shift) & jnp.uint32((1 << window) - 1)).astype(jnp.int32)

    digits = jnp.stack([digit_at(k) for k in range(ndigits)], axis=0)

    # table[j] = [j]P, built with sequential complete adds (the unrolled
    # construction the XLA path could not afford)
    tbl = [infinity(F, batch), p]
    for _ in range(2, 1 << window):
        tbl.append(add(tbl[-1], p, F))
    table = jnp.stack(tbl, axis=0)  # (2^window,) + batch + point dims

    def gather(digit):
        idx = digit.reshape((1,) + digit.shape + (1,) * (F.coord_ndim + 1))
        return jnp.take_along_axis(table, idx, axis=0)[0]

    def body(acc, digit):
        for _ in range(window):
            acc = double(acc, F)
        return add(acc, gather(digit), F), None

    out, _ = jax.lax.scan(body, gather(digits[0]), digits[1:])
    return out


if _os.environ.get("LIGHTHOUSE_TPU_PALLAS") == "1":  # pragma: no cover
    _scalar_mul_u64_bit = scalar_mul_u64

    def scalar_mul_u64(p, scalars, F):  # noqa: F811
        return scalar_mul_u64_windowed(p, scalars, F)


# --- cross-set reductions ---------------------------------------------------


def sum_points(p, F):
    """EC sum over axis 0 (any length; pads to a power of two with
    infinity) as ONE scanned halving body: each iteration adds adjacent
    pairs into the front half and refills the back half with infinity.
    log2(n) iterations; compiled program size is a single complete add
    regardless of n."""
    n = p.shape[0]
    m = 1
    while m < n:
        m *= 2
    batch = p.shape[1 : p.ndim - F.coord_ndim - 1]
    if m > n:
        p = jnp.concatenate([p, infinity(F, (m - n,) + batch)], axis=0)
    if m == 1:
        return p[0]
    half = m // 2
    pad = infinity(F, (half,) + batch)
    steps = m.bit_length() - 1

    def body(acc, _):
        s = add(acc[0::2], acc[1::2], F)
        return jnp.concatenate([s, pad], axis=0), None

    out, _ = jax.lax.scan(body, p, None, length=steps)
    return out[0]


# --- affine conversion ------------------------------------------------------


def to_affine_g1(p):
    """Batched projective -> affine for G1 (one Fermat inversion total via
    Montgomery batch inversion). Infinity maps to (0, 0) + mask."""
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    inf = L.is_zero(z)
    z_safe = L.select(inf, jnp.broadcast_to(L.ONE, z.shape), z)
    flat = z_safe.reshape(-1, W)
    zinv = T.fp_batch_inv(flat, axis=0).reshape(z.shape)
    ax = L.mul(x, zinv)
    ay = L.mul(y, zinv)
    zero = jnp.zeros_like(ax)
    return (
        jnp.stack([L.select(inf, zero, ax), L.select(inf, zero, ay)], axis=-2),
        inf,
    )


def to_affine_g2(p):
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    inf = T.fp2_is_zero(z)
    z_safe = T.fp2_select(inf, T.fp2_one(z.shape[:-2]), z)
    flat = z_safe.reshape(-1, 2, W)
    zinv = T.fp2_batch_inv(flat, axis=0).reshape(z.shape)
    ax = T.fp2_mul(x, zinv)
    ay = T.fp2_mul(y, zinv)
    zero = jnp.zeros_like(ax)
    return (
        jnp.stack(
            [T.fp2_select(inf, zero, ax), T.fp2_select(inf, zero, ay)], axis=-3
        ),
        inf,
    )


def from_affine(aff, inf, F):
    """(..., 2, coord) affine + inf mask -> projective; infinity -> (0, 1, 0)."""
    x, y = _coord(aff, 0, F), _coord(aff, 1, F)
    shape = inf.shape
    z = F.select(inf, F.zero(shape), F.one(shape))
    one = F.one(shape)
    return _pack(F.select(inf, F.zero(shape), x), F.select(inf, one, y), z, F)


# --- host <-> device --------------------------------------------------------


def g1_pack(points) -> jnp.ndarray:
    """Oracle affine G1 points -> (n, 3, W) projective device array."""
    out = np.zeros((len(points), 3, W), np.int32)
    for i, pt in enumerate(points):
        if pt.inf:
            out[i, 1] = L.to_limbs(1)
        else:
            out[i, 0] = L.to_limbs(pt.x.n)
            out[i, 1] = L.to_limbs(pt.y.n)
            out[i, 2] = L.to_limbs(1)
    return jnp.asarray(out)


def g2_pack(points) -> jnp.ndarray:
    """Oracle affine G2 points -> (n, 3, 2, W) projective device array."""
    out = np.zeros((len(points), 3, 2, W), np.int32)
    for i, pt in enumerate(points):
        if pt.inf:
            out[i, 1, 0] = L.to_limbs(1)
        else:
            out[i, 0, 0] = L.to_limbs(pt.x.c0.n)
            out[i, 0, 1] = L.to_limbs(pt.x.c1.n)
            out[i, 1, 0] = L.to_limbs(pt.y.c0.n)
            out[i, 1, 1] = L.to_limbs(pt.y.c1.n)
            out[i, 2, 0] = L.to_limbs(1)
    return jnp.asarray(out)


def g1_unpack(p) -> list:
    """(n, 3, W) projective device array -> oracle affine points (host)."""
    aff, inf = to_affine_g1(p)
    aff, inf = np.asarray(aff), np.asarray(inf)
    out = []
    for i in range(aff.shape[0]):
        if inf[i]:
            out.append(Point(Fp(0), Fp(0), True))
        else:
            out.append(
                Point(Fp(L.to_fp_int(aff[i, 0])), Fp(L.to_fp_int(aff[i, 1])))
            )
    return out


def g2_unpack(p) -> list:
    aff, inf = to_affine_g2(p)
    aff, inf = np.asarray(aff), np.asarray(inf)
    out = []
    for i in range(aff.shape[0]):
        if inf[i]:
            out.append(Point(Fp2.zero(), Fp2.zero(), True))
        else:
            x = Fp2(L.to_fp_int(aff[i, 0, 0]), L.to_fp_int(aff[i, 0, 1]))
            y = Fp2(L.to_fp_int(aff[i, 1, 0]), L.to_fp_int(aff[i, 1, 1]))
            out.append(Point(x, y))
    return out


# --- psi endomorphism & subgroup checks ------------------------------------

# psi coefficients from the oracle's derivation (curve_ref.py:107-108).
_PSI_CX_DEV = jnp.asarray(T.fp2_from_ints(_PSI_CX.c0.n, _PSI_CX.c1.n))
_PSI_CY_DEV = jnp.asarray(T.fp2_from_ints(_PSI_CY.c0.n, _PSI_CY.c1.n))

_X_ABS = -BLS_X


def psi(p):
    """Projective psi: (cx conj(X), cy conj(Y), conj(Z)) -- conjugation and
    the coefficient scalings commute with the projective scaling, so no
    normalization is needed."""
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    return jnp.stack(
        [
            T.fp2_mul(T.fp2_conj(x), _PSI_CX_DEV),
            T.fp2_mul(T.fp2_conj(y), _PSI_CY_DEV),
            T.fp2_conj(z),
        ],
        axis=-3,
    )


def g2_subgroup_check(p) -> jnp.ndarray:
    """P in G2 iff psi(P) == [x]P (x < 0: [x]P = -[|x|]P). The fast check
    blst performs (blst.rs:72-82); oracle-validated."""
    xp = neg(scalar_mul_static(p, _X_ABS, FP2), FP2)
    return eq(psi(p), xp, FP2) | is_infinity(p, FP2)


def g1_subgroup_check(p) -> jnp.ndarray:
    """Definitional [r]P == O. Runs once per pubkey at cache-build time (the
    reference's ValidatorPubkeyCache boundary), not in the per-batch path."""
    return is_infinity(scalar_mul_static(p, R, FP), FP)


def on_curve_g1(p) -> jnp.ndarray:
    """Y^2 Z == X^3 + 4 Z^3 (projective form); infinity passes."""
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    lhs = L.mul(L.sq(y), z)
    rhs = L.add(L.mul(L.sq(x), x), L.mul_small(L.mul(L.sq(z), z), 4))
    return L.eq(lhs, rhs) | is_infinity(p, FP)


def on_curve_g2(p) -> jnp.ndarray:
    """Y^2 Z == X^3 + (4 + 4u) Z^3; infinity passes."""
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    b = T.fp2_mul_by_xi(T.fp2_mul_small(T.fp2_mul(T.fp2_sq(z), z), 4))
    lhs = T.fp2_mul(T.fp2_sq(y), z)
    rhs = T.fp2_add(T.fp2_mul(T.fp2_sq(x), x), b)
    return T.fp2_eq(lhs, rhs) | is_infinity(p, FP2)


# --- generators -------------------------------------------------------------

G1_GEN = jnp.asarray(
    np.stack([L.to_limbs(G1_X), L.to_limbs(G1_Y), L.to_limbs(1)])
)  # (3, W)

G2_GEN = jnp.asarray(
    np.stack(
        [
            T.fp2_from_ints(*G2_X),
            T.fp2_from_ints(*G2_Y),
            T.fp2_from_ints(1, 0),
        ]
    )
)  # (3, 2, W)
