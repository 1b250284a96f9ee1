"""Differential tests: TPU Jacobian group law vs the pure-Python oracle.

Covers the batched point ops that replace blst's POINTonE1/POINTonE2
(reference crypto/bls/src/impls/blst.rs:72-106): add/double incl. all
exceptional cases, mixed add, the runtime-64-bit weight ladder, affine
conversion, psi, the subgroup/on-curve checks, and the cross-row point
sum (halving scan and fold).

Structure note: every op is wrapped in ONE module-level jitted kernel at
ONE batch shape (B = 8), so the suite pays each XLA compile exactly once
(and the persistent cache makes repeat runs cheap). Oracle values are
computed host-side per case.
"""

import math
import random

import numpy as np
import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls import curve_ref as C
from lighthouse_tpu.crypto.bls.constants import B2, P, R
from lighthouse_tpu.crypto.bls.fields_ref import Fp, Fp2
from lighthouse_tpu.crypto.bls.tpu import curve as TC
from lighthouse_tpu.crypto.bls.tpu import limbs as L
from lighthouse_tpu.utils import metrics

rng = random.Random(0xC0FFEE)
B = 8  # unified batch size -> one compile per kernel

INF1 = C.Point(Fp(0), Fp(0), True)
INF2 = C.Point(Fp2.zero(), Fp2.zero(), True)

jadd1 = jax.jit(lambda p, q: TC.add(p, q, TC.FP))
jdbl1 = jax.jit(lambda p: TC.double(p, TC.FP))
jmul1 = jax.jit(lambda p, s: TC.scalar_mul_u64(p, s, TC.FP))
jaff1 = jax.jit(TC.to_affine_g1)
joncurve1 = jax.jit(TC.on_curve_g1)
jsubgroup1 = jax.jit(TC.g1_subgroup_check)
jsum1 = jax.jit(lambda p: TC.sum_points(p, TC.FP))
jfold1 = jax.jit(lambda p: TC.fold_sum(p, 2, TC.FP))

jadd2 = jax.jit(lambda p, q: TC.add(p, q, TC.FP2))
jmadd2 = jax.jit(lambda p, q, qi: TC.add_mixed(p, q, qi, TC.FP2))
jdbl2 = jax.jit(lambda p: TC.double(p, TC.FP2))
jmul2 = jax.jit(lambda p, s: TC.scalar_mul_u64(p, s, TC.FP2))
jaff2 = jax.jit(TC.to_affine_g2)
jpsi = jax.jit(TC.psi)
joncurve2 = jax.jit(TC.on_curve_g2)
jsubgroup2 = jax.jit(TC.g2_subgroup_check)


def rand_g1(n):
    g = C.g1_generator()
    return [g.mul(rng.randrange(1, R)) for _ in range(n)]


def rand_g2(n):
    g = C.g2_generator()
    return [g.mul(rng.randrange(1, R)) for _ in range(n)]


def unpack_g1(dev):
    aff, inf = jaff1(dev)
    aff, inf = np.asarray(aff), np.asarray(inf)
    out = []
    for i in range(aff.shape[0]):
        if inf[i]:
            out.append(INF1)
        else:
            out.append(
                C.Point(Fp(L.to_fp_int(aff[i, 0])), Fp(L.to_fp_int(aff[i, 1])))
            )
    return out


def unpack_g2(dev):
    aff, inf = jaff2(dev)
    aff, inf = np.asarray(aff), np.asarray(inf)
    out = []
    for i in range(aff.shape[0]):
        if inf[i]:
            out.append(INF2)
        else:
            x = Fp2(L.to_fp_int(aff[i, 0, 0]), L.to_fp_int(aff[i, 0, 1]))
            y = Fp2(L.to_fp_int(aff[i, 1, 0]), L.to_fp_int(aff[i, 1, 1]))
            out.append(C.Point(x, y))
    return out


def u64_scalars(vals):
    return jnp.asarray(
        np.array(
            [[(v >> 32) & 0xFFFFFFFF, v & 0xFFFFFFFF] for v in vals], np.uint32
        )
    )


def non_subgroup_g2():
    """A curve point outside the r-torsion (random x, cofactor NOT cleared)."""
    while True:
        x = Fp2(rng.randrange(P), rng.randrange(P))
        y2 = x * x * x + Fp2(*B2)
        y = y2.sqrt()
        if y is not None:
            p = C.Point(x, y)
            if not C.g2_subgroup_check_psi(p):
                return p


class TestG1:
    def test_add_covers_all_exceptional_cases(self):
        pts = rand_g1(4)
        a, b = pts[0], pts[1]
        cases = [
            (a, b),          # generic
            (a, a),          # P + P -> double
            (a, -a),         # P + (-P) -> infinity
            (INF1, b),       # inf + Q
            (a, INF1),       # P + inf
            (INF1, INF1),    # inf + inf
            (pts[2], pts[3]),
            (-pts[2], pts[3]),
        ]
        pa = TC.g1_pack([c[0] for c in cases])
        pb = TC.g1_pack([c[1] for c in cases])
        assert unpack_g1(jadd1(pa, pb)) == [x + y for x, y in cases]
        assert unpack_g1(jdbl1(pa)) == [x.double() for x, _ in cases]

    def test_scalar_mul_u64(self):
        pts = rand_g1(B)
        scalars = [rng.randrange(1 << 64) for _ in range(B - 2)] + [0, 1]
        got = unpack_g1(jmul1(TC.g1_pack(pts), u64_scalars(scalars)))
        assert got == [p.mul(v) for p, v in zip(pts, scalars)]

    def test_scalar_mul_static_small_exponent(self):
        # arbitrary-exponent static ladder (the big fixed exponents R and
        # |x| are covered by the subgroup checks); 0b100101 hits both bit
        # kinds in a tiny compile
        pts = rand_g1(B)
        dev = TC.g1_pack(pts)
        got = unpack_g1(
            jax.jit(lambda p: TC.scalar_mul_static(p, 37, TC.FP))(dev)
        )
        assert got == [p.mul(37) for p in pts]

    def test_subgroup_and_curve_checks(self):
        good = rand_g1(B)
        dev = TC.g1_pack(good)
        assert np.asarray(joncurve1(dev)).all()
        assert np.asarray(jsubgroup1(dev)).all()
        bad = dev.at[0, 1, 0].add(1)  # off-curve junk: tweak y
        assert not np.asarray(joncurve1(bad))[0]


class TestG2:
    def test_add_and_mixed_add(self):
        pts = rand_g2(3)
        a, b = pts[0], pts[1]
        p_pts = [a, a, INF2, a, b, pts[2], a, INF2]
        q_pts = [b, a, b, INF2, pts[2], pts[2], -a, INF2]
        pa = TC.g2_pack(p_pts)
        qdev = TC.g2_pack(q_pts)
        want = [x + y for x, y in zip(p_pts, q_pts)]
        assert unpack_g2(jadd2(pa, qdev)) == want
        q_inf = jnp.asarray([p.inf for p in q_pts])
        assert unpack_g2(jmadd2(pa, qdev[:, :2], q_inf)) == want

    def test_scalar_mul_u64_and_psi(self):
        pts = rand_g2(B)
        scalars = [rng.randrange(1 << 64) for _ in range(B)]
        dev = TC.g2_pack(pts)
        got = unpack_g2(jmul2(dev, u64_scalars(scalars)))
        assert got == [p.mul(v) for p, v in zip(pts, scalars)]
        assert unpack_g2(jpsi(dev)) == [C.psi(p) for p in pts]

    def test_double_with_nontrivial_z(self):
        pts = rand_g2(B - 1) + [INF2]
        dev = TC.g2_pack(pts)
        assert unpack_g2(jdbl2(dev)) == [p.double() for p in pts]

    def test_subgroup_check(self):
        good = rand_g2(B - 2)
        bad = non_subgroup_g2()
        dev = TC.g2_pack(good + [bad, INF2])
        got = np.asarray(jsubgroup2(dev))
        assert got.tolist() == [True] * (B - 2) + [False, True]
        assert np.asarray(joncurve2(dev)).all()


def _sum_adds(m, width):
    """Complete adds of `sum_points` over m rows (a power of two) of
    `width` points: the fold's chunk steps plus the halving steps."""
    b = TC.fold_rows(m, width)
    if not b:
        return m // 2 * int(math.log2(m)) * width
    return (m // b - 1) * b * width + b // 2 * int(math.log2(b)) * width


class TestPointSum:
    def test_fold_and_halving_match_oracle(self):
        # columns: generic, a row of infinity, mixed infinity, P + (-P),
        # all infinity; 5 rows pad to 8, so the fold at chunk 2 scans 3
        # steps before its halving
        n = 5
        pts = rand_g1(n)
        a = pts[0]
        cols = [
            pts,
            [INF1] * n,
            [INF1, pts[1], INF1, pts[3], INF1],
            [a, -a, INF1, INF1, a],
            [a, -a, pts[2], -pts[2], INF1],
            [INF1, INF1, INF1, INF1, pts[4]],
            [pts[4], INF1, INF1, INF1, INF1],
            [a] * n,
        ]
        dev = jnp.stack([TC.g1_pack(c) for c in cols], axis=1)  # (n, B, 3, W)
        want = []
        for c in cols:
            acc = INF1
            for q in c:
                acc = acc + q
            want.append(acc)
        assert want[3] == a and want[4] == INF1 and want[1] == INF1
        assert unpack_g1(jfold1(dev)) == want
        assert unpack_g1(jsum1(dev)) == want

    def test_single_row_is_identity(self):
        dev = TC.g1_pack(rand_g1(B))[None]
        for got in (TC.fold_sum(dev, 2, TC.FP), TC.sum_points(dev, TC.FP)):
            np.testing.assert_array_equal(np.asarray(got), dev[0])

    def test_fold_rule_at_the_cells_shapes(self):
        # gossip: key sum 4 x 64, signature sum 64 x 1, map_to_g2's 3-point
        # sum 4 x 64; block signature sum 256 x 1
        for m, width in ((4, 64), (64, 1), (256, 1), (4, 128)):
            assert TC.fold_rows(m, width) == 0
            assert _sum_adds(m, width) == m // 2 * int(math.log2(m)) * width
        # block key sum: 512 keys x 256 sets; grouping: 256 x 128 messages
        assert TC.fold_rows(512, 256) * 256 == TC.FOLD_WIDTH == 4096
        assert TC.fold_rows(512, 256) == 16
        assert _sum_adds(512, 256) == 135_168  # halving: 589,824
        assert TC.fold_rows(256, 128) == 32
        assert _sum_adds(256, 128) == 38_912  # halving: 131,072
        # the fold starts where a halving step reaches FOLD_WIDTH adds
        assert TC.fold_rows(2 * TC.FOLD_WIDTH, 1) == TC.FOLD_WIDTH
        assert TC.fold_rows(TC.FOLD_WIDTH, 1) == 0

    def test_fold_counter_counts_traced_folds(self):
        def traced(shape):
            before = metrics.TPU_POINT_SUM_FOLDS.value
            jax.eval_shape(
                lambda p: TC.sum_points(p, TC.FP),
                jax.ShapeDtypeStruct(shape + (3, TC.W), jnp.int32),
            )
            return metrics.TPU_POINT_SUM_FOLDS.value - before

        assert traced((512, 256)) == 1
        assert traced((256, 128)) == 1
        assert traced((4, 64)) == 0
        assert traced((64,)) == 0
