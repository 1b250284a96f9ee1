"""The Pallas kernel suite (pallas_kernels.py), run in interpreter mode
off-TPU: bit-exact against the XLA path and the big-int oracle
(fields_ref.py), including adversarial maximal-limb inputs and
non-block-aligned batches.

The Fp multiply/square tests are cheap and run in tier-1; the fused
tower/Miller kernels compile slowly in interpret mode, so their parity
matrix carries `kernels` + `slow` and runs in the dedicated kernels CI
job."""

import numpy as np
import pytest

from lighthouse_tpu.crypto.bls.constants import P
from lighthouse_tpu.crypto.bls.tpu import limbs as L
from lighthouse_tpu.crypto.bls.tpu import pairing as TP
from lighthouse_tpu.crypto.bls.tpu import tower as T
from lighthouse_tpu.crypto.bls.tpu import pallas_kernels as PK
from lighthouse_tpu.crypto.bls.tpu.pallas_kernels import fp_mul, fp_sq


def lazy_random(rng, shape):
    """Random limbs across the full lazy range [-1, 2^12]."""
    return rng.integers(-1, (1 << 12) + 1, size=shape + (L.W,)).astype(np.int32)


class TestPallasMul:
    @pytest.mark.parametrize("shape", [(1,), (7,), (300,), (2, 5)])
    def test_matches_xla_path_bitexact(self, shape):
        rng = np.random.default_rng(3)
        a = lazy_random(rng, shape)
        b = lazy_random(rng, shape)
        got = np.asarray(fp_mul(a, b))
        want = np.asarray(L.mul(a, b))
        assert got.shape == want.shape
        assert (got == want).all()

    def test_matches_oracle_mod_p(self):
        rng = np.random.default_rng(5)
        xs = [int(rng.integers(0, 2**63)) * P // (i + 7) % P for i in range(6)]
        ys = [(x * 31 + 11) % P for x in xs]
        a = np.stack([L.to_limbs(x) for x in xs]).astype(np.int32)
        b = np.stack([L.to_limbs(y) for y in ys]).astype(np.int32)
        out = np.asarray(L.canon(fp_mul(a, b)))
        for i, (x, y) in enumerate(zip(xs, ys)):
            assert L.to_int(out[i]) == x * y % P

    def test_maximal_limbs_do_not_overflow(self):
        a = np.full((4, L.W), (1 << 12), np.int32)
        got = np.asarray(fp_mul(a, a))
        want = np.asarray(L.mul(a, a))
        assert (got == want).all()

    def test_square_and_broadcast(self):
        rng = np.random.default_rng(9)
        a = lazy_random(rng, (3,))
        assert (np.asarray(fp_sq(a)) == np.asarray(L.sq(a))).all()
        one = lazy_random(rng, ())
        got = np.asarray(fp_mul(one, a))  # broadcast leading dims
        want = np.asarray(L.mul(one, a))
        assert (got == want).all()


class TestPallasSq:
    """The dedicated squaring kernel: half the partial products of the
    generic multiply, same column sums, so outputs stay bit-identical."""

    @pytest.mark.parametrize("shape", [(1,), (9,), (2, 3)])
    def test_matches_xla_path_bitexact(self, shape):
        rng = np.random.default_rng(11)
        a = lazy_random(rng, shape)
        assert (np.asarray(fp_sq(a)) == np.asarray(L.sq(a))).all()

    def test_matches_oracle_mod_p(self):
        rng = np.random.default_rng(13)
        xs = [int(rng.integers(0, 2**63)) * P // (i + 3) % P for i in range(5)]
        a = np.stack([L.to_limbs(x) for x in xs]).astype(np.int32)
        out = np.asarray(L.canon(fp_sq(a)))
        for i, x in enumerate(xs):
            assert L.to_int(out[i]) == x * x % P

    def test_maximal_limbs_do_not_overflow(self):
        a = np.full((3, L.W), (1 << 12), np.int32)
        assert (np.asarray(fp_sq(a)) == np.asarray(L.sq(a))).all()


@pytest.mark.kernels
@pytest.mark.slow
class TestFusedTowerKernels:
    """Seeded parity matrix of the fused Fp6/Fp12 tower kernels vs the
    lax tower (tower.py) -- same formulas, same reduction schedules, so
    every int32 limb must match exactly."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_fp6_mul_bitexact(self, seed):
        rng = np.random.default_rng(100 + seed)
        a = lazy_random(rng, (2, 3, 2))
        b = lazy_random(rng, (2, 3, 2))
        got = np.asarray(PK.fp6_mul(a, b))
        want = np.asarray(T.fp6_mul(a, b))
        assert got.shape == want.shape
        assert (got == want).all()

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_fp12_mul_bitexact(self, seed):
        rng = np.random.default_rng(200 + seed)
        a = lazy_random(rng, (2, 2, 3, 2))
        b = lazy_random(rng, (2, 2, 3, 2))
        got = np.asarray(PK.fp12_mul(a, b))
        want = np.asarray(T.fp12_mul(a, b))
        assert got.shape == want.shape
        assert (got == want).all()

    def test_fp12_mul_matches_oracle(self):
        from lighthouse_tpu.crypto.bls.fields_ref import Fp2 as RefFp2
        from lighthouse_tpu.crypto.bls.fields_ref import Fp6 as RefFp6
        from lighthouse_tpu.crypto.bls.fields_ref import Fp12 as RefFp12

        rng = np.random.default_rng(7)

        def ref12():
            def fp2():
                return RefFp2(
                    int(rng.integers(0, 2**62)) * 3 % P,
                    int(rng.integers(0, 2**62)) * 5 % P,
                )

            return RefFp12(
                RefFp6(fp2(), fp2(), fp2()), RefFp6(fp2(), fp2(), fp2())
            )

        x, y = ref12(), ref12()
        a = T.fp12_pack_ref(x)[None]
        b = T.fp12_pack_ref(y)[None]
        out = T.fp12_to_ref(np.asarray(L.canon(PK.fp12_mul(a, b)))[0])
        assert out == x * y

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_cyclotomic_sq_bitexact(self, seed):
        rng = np.random.default_rng(300 + seed)
        a = lazy_random(rng, (2, 2, 3, 2))
        got = np.asarray(PK.fp12_cyclotomic_sq(a))
        want = np.asarray(T.fp12_cyclotomic_sq(a))
        assert got.shape == want.shape
        assert (got == want).all()

    def test_maximal_limbs_do_not_overflow(self):
        a = np.full((2, 2, 3, 2, L.W), (1 << 12), np.int32)
        assert (
            np.asarray(PK.fp12_cyclotomic_sq(a))
            == np.asarray(T.fp12_cyclotomic_sq(a))
        ).all()


@pytest.mark.kernels
@pytest.mark.slow
class TestFusedMillerKernels:
    """Seeded parity of the fused Miller-loop step kernels vs the lax
    scan body (pairing.py): Jacobian point arithmetic + sparse line
    update fused into one kernel, bit-identical limbs out."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_dbl_step_bitexact(self, seed):
        rng = np.random.default_rng(400 + seed)
        f = lazy_random(rng, (2, 2, 3, 2))
        t = lazy_random(rng, (2, 3, 2))
        xp = lazy_random(rng, (2,))
        yp = lazy_random(rng, (2,))
        t_ref, line = TP._dbl_step(t, xp, yp)
        f_ref = TP.mul_by_line(T.fp12_sq(f), line)
        f_got, t_got = PK.miller_dbl_step(f, t, xp, yp)
        assert (np.asarray(f_got) == np.asarray(f_ref)).all()
        assert (np.asarray(t_got) == np.asarray(t_ref)).all()

    @pytest.mark.parametrize("seed", [0, 1])
    def test_add_step_bitexact(self, seed):
        rng = np.random.default_rng(500 + seed)
        f = lazy_random(rng, (2, 2, 3, 2))
        t = lazy_random(rng, (2, 3, 2))
        q = lazy_random(rng, (2, 2, 2))
        xp = lazy_random(rng, (2,))
        yp = lazy_random(rng, (2,))
        t_ref, line = TP._add_step(t, q, xp, yp)
        f_ref = TP.mul_by_line(f, line)
        f_got, t_got = PK.miller_add_step(f, t, q, xp, yp)
        assert (np.asarray(f_got) == np.asarray(f_ref)).all()
        assert (np.asarray(t_got) == np.asarray(t_ref)).all()


def test_env_switch_rebinds_tower_pairing_curve(monkeypatch):
    """LIGHTHOUSE_TPU_PALLAS=1 reroutes the tower multiplies, the Miller
    scan body, and the scalar ladder -- path-distinguishing checks on the
    REBOUND modules (numeric parity is the kernel tests' job)."""
    import sys

    monkeypatch.setenv("LIGHTHOUSE_TPU_PALLAS", "1")
    saved = {
        k: v for k, v in sys.modules.items() if "lighthouse_tpu" in k
    }
    try:
        for k in list(saved):
            del sys.modules[k]
        import lighthouse_tpu.crypto.bls.tpu.curve as fresh_C
        import lighthouse_tpu.crypto.bls.tpu.pairing as fresh_P
        import lighthouse_tpu.crypto.bls.tpu.tower as fresh_T

        # tower multiplies route through the fused kernels
        for fn in (fresh_T.fp6_mul, fresh_T.fp12_mul,
                   fresh_T.fp12_cyclotomic_sq):
            assert "pallas_kernels" in fn.__code__.co_names
        # the Miller scan body takes the fused-step branch
        assert fresh_P._USE_PALLAS is True
        assert fresh_P.PK is sys.modules[
            "lighthouse_tpu.crypto.bls.tpu.pallas_kernels"
        ]
        # the scalar ladder is the windowed re-try
        assert "scalar_mul_u64_windowed" in (
            fresh_C.scalar_mul_u64.__code__.co_names
        )
    finally:
        sys.modules.update(saved)


@pytest.mark.kernels
@pytest.mark.slow
def test_windowed_ladder_matches_bit_ladder():
    """The windowed scalar ladder (re-tried under the Pallas flag; see
    the revert NOTE in curve.py) against the MSB-first bit ladder: same
    points for the same (hi, lo) scalars, including zero.

    slow: the windowed ladder's XLA compile alone runs ~2 min on CPU --
    the same compile blowup that forced the original revert -- so the
    parity proof rides the kernels CI job, not tier-1."""
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.constants import G1_X, G1_Y
    from lighthouse_tpu.crypto.bls.tpu import curve as C

    g = np.stack([L.to_limbs(G1_X), L.to_limbs(G1_Y), L.to_limbs(1)])
    p = jnp.asarray(np.broadcast_to(g, (3,) + g.shape))
    scalars = jnp.asarray(
        np.array([[0, 0], [0, 5], [0x12345678, 0x9ABCDEF1]], np.uint32)
    )
    want = np.asarray(C.scalar_mul_u64(p, scalars, C.FP))
    got = np.asarray(C.scalar_mul_u64_windowed(p, scalars, C.FP))
    # projective representatives may differ; compare affine canon forms
    def affine(pts):
        aff, inf = C.to_affine_g1(jnp.asarray(pts))
        return np.asarray(L.canon(aff)), np.asarray(inf)

    wa, wi = affine(want)
    ga, gi = affine(got)
    assert (wi == gi).all()
    assert (wa[~wi] == ga[~gi]).all()


def test_env_switch_rebinds_mul(monkeypatch):
    """LIGHTHOUSE_TPU_PALLAS=1 swaps limbs.mul to the fused kernel."""
    import sys

    monkeypatch.setenv("LIGHTHOUSE_TPU_PALLAS", "1")
    saved = {
        k: v for k, v in sys.modules.items() if "lighthouse_tpu" in k
    }
    try:
        for k in list(saved):
            del sys.modules[k]
        import lighthouse_tpu.crypto.bls.tpu.limbs as fresh

        # path-distinguishing: the rebound mul must actually route through
        # fp_mul, and sq through the dedicated half-products squaring
        # kernel (the numeric result alone matches on BOTH paths)
        assert "fp_mul" in fresh.mul.__code__.co_names
        assert "fp_sq" in fresh.sq.__code__.co_names
        rng = np.random.default_rng(1)
        a = lazy_random(rng, (2,))
        out = np.asarray(fresh.mul(a, a))
        ref = np.asarray(L.sq(a))
        assert (out == ref).all()
    finally:
        sys.modules.update(saved)


@pytest.mark.parametrize(
    "backend,interpret",
    [("cpu", True), ("tpu", False), ("gpu", None)],
)
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode on the CPU, compiled kernels on a TPU, and a refusal
    elsewhere: no backend silently runs the interpreter as device work."""
    monkeypatch.setattr(PK.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="TPU or the CPU"):
            PK._interpret()
    else:
        assert PK._interpret() is interpret
