"""Algebraic self-tests for the pure-Python BLS12-381 oracle.

No external test vectors are available in this environment (EF
consensus-spec-tests are a multi-GB download), so correctness is enforced the
way a spec implementation can self-verify: parameter identities, on-curve and
subgroup membership at every stage, bilinearity and non-degeneracy of the
pairing, and serialization round-trips. Mirrors the intent of
crypto/bls/tests/tests.rs and testing/ef_tests' bls handlers in the reference.
"""

import random

import pytest

from lighthouse_tpu.crypto.bls.constants import BLS_X, DST, P, R
from lighthouse_tpu.crypto.bls import curve_ref as cv
from lighthouse_tpu.crypto.bls import hash_to_curve_ref as h2c
from lighthouse_tpu.crypto.bls import pairing_ref as pr
from lighthouse_tpu.crypto.bls.fields_ref import Fp, Fp2, Fp12


class TestParameters:
    def test_bls_family_identities(self):
        x = BLS_X
        assert R == x**4 - x**2 + 1
        assert P == (x - 1) ** 2 * R // 3 + x

    def test_p_mod(self):
        assert P % 4 == 3  # enables the sqrt shortcuts
        assert (P * P) % 16 == 9  # enables the Fp2 sqrt_ratio chain


_rng = random.Random(5)


class TestFields:
    @pytest.mark.parametrize(
        "n",
        [1, P - 1, 2**380] + [_rng.randrange(2, P - 1) for _ in range(4)],
        ids=["1", "p-1", "2^380"] + [f"random{i}" for i in range(4)],
    )
    def test_fp_inv_matches_fermat(self, n):
        x = Fp(n)
        assert x.inv().n == pow(x.n, P - 2, P)
        assert x * x.inv() == Fp.one()

    def test_fp_inv_of_zero_raises(self):
        with pytest.raises(ZeroDivisionError):
            Fp(0).inv()

    def test_fp2_inv_mul(self):
        a = Fp2(1234567, 7654321)
        assert a * a.inv() == Fp2.one()

    def test_fp2_sqrt_roundtrip(self):
        a = Fp2(987654321, 123456789)
        sq = a.sq()
        s = sq.sqrt()
        assert s is not None and s.sq() == sq

    def test_fp12_inv_frobenius(self):
        # build a generic Fp12 element from pairing output
        f = pr.pairing(cv.g1_generator(), cv.g2_generator())
        assert f * f.inv() == Fp12.one()
        # Frobenius must be the p-power map: check via f^(p) on a cyclotomic el
        assert f.frobenius(12) == f
        assert f.frobenius(6) == f.conj()  # cyclotomic: f^(p^6) = f^-1 = conj


class TestCurve:
    def test_generators_on_curve_and_in_subgroup(self):
        g1, g2 = cv.g1_generator(), cv.g2_generator()
        assert cv.is_on_g1(g1) and cv.is_on_g2(g2)
        assert g1.mul(R).inf and g2.mul(R).inf

    def test_group_law(self):
        g = cv.g1_generator()
        assert g.double() + g == g.mul(3)
        assert (g.mul(5) + g.mul(7)) == g.mul(12)
        assert (g + (-g)).inf

    def test_psi_subgroup_check_matches_definition(self):
        g2 = cv.g2_generator()
        for k in (1, 2, 12345, R - 1):
            assert cv.g2_subgroup_check_psi(g2.mul(k))
        # a point on the curve but (whp) outside the subgroup
        x = Fp2(1, 0)
        while True:
            y2 = x * x * x + Fp2(4, 4)
            y = y2.sqrt()
            if y is not None:
                break
            x = x + Fp2.one()
        q = cv.Point(x, y, False)
        assert cv.is_on_g2(q)
        assert cv.g2_subgroup_check_psi(q) == cv.g2_subgroup_check(q)
        assert not cv.g2_subgroup_check_psi(q)

    def test_clear_cofactor_lands_in_subgroup(self):
        x = Fp2(7, 11)
        while True:
            y2 = x * x * x + Fp2(4, 4)
            y = y2.sqrt()
            if y is not None:
                break
            x = x + Fp2.one()
        q = cv.clear_cofactor_g2(cv.Point(x, y, False))
        assert cv.is_on_g2(q) and cv.g2_subgroup_check(q)

    def test_serialization_roundtrip_g1(self):
        for k in (1, 2, 31415926):
            p = cv.g1_generator().mul(k)
            assert cv.g1_from_bytes(cv.g1_to_bytes(p)) == p
        inf = cv.Point(Fp.zero(), Fp.zero(), True)
        assert cv.g1_from_bytes(cv.g1_to_bytes(inf)).inf

    def test_serialization_roundtrip_g2(self):
        for k in (1, 2, 271828182):
            p = cv.g2_generator().mul(k)
            assert cv.g2_from_bytes(cv.g2_to_bytes(p)) == p
        inf = cv.Point(Fp2.zero(), Fp2.zero(), True)
        assert cv.g2_from_bytes(cv.g2_to_bytes(inf)).inf

    def test_deserialize_rejects_bad(self):
        with pytest.raises(cv.DeserializeError):
            cv.g1_from_bytes(bytes(48))  # no compression bit
        # find an x with x^3 + 4 a non-square, serialize it, expect rejection
        x = 1
        while Fp(x * x * x + 4).sqrt() is not None:
            x += 1
        bad = bytearray(x.to_bytes(48, "big"))
        bad[0] |= 0x80
        with pytest.raises(cv.DeserializeError):
            cv.g1_from_bytes(bytes(bad))
        # x >= P must be rejected too
        overflow = bytearray((P + 1).to_bytes(48, "big"))
        overflow[0] |= 0x80
        with pytest.raises(cv.DeserializeError):
            cv.g1_from_bytes(bytes(overflow))


class TestPairing:
    def test_non_degenerate(self):
        e = pr.pairing(cv.g1_generator(), cv.g2_generator())
        assert e != Fp12.one()
        assert e.pow(R) == Fp12.one()

    def test_bilinearity(self):
        g1, g2 = cv.g1_generator(), cv.g2_generator()
        e = pr.pairing(g1, g2)
        assert pr.pairing(g1.mul(2), g2) == e.pow(2)
        assert pr.pairing(g1, g2.mul(3)) == e.pow(3)
        assert pr.pairing(g1.mul(5), g2.mul(7)) == e.pow(35)

    def test_infinity_neutral(self):
        g1, g2 = cv.g1_generator(), cv.g2_generator()
        inf1 = cv.Point(Fp.zero(), Fp.zero(), True)
        assert pr.pairing(inf1, g2) == Fp12.one()

    def test_multi_pairing_product(self):
        g1, g2 = cv.g1_generator(), cv.g2_generator()
        # e(aG1, G2) * e(-aG1, G2) == 1
        a = 123456789
        out = pr.multi_pairing([(g1.mul(a), g2), (-(g1.mul(a)), g2)])
        assert out == Fp12.one()
        # e(aG1, bG2) * e(-G1, abG2) == 1  (the verify equation shape)
        b = 987654321
        out = pr.multi_pairing([(g1.mul(a), g2.mul(b)), (-g1, g2.mul(a * b % R))])
        assert out == Fp12.one()


class TestHashToCurve:
    def test_expand_message_xmd_shape(self):
        out = h2c.expand_message_xmd(b"abc", DST, 256)
        assert len(out) == 256
        # deterministic
        assert out == h2c.expand_message_xmd(b"abc", DST, 256)

    def test_sswu_output_on_isogenous_curve(self):
        for msg in (b"", b"abc", b"lighthouse-tpu"):
            (u0, u1) = h2c.hash_to_field_fp2(msg, 2)
            for u in (u0, u1):
                x, y = h2c.map_to_curve_sswu_prime(u)
                lhs = y.sq()
                rhs = (x.sq() + h2c._A) * x + h2c._B
                assert lhs == rhs, "SSWU image must satisfy E2' equation"

    def test_iso_image_on_e2(self):
        """The strongest available check on the ISO3 constants: points mapped
        through the isogeny must land exactly on E2."""
        for msg in (b"", b"abc", b"a" * 100, b"\x00" * 32):
            (u0, u1) = h2c.hash_to_field_fp2(msg, 2)
            for u in (u0, u1):
                p = h2c.map_to_curve_g2(u)
                assert cv.is_on_g2(p), "ISO3 constants are inconsistent"

    def test_hash_to_g2_in_subgroup(self):
        p = h2c.hash_to_g2(b"lighthouse-tpu test message")
        assert cv.is_on_g2(p)
        assert cv.g2_subgroup_check(p)

    def test_hash_distinct_messages_distinct_points(self):
        assert h2c.hash_to_g2(b"m1") != h2c.hash_to_g2(b"m2")

    def test_signature_scheme_shape(self):
        """sign/verify round-trip at the pairing level: e(pk, H(m)) == e(g1, sig)."""
        sk = 0x1234567890ABCDEF1234567890ABCDEF
        g1 = cv.g1_generator()
        pk = g1.mul(sk)
        h = h2c.hash_to_g2(b"attestation data root")
        sig = h.mul(sk)
        lhs = pr.multi_pairing([(pk, h), (-g1, sig)])
        assert lhs == Fp12.one()
