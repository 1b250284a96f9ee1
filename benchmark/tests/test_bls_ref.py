"""The benchmark's own BLS12-381 arithmetic agrees with an independent
implementation: the program's pure-Python oracle."""

import pytest

from benchmark import bls_ref as B


def _oracle_affine_g2(p):
    return ((p.x.c0.n, p.x.c1.n), (p.y.c0.n, p.y.c1.n))


@pytest.mark.parametrize("msg", [b"", b"abc", bytes(range(32))])
def test_hash_to_g2_matches_the_oracle(msg):
    from lighthouse_tpu.crypto.bls.hash_to_curve_ref import hash_to_g2

    assert B.affine(B.FP2, B.hash_to_g2(msg)) == _oracle_affine_g2(hash_to_g2(msg))


@pytest.mark.parametrize("sk", [1, 7, B.R - 1, 0x5EED_1234_5678_9ABC_DEF0])
def test_g1_keys_and_g2_multiples_match_the_oracle(sk):
    from lighthouse_tpu.crypto.bls import curve_ref as C
    from lighthouse_tpu.crypto.bls.hash_to_curve_ref import hash_to_g2

    assert B.g1_compress(B.mul(B.FP, B.G1_GEN, sk)) == C.g1_to_bytes(
        C.g1_generator().mul(sk)
    )
    h = B.hash_to_g2(b"m")
    assert B.affine(B.FP2, B.mul(B.FP2, h, sk)) == _oracle_affine_g2(
        hash_to_g2(b"m").mul(sk)
    )


def test_group_law_corners():
    h = B.hash_to_g2(b"corner")
    assert B.is_inf(B.FP2, B.mul(B.FP2, h, B.R))
    assert B.eq(B.FP2, B.add(B.FP2, h, h), B.dbl(B.FP2, h))
    assert B.is_inf(B.FP2, B.add(B.FP2, h, B.neg(B.FP2, h)))
    assert B.on_curve_g2(B.affine(B.FP2, h))
