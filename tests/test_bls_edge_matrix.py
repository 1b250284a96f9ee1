"""BLS rejection-path matrix across backends (VERDICT r3 item 6;
reference crypto/bls/tests/tests.rs:248-303 +
testing/ef_tests/src/cases/bls_batch_verify.rs semantics): infinity
points, non-subgroup points, x >= p encodings, flag-bit abuse, and
batch-poisoning asserted IDENTICALLY on the cpu oracle and the jax_tpu
kernel."""

import pytest

from lighthouse_tpu.crypto.bls import (
    INFINITY_PUBLIC_KEY,
    INFINITY_SIGNATURE,
    BlsError,
    PublicKey,
    SecretKey,
    Signature,
    SignatureSet,
    set_backend,
    verify_signature_sets,
)
from lighthouse_tpu.crypto.bls import curve_ref as C
from lighthouse_tpu.crypto.bls.constants import P
from lighthouse_tpu.crypto.bls.fields_ref import Fp
from lighthouse_tpu.crypto.bls.hash_to_curve_ref import (
    hash_to_field_fp2,
    map_to_curve_g2,
)

BACKENDS = ["cpu", "jax_tpu"]

# device-path tests: the staged verifier compiles in set-up (conftest)
pytestmark = pytest.mark.usefixtures("staged_verifier")


@pytest.fixture(autouse=True)
def _restore_backend():
    yield
    set_backend("fake")


def non_subgroup_g1_bytes() -> bytes:
    """An on-curve G1 point OUTSIDE the r-torsion (the overwhelming
    majority of curve points: cofactor ~7.6e9), compressed."""
    x = 1
    while True:
        rhs = Fp(x) * Fp(x) * Fp(x) + Fp(4)
        y = rhs.sqrt()
        if y is not None:
            p = C.Point(Fp(x), y)
            assert C.is_on_g1(p)
            if not C.g1_subgroup_check(p):
                return C.g1_to_bytes(p)
        x += 1


def non_subgroup_g2_bytes() -> bytes:
    """On-curve, non-subgroup G2: the SSWU map image BEFORE cofactor
    clearing."""
    u = hash_to_field_fp2(b"edge-matrix", 1)[0]
    p = map_to_curve_g2(u)
    assert C.is_on_g2(p)
    assert not C.g2_subgroup_check(p)
    return C.g2_to_bytes(p)


def valid_set(i: int = 0):
    msg = bytes([i]) * 32
    sk = SecretKey(100 + i)
    return SignatureSet.single_pubkey(sk.sign(msg), sk.public_key(), msg)


class TestDeserializationRejections:
    """Decompression-layer rejections are backend-independent: the api
    validates before any backend sees bytes (generic_public_key.rs
    semantics)."""

    def test_infinity_pubkey_rejected(self):
        with pytest.raises(BlsError):
            PublicKey.from_bytes(INFINITY_PUBLIC_KEY)

    def test_non_subgroup_g1_pubkey_rejected(self):
        with pytest.raises(BlsError, match="subgroup"):
            PublicKey.from_bytes(non_subgroup_g1_bytes())

    def test_x_ge_p_rejected(self):
        # x = p with the compression bit: non-canonical field encoding
        bad = bytearray(P.to_bytes(48, "big"))
        bad[0] |= 0x80
        with pytest.raises(BlsError):
            PublicKey.from_bytes(bytes(bad))
        bad_sig = bytes(bad) + bytes(48)
        with pytest.raises(BlsError):
            Signature.from_bytes(bad_sig)

    def test_uncompressed_flag_rejected(self):
        good = SecretKey(3).public_key().to_bytes()
        bad = bytes([good[0] & 0x7F]) + good[1:]  # clear compression bit
        with pytest.raises(BlsError):
            PublicKey.from_bytes(bad)

    def test_infinity_flag_with_nonzero_body_rejected(self):
        bad = bytearray(INFINITY_PUBLIC_KEY)
        bad[20] = 1
        with pytest.raises(BlsError):
            PublicKey.from_bytes(bytes(bad))
        bad_sig = bytearray(INFINITY_SIGNATURE)
        bad_sig[50] = 1
        with pytest.raises(BlsError):
            Signature.from_bytes(bytes(bad_sig))

    def test_point_not_on_curve_rejected(self):
        # x = 2 has no y on g1 (2^3+4 is a non-residue); flag it compressed
        x = 2
        assert Fp(x * x * x + 4).sqrt() is None
        bad = bytearray(x.to_bytes(48, "big"))
        bad[0] |= 0x80
        with pytest.raises(BlsError):
            PublicKey.from_bytes(bytes(bad))


@pytest.mark.parametrize("backend", BACKENDS)
class TestVerificationRejections:
    """Verification-time rejections: must agree between the pure-Python
    oracle and the TPU kernel."""

    def test_non_subgroup_signature_fails_verify(self, backend):
        set_backend(backend)
        s = valid_set()
        evil = Signature.from_bytes(non_subgroup_g2_bytes())
        forged = SignatureSet.single_pubkey(evil, s.pubkeys[0], s.message)
        assert not verify_signature_sets([forged], seed=3)

    def test_infinity_signature_fails_verify(self, backend):
        set_backend(backend)
        s = valid_set()
        forged = SignatureSet.single_pubkey(
            Signature.infinity(), s.pubkeys[0], s.message
        )
        assert not verify_signature_sets([forged], seed=3)

    def test_empty_batch_is_false(self, backend):
        set_backend(backend)
        assert not verify_signature_sets([], seed=3)

    def test_set_with_no_pubkeys_is_false(self, backend):
        set_backend(backend)
        s = valid_set()
        empty = SignatureSet(s.signature, [], s.message)
        assert not verify_signature_sets([s, empty], seed=3)

    def test_one_forged_set_poisons_the_batch(self, backend):
        set_backend(backend)
        sets = [valid_set(i) for i in range(3)]
        sets[1].message = b"\x66" * 32  # signature no longer matches
        assert not verify_signature_sets(sets, seed=3)
        # and the honest remainder still verifies
        assert verify_signature_sets(
            [sets[0], sets[2]], seed=3
        )

    def test_wrong_pubkey_fails(self, backend):
        set_backend(backend)
        s = valid_set(0)
        other = SecretKey(999).public_key()
        forged = SignatureSet.single_pubkey(s.signature, other, s.message)
        assert not verify_signature_sets([forged], seed=3)


@pytest.mark.parametrize("backend", BACKENDS)
class TestAggregateVerify:
    """spec AggregateVerify: ONE aggregate signature over DISTINCT
    messages -- identical verdicts on the oracle and the kernel."""

    def _claim(self, k=2):
        from lighthouse_tpu.crypto.bls import AggregateSignature

        sks = [SecretKey(50 + i) for i in range(k)]
        msgs = [bytes([i + 1]) * 32 for i in range(k)]
        agg = AggregateSignature.aggregate(
            [sk.sign(m) for sk, m in zip(sks, msgs)]
        )
        return agg.to_signature(), [sk.public_key() for sk in sks], msgs

    def test_valid_claim_verifies(self, backend):
        from lighthouse_tpu.crypto.bls import aggregate_verify

        set_backend(backend)
        sig, pks, msgs = self._claim()
        assert aggregate_verify(sig, pks, msgs)

    def test_swapped_messages_fail(self, backend):
        from lighthouse_tpu.crypto.bls import aggregate_verify

        set_backend(backend)
        sig, pks, msgs = self._claim()
        assert not aggregate_verify(sig, pks, list(reversed(msgs)))

    def test_structural_rejections(self, backend):
        from lighthouse_tpu.crypto.bls import aggregate_verify

        set_backend(backend)
        sig, pks, msgs = self._claim()
        assert not aggregate_verify(sig, pks, msgs[:1])  # length mismatch
        assert not aggregate_verify(sig, [], [])  # empty claim
        assert not aggregate_verify(Signature.infinity(), pks, msgs)

    def test_non_subgroup_signature_fails(self, backend):
        from lighthouse_tpu.crypto.bls import aggregate_verify

        set_backend(backend)
        _, pks, msgs = self._claim()
        evil = Signature.from_bytes(non_subgroup_g2_bytes())
        assert not aggregate_verify(evil, pks, msgs)
