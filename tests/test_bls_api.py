"""Backend-pluggable BLS API tests, run against ALL backends.

Mirrors the reference's strategy of running its test suite per backend
(Makefile:109-114 runs ef_tests under blst, fake_crypto, and milagro;
crypto/bls/tests/tests.rs test_suite! macro instantiates per backend).
"""

import random

import pytest

from lighthouse_tpu.crypto.bls import (
    AggregateSignature,
    BlsError,
    PublicKey,
    SecretKey,
    Signature,
    SignatureSet,
    set_backend,
    verify_signature_sets,
)
from lighthouse_tpu.crypto.bls.constants import R

rng = random.Random(7)

# device-path tests: the staged verifier compiles in set-up (conftest)
pytestmark = pytest.mark.usefixtures("staged_verifier")


def keypair():
    sk = SecretKey(rng.randrange(1, R))
    return sk, sk.public_key()


@pytest.fixture(params=["cpu", "jax_tpu"])
def backend(request):
    set_backend(request.param)
    yield request.param
    set_backend("jax_tpu")


class TestSerde:
    def test_pubkey_round_trip(self):
        _, pk = keypair()
        assert PublicKey.from_bytes(pk.to_bytes()) == pk

    def test_signature_round_trip(self):
        sk, _ = keypair()
        sig = sk.sign(b"\x11" * 32)
        assert Signature.from_bytes(sig.to_bytes()) == sig

    def test_infinity_pubkey_rejected(self):
        from lighthouse_tpu.crypto.bls import INFINITY_PUBLIC_KEY

        with pytest.raises(BlsError):
            PublicKey.from_bytes(INFINITY_PUBLIC_KEY)

    def test_infinity_signature_representable(self):
        from lighthouse_tpu.crypto.bls import INFINITY_SIGNATURE

        sig = Signature.from_bytes(INFINITY_SIGNATURE)
        assert sig.is_infinity()
        assert sig.to_bytes() == INFINITY_SIGNATURE


class TestVerify:
    def test_single_good_and_bad(self, backend):
        sk, pk = keypair()
        msg = b"\x22" * 32
        sig = sk.sign(msg)
        good = SignatureSet.single_pubkey(sig, pk, msg)
        assert verify_signature_sets([good], seed=1)
        bad = SignatureSet.single_pubkey(sig, pk, b"\x23" * 32)
        assert not verify_signature_sets([bad], seed=1)

    def test_fast_aggregate_verify(self, backend):
        msg = b"\x33" * 32
        keys = [keypair() for _ in range(4)]
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk, _ in keys])
        s = SignatureSet.multiple_pubkeys(
            agg.to_signature(), [pk for _, pk in keys], msg
        )
        assert verify_signature_sets([s], seed=2)
        # dropping a contributor invalidates
        s_bad = SignatureSet.multiple_pubkeys(
            agg.to_signature(), [pk for _, pk in keys[:3]], msg
        )
        assert not verify_signature_sets([s_bad], seed=2)

    def test_batch_mixed_sets(self, backend):
        # 2 single-key sets, a 2-key set and the forged one: every verify
        # stays in the 4-set bucket that staged_verifier compiled
        batch = []
        for i in range(2):
            sk, pk = keypair()
            msg = bytes([i]) * 32
            batch.append(SignatureSet.single_pubkey(sk.sign(msg), pk, msg))
        msg = b"\x44" * 32
        keys = [keypair() for _ in range(2)]
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk, _ in keys])
        batch.append(
            SignatureSet.multiple_pubkeys(
                agg.to_signature(), [pk for _, pk in keys], msg
            )
        )
        assert verify_signature_sets(batch, seed=3)
        # one wrong signature poisons the whole batch (caller then re-splits,
        # as reference attestation_verification/batch.rs:122-133 does)
        sk_x, pk_x = keypair()
        batch.append(
            SignatureSet.single_pubkey(sk_x.sign(b"\x55" * 32), pk_x, b"\x66" * 32)
        )
        assert not verify_signature_sets(batch, seed=3)

    def test_repeated_messages_dedup_path(self, backend):
        """Batches with repeated messages (the production gossip shape the
        jax backend dedups hash-to-curve work for): distinct signers over
        shared messages verify; one signer on the WRONG shared message
        still poisons the batch (the dedup gather must not conflate
        per-set signatures)."""
        msgs = [b"\x71" * 32, b"\x72" * 32]
        batch = []
        signers = []
        for i in range(6):
            sk, pk = keypair()
            m = msgs[i % 2]
            batch.append(SignatureSet.single_pubkey(sk.sign(m), pk, m))
            signers.append((sk, pk))
        assert verify_signature_sets(batch, seed=11)
        # signer 5 signs msg[1] but the set claims msg[0]
        sk, pk = signers[5]
        batch[5] = SignatureSet.single_pubkey(sk.sign(msgs[1]), pk, msgs[0])
        assert not verify_signature_sets(batch, seed=11)

    def test_infinity_signature_never_verifies(self, backend):
        _, pk = keypair()
        s = SignatureSet.single_pubkey(Signature.infinity(), pk, b"\x00" * 32)
        assert not verify_signature_sets([s], seed=4)

    def test_empty_pubkeys_fails(self, backend):
        sk, _ = keypair()
        s = SignatureSet(sk.sign(b"\x01" * 32), [], b"\x01" * 32)
        assert not verify_signature_sets([s], seed=5)

    def test_fake_backend_accepts_everything(self):
        set_backend("fake")
        try:
            sk, pk = keypair()
            s = SignatureSet.single_pubkey(sk.sign(b"\x0a" * 32), pk, b"\x0b" * 32)
            assert verify_signature_sets([s])
        finally:
            set_backend("jax_tpu")


@pytest.mark.chaos
def test_midbatch_fault_degrades_to_cpu_oracle_and_reprobes():
    """Killing the real jax_tpu backend mid-batch completes
    verify_signature_sets() on the cpu oracle with the unfaulted verdict,
    and the breaker's half-open probe hands the hot path back to jax_tpu
    (same 4-set bucket as the tests above, so no new shape compiles)."""
    from lighthouse_tpu.crypto.bls.backends import cpu, jax_tpu
    from lighthouse_tpu.crypto.bls.backends.fallback import FallbackBackend
    from lighthouse_tpu.resilience import (
        CircuitBreaker,
        EventLog,
        FaultPlan,
        VirtualClock,
    )

    sets = []
    for i in range(2):
        sk, pk = keypair()
        msg = bytes([i]) * 32
        sets.append(SignatureSet.single_pubkey(sk.sign(msg), pk, msg))
    clock, events = VirtualClock(), EventLog()
    plan = FaultPlan(seed=1, clock=clock, events=events)
    plan.fail_next("jax_tpu.verify_signature_sets", 1)
    backend = FallbackBackend(
        primary=plan.wrap(jax_tpu, "jax_tpu"),
        fallback=cpu,
        breaker=CircuitBreaker(
            clock=clock, failure_threshold=1, reset_timeout_s=10.0,
            events=events, name="bls_primary",
        ),
        events=events,
    )
    assert backend.verify_signature_sets(sets, seed=5) is True
    assert backend.breaker.state == CircuitBreaker.OPEN
    assert events.kinds().count("bls_fallback") == 1
    clock.advance(11.0)
    assert backend.verify_signature_sets(sets, seed=5) is True
    assert backend.breaker.state == CircuitBreaker.CLOSED
    assert backend.active_backend_name() == "jax_tpu"
