"""Aggregation-aware batch verification (the mega-pairing).

The aggregated jax_tpu path groups a batch's sets by message, aggregates
the RLC-weighted pubkeys per distinct message, and verifies the whole
batch with ~m + 1 Miller pairs (crypto/bls/aggregation.py). These tests
pin its two contracts:

  * PARITY: accept/reject is bit-identical to the CPU oracle across a
    seeded property matrix of random batch shapes -- n sets over m
    messages, duplicate pubkeys within and across sets, infinity
    aggregate pubkeys, planted forgeries -- and forged items are
    attributed exactly through the O(k log n) bisection.
  * COST SHAPE: the Miller-pair count metric scales with bucketed
    DISTINCT MESSAGES on the aggregated path and with bucketed sets when
    aggregation is disabled (the acceptance criterion of ISSUE 6).

Shapes stay tiny (n <= 8, k <= 2) so the XLA compiles ride the same
warm buckets as the rest of the suite.
"""

import random

import pytest

from lighthouse_tpu.crypto.bls import (
    AggregateSignature,
    PublicKey,
    SecretKey,
    SignatureSet,
    set_backend,
    verify_signature_sets,
)
from lighthouse_tpu.crypto.bls import aggregation as AG
from lighthouse_tpu.crypto.bls.backends import jax_tpu
from lighthouse_tpu.crypto.bls.constants import R
from lighthouse_tpu.utils import metrics as M


# device-path tests: the staged verifier compiles in set-up (conftest)
pytestmark = pytest.mark.usefixtures("staged_verifier")


@pytest.fixture(autouse=True)
def _restore_backend():
    yield
    set_backend("fake")


def _keypair(rng):
    sk = SecretKey(rng.randrange(1, R))
    return sk, sk.public_key()


def _msg(i: int) -> bytes:
    return bytes([i + 1]) * 32


def _good_set(rng, msg, k: int = 1, pool=None):
    """One valid fast_aggregate_verify set; `pool` supplies shared
    keypairs so pubkeys repeat across sets (mainnet attester overlap)."""
    pairs = [
        pool[rng.randrange(len(pool))] if pool else _keypair(rng)
        for _ in range(k)
    ]
    agg = AggregateSignature.aggregate([sk.sign(msg) for sk, _ in pairs])
    return SignatureSet.multiple_pubkeys(
        agg.to_signature(), [pk for _, pk in pairs], msg
    )


def _forged_set(rng, msg, k: int = 1):
    """Signature over a DIFFERENT message than the set claims."""
    pairs = [_keypair(rng) for _ in range(k)]
    agg = AggregateSignature.aggregate(
        [sk.sign(b"\xEE" * 32) for sk, _ in pairs]
    )
    return SignatureSet.multiple_pubkeys(
        agg.to_signature(), [pk for _, pk in pairs], msg
    )


def _both_verdicts(sets, seed):
    set_backend("cpu")
    cpu = verify_signature_sets(sets, seed=seed)
    set_backend("jax_tpu")
    tpu = verify_signature_sets(sets, seed=seed)
    return cpu, tpu


class TestGroupingPlan:
    def test_groups_partition_sets_in_first_seen_order(self):
        rng = random.Random(0)
        sets = [
            _good_set(rng, m)
            for m in (_msg(0), _msg(1), _msg(0), _msg(2), _msg(1), _msg(0))
        ]
        g = AG.group_sets(sets)
        assert g.messages == [_msg(0), _msg(1), _msg(2)]
        assert g.set_message == [0, 1, 0, 2, 1, 0]
        assert g.members == [[0, 2, 5], [1, 4], [3]]
        assert g.max_group() == 3

    def test_grid_masks_padding_slots(self):
        idx, real = AG.group_grid([[0, 2, 5], [3]], m_b=4, g_b=4)
        assert idx.shape == real.shape == (4, 4)
        assert list(idx[0]) == [0, 2, 5, 0] and list(real[0]) == [
            True, True, True, False,
        ]
        assert list(real[1]) == [True, False, False, False]
        assert not real[2:].any()


class TestOracleParity:
    def test_seeded_random_shape_matrix(self):
        """Random (n sets x m messages) batches with duplicate pubkeys and
        0-2 planted forgeries: the aggregated path's verdict matches the
        CPU oracle on every trial, and clean trials accept."""
        rng = random.Random(0xA661)
        pool = [_keypair(rng) for _ in range(4)]
        for trial in range(8):
            n = rng.randrange(2, 9)
            m = rng.randrange(1, n)  # m < n: the aggregated path engages
            n_bad = rng.choice((0, 0, 1, 2))
            sets = [
                _good_set(
                    rng, _msg(rng.randrange(m)), k=rng.randrange(1, 3),
                    pool=pool if rng.random() < 0.5 else None,
                )
                for _ in range(n - n_bad)
            ]
            sets += [
                _forged_set(rng, _msg(rng.randrange(m)))
                for _ in range(n_bad)
            ]
            rng.shuffle(sets)
            cpu, tpu = _both_verdicts(sets, seed=trial)
            assert cpu == tpu, f"trial {trial}: cpu={cpu} tpu={tpu}"
            assert cpu == (n_bad == 0), f"trial {trial}"

    def test_duplicate_pubkeys_within_and_across_sets(self):
        rng = random.Random(7)
        sk, pk = _keypair(rng)
        msg = _msg(0)
        sig = sk.sign(msg)
        double = AggregateSignature.aggregate([sig, sig])
        sets = [
            # the same key counted twice INSIDE one set
            SignatureSet.multiple_pubkeys(double.to_signature(), [pk, pk], msg),
            # and the same key ACROSS sets sharing the message group
            SignatureSet.single_pubkey(sig, pk, msg),
            SignatureSet.single_pubkey(sig, pk, msg),
        ]
        cpu, tpu = _both_verdicts(sets, seed=11)
        assert cpu is True and tpu is True

    def test_infinity_aggregate_pubkey_rejected_identically(self):
        """A set whose pubkeys cancel to infinity (pk + (-pk)) must be
        rejected by BOTH backends even when its message group contains an
        honest set the cancellation could try to hide behind."""
        rng = random.Random(9)
        sk, pk = _keypair(rng)
        neg = PublicKey(-pk.point)
        msg = _msg(0)
        # the signature itself is well-formed; the infinite AGGREGATE
        # pubkey is what must trip the per-set structural check
        bad = SignatureSet.multiple_pubkeys(sk.sign(msg), [pk, neg], msg)
        honest = _good_set(rng, msg)
        cpu, tpu = _both_verdicts([honest, bad], seed=3)
        assert cpu is False and tpu is False

    def test_infinity_signature_rejected_identically(self):
        rng = random.Random(10)
        msg = _msg(0)
        inf_sig = AggregateSignature().to_signature()  # point at infinity
        _, pk = _keypair(rng)
        bad = SignatureSet.single_pubkey(inf_sig, pk, msg)
        cpu, tpu = _both_verdicts([_good_set(rng, msg), bad], seed=4)
        assert cpu is False and tpu is False

    def test_aggregated_and_per_set_paths_agree(self, monkeypatch):
        """The same batch through both device layouts: the mega-pairing
        and the per-set staged path return identical verdicts (they are
        the same product, regrouped)."""
        rng = random.Random(21)
        sets = [_good_set(rng, _msg(i % 2)) for i in range(5)]
        bad = sets + [_forged_set(rng, _msg(0))]
        set_backend("jax_tpu")
        agg = (
            verify_signature_sets(sets, seed=6),
            verify_signature_sets(bad, seed=6),
        )
        monkeypatch.setenv("LIGHTHOUSE_TPU_MSG_AGG", "0")
        per_set = (
            verify_signature_sets(sets, seed=6),
            verify_signature_sets(bad, seed=6),
        )
        assert agg == per_set == (True, False)


class TestFailureAttribution:
    def test_planted_forgeries_attributed_by_bisection(self):
        """The mega-pairing's verdict is all-or-nothing; the bisection
        fallback re-verifies sub-batches through the SAME aggregated
        backend and must pin exactly the planted items."""
        from lighthouse_tpu.chain.attestation_verification import (
            bisect_batch_failures,
        )

        rng = random.Random(0xBAD)
        sets = [_good_set(rng, _msg(i % 3)) for i in range(8)]
        bad_idx = {2, 6}
        for i in bad_idx:
            sets[i] = _forged_set(rng, _msg(i % 3))
        set_backend("jax_tpu")
        assert not verify_signature_sets(sets, seed=1)
        bad_before = M.BLS_BISECTION_BAD_ITEMS.value
        items = list(enumerate(sets))
        ok, bad = bisect_batch_failures(items, lambda item: [item[1]])
        assert {i for i, _ in bad} == bad_idx
        assert {i for i, _ in ok} == set(range(8)) - bad_idx
        assert M.BLS_BISECTION_BAD_ITEMS.value == bad_before + len(bad_idx)


class TestPairingCostShape:
    def test_pair_count_scales_with_messages_not_sets(self):
        """ISSUE 6 acceptance: on the aggregated path the Miller-pair
        metric rides the bucketed MESSAGE count; disabling aggregation
        reverts it to the bucketed SET count for the same batch."""
        rng = random.Random(31)
        sets = [_good_set(rng, _msg(i % 2)) for i in range(8)]
        set_backend("jax_tpu")
        agg_batches = M.BLS_AGGREGATED_BATCHES.value
        pairs_total = M.BLS_MILLER_PAIRS.value
        assert verify_signature_sets(sets, seed=2)
        # 2 distinct messages bucket to 4 -> 5 pairs, NOT bucket(8)+1 = 9
        assert M.BLS_MILLER_PAIRS_LAST.value == jax_tpu._bucket(2) + 1 == 5
        assert M.BLS_MILLER_PAIRS.value == pairs_total + 5
        assert M.BLS_AGGREGATED_BATCHES.value == agg_batches + 1
        assert M.BLS_AGGREGATION_RATIO.value == pytest.approx(8 / 5)

    def test_disabled_aggregation_pays_per_set_pairs(self, monkeypatch):
        monkeypatch.setenv("LIGHTHOUSE_TPU_MSG_AGG", "0")
        rng = random.Random(32)
        sets = [_good_set(rng, _msg(i % 2)) for i in range(8)]
        set_backend("jax_tpu")
        agg_batches = M.BLS_AGGREGATED_BATCHES.value
        assert verify_signature_sets(sets, seed=2)
        assert M.BLS_MILLER_PAIRS_LAST.value == jax_tpu._bucket(8) + 1 == 9
        assert M.BLS_AGGREGATED_BATCHES.value == agg_batches
        assert M.BLS_AGGREGATION_RATIO.value == pytest.approx(8 / 9)

    def test_all_distinct_messages_skip_the_grid(self):
        """m == n leaves nothing to collapse: the marshal returns no grid
        and the per-set path runs (no extra compile shapes)."""
        rng = random.Random(33)
        sets = [_good_set(rng, _msg(i)) for i in range(4)]
        mb = jax_tpu._marshal_batch(sets, seed=1)
        assert mb is not None and mb.grid_idx is None
        assert mb.n_sets == mb.n_messages == 4


class TestPipelinePreMarshalAggregation:
    def test_pipeline_records_aggregate_phase_before_dispatch(self):
        from lighthouse_tpu.crypto.bls.pipeline import VerifyPipeline
        from lighthouse_tpu.resilience.primitives import EventLog

        rng = random.Random(41)
        set_backend("jax_tpu")
        events = EventLog()
        pipe = VerifyPipeline(events=events)
        sets = [_good_set(rng, _msg(i % 2)) for i in range(4)]
        fut = pipe.submit(sets, seed=5)
        assert fut.result() is True
        kinds = events.kinds()
        assert kinds.index("pipeline_aggregate") < kinds.index(
            "pipeline_dispatch"
        )
        assert kinds.index("pipeline_marshal") < kinds.index(
            "pipeline_aggregate"
        )
