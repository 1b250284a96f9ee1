"""Duty-driven precompute & speculative verification (speculate/).

Property tests for the two tentpole halves against a REAL-key chain
harness on the CPU oracle backend, mirroring tests/test_bls_aggregation.py:

  * PARITY: accept/reject through the precompute path (full-bits hit and
    partial-bits incremental correction) is bit-identical to the
    flag-off path, and the substituted aggregate pubkey is the exact
    group sum the backend would have computed per set;
  * SOUNDNESS: planted forgeries -- wrong signer subset under full-bits
    claims, tampered messages, a valid-but-different signature against a
    pre-verified memo entry -- are rejected on BOTH paths and attributed
    through the bisection ("invalid signature"), and a stale shuffling
    key (the reorg-moved-the-seed case) drops the cached epoch and falls
    through to the normal fully-verified path;
  * SCHEDULING: confirm-on-arrival drops the indexed set from the
    dispatched batch (2 sets instead of 3), and the idle gate refuses to
    run while the processor reports pending/deferred/busy work.

Committee shapes stay tiny (16 validators on MINIMAL -> committee size
2) and every verify runs real pairings on the pure-Python oracle: the
precompute substitutes exact group arithmetic, so path selection and
verdict parity are backend-independent, and the oracle keeps this file
free of device compiles (the staged verifier's first jax_tpu compile
costs minutes standalone).
"""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from lighthouse_tpu.chain import attestation_verification as AV
from lighthouse_tpu.chain.attestation_verification import (
    batch_verify_aggregates,
    is_aggregator,
)
from lighthouse_tpu.crypto.bls import (
    AggregateSignature,
    Signature,
    set_backend,
)
from lighthouse_tpu.harness import BeaconChainHarness
from lighthouse_tpu.pool import ObservedAggregates, ObservedAggregators
from lighthouse_tpu.speculate import attach_speculation
from lighthouse_tpu.ssz import uint64
from lighthouse_tpu.state_transition import (
    BlockSignatureStrategy,
    ConsensusContext,
    clone_state,
    process_slots,
)
from lighthouse_tpu.types import (
    ChainSpec,
    MINIMAL,
    compute_epoch_at_slot,
    compute_signing_root,
    get_domain,
    types_for,
)
from lighthouse_tpu.types.chain_spec import (
    DOMAIN_AGGREGATE_AND_PROOF,
    DOMAIN_BEACON_ATTESTER,
    DOMAIN_SELECTION_PROOF,
)
from lighthouse_tpu.types.containers import SigningData
from lighthouse_tpu.utils import metrics as M

pytestmark = pytest.mark.speculate


@pytest.fixture(scope="module")
def env():
    """One signed chain for the whole module: 16 interop validators on
    MINIMAL (committee size 2, one committee per slot -- every epoch-0
    committee is disjoint, so multi-aggregate batches never collide on
    the aggregator-dedup early check). Block-signature verification is
    skipped on import (the blocks are honestly signed; these tests only
    exercise the aggregate gossip path)."""
    set_backend("cpu")
    h = BeaconChainHarness(16, MINIMAL, ChainSpec.interop(), sign=True)
    h.strategy = BlockSignatureStrategy.NO_VERIFICATION
    h.extend_chain(3)
    # attestation producer's view one slot past head: lets it build
    # aggregates for the head slot itself (block root known for slot 3)
    adv = process_slots(clone_state(h.chain.head_state), 4, MINIMAL, h.spec)
    yield SimpleNamespace(h=h, chain=h.chain, adv=adv)
    set_backend("fake")


@pytest.fixture()
def sub(env):
    s = attach_speculation(
        env.chain,
        signature_source=env.h.producer.aggregate_signature_source(),
    )
    yield s
    s.detach()


@pytest.fixture()
def captured(monkeypatch):
    """Spy on the batch dispatch: every verify_signature_sets_async call's
    flattened set list, so tests can assert WHAT reached the backend
    (per-set pubkey counts, dropped indexed sets)."""
    calls: list[list] = []
    real = AV.verify_signature_sets_async

    def spy(sets, **kw):
        calls.append(list(sets))
        return real(sets, **kw)

    monkeypatch.setattr(AV, "verify_signature_sets_async", spy)
    return calls


def _verify(chain, aggs):
    return batch_verify_aggregates(
        chain, aggs, ObservedAggregates(), ObservedAggregators()
    )


def _committee(env, slot: int, index: int = 0):
    epoch = compute_epoch_at_slot(slot, env.h.preset)
    return list(
        ConsensusContext(env.h.preset, env.h.spec)
        .committee_cache(env.adv, epoch)
        .get_beacon_committee(slot, index)
    )


def _make_aggregate(
    env, slot: int, index: int = 0, bits=None, signers=None, sign_root=None
):
    """SignedAggregateAndProof with controllable participation bits and
    signing set (the forgery construction seat): `bits` claims a signer
    subset, `signers` actually signs (defaults to the bit-selected
    members -- honest), `sign_root` substitutes a tampered message. The
    selection proof and outer signature are always REAL, so forgeries
    survive every early check and reach the pairing."""
    prod = env.h.producer
    preset, spec = env.h.preset, env.h.spec
    state = env.adv
    epoch = compute_epoch_at_slot(slot, preset)
    committee = _committee(env, slot, index)
    if bits is None:
        bits = tuple(True for _ in committee)
    if signers is None:
        signers = [v for v, b in zip(committee, bits) if b]
    data = prod.attestation_data_for(state, slot, index)
    domain = get_domain(state, DOMAIN_BEACON_ATTESTER, epoch, preset)
    root = (
        sign_root if sign_root is not None
        else compute_signing_root(data, domain)
    )
    agg = AggregateSignature.aggregate(
        [Signature.from_bytes(prod._sign_root(root, v)) for v in signers]
    )
    t = types_for(preset)
    att = t.Attestation(
        aggregation_bits=bits, data=data, signature=agg.to_bytes()
    )
    sel_domain = get_domain(state, DOMAIN_SELECTION_PROOF, epoch, preset)
    sel_root = SigningData(
        object_root=uint64.hash_tree_root(slot), domain=sel_domain
    ).tree_hash_root()
    for aggregator in committee:
        proof = prod._sign_root(sel_root, aggregator)
        if is_aggregator(len(committee), proof, spec):
            break
    else:
        raise RuntimeError("no aggregator found in committee")
    msg = t.AggregateAndProof(
        aggregator_index=aggregator, aggregate=att, selection_proof=proof
    )
    agg_domain = get_domain(state, DOMAIN_AGGREGATE_AND_PROOF, epoch, preset)
    sig = prod._sign_root(compute_signing_root(msg, agg_domain), aggregator)
    return t.SignedAggregateAndProof(message=msg, signature=sig)


class TestPrecomputePath:
    def test_full_bits_hit_parity_and_zero_aggregation(self, env, sub, captured):
        """A full-participation aggregate: the flag-off path pays per-set
        pubkey aggregation (a multi-pubkey indexed set reaches the
        backend); the precompute path ships ONLY single-pubkey sets --
        zero per-set aggregation -- with an identical accept verdict."""
        agg = _make_aggregate(env, 3)
        sub.enabled = False
        v_off, r_off = _verify(env.chain, [agg])
        off_sets = captured[-1]
        assert len(v_off) == 1 and r_off == []
        assert max(len(s.pubkeys) for s in off_sets) == 2

        hits0 = sub.precompute.stats["full_hits"]
        sub.enabled = True
        v_on, r_on = _verify(env.chain, [agg])
        on_sets = captured[-1]
        assert len(v_on) == 1 and r_on == []
        assert v_on[0].indexed_indices == v_off[0].indexed_indices
        assert len(on_sets) == 3
        assert all(len(s.pubkeys) == 1 for s in on_sets)
        assert sub.precompute.stats["full_hits"] == hits0 + 1

    def test_partial_bits_correction_is_exact(self, env, sub, captured):
        """Partial participation: the incremental correction (full
        aggregate minus absent members) substitutes the EXACT group sum
        of the present members, and the verdict matches the flag-off
        path."""
        agg = _make_aggregate(env, 2, bits=(True, False))
        sub.enabled = False
        v_off, r_off = _verify(env.chain, [agg])
        assert len(v_off) == 1 and r_off == []

        sub.enabled = True
        corr0 = sub.precompute.stats["corrections"]
        v_on, r_on = _verify(env.chain, [agg])
        assert len(v_on) == 1 and r_on == []
        assert sub.precompute.stats["corrections"] == corr0 + 1
        entry = sub.precompute._epochs[0][(2, 0)]
        # dispatch order: selection proof, aggregate-and-proof, indexed
        ind_set = captured[-1][2]
        assert ind_set.pubkeys == [entry.member_pks[0]]

    def test_correction_memoized_per_bit_pattern(self, env, sub):
        """The same partial pattern twice: one correction entry, reused
        (gossip re-sends identical bit patterns)."""
        agg = _make_aggregate(env, 2, bits=(True, False))
        for _ in range(2):
            v, r = _verify(env.chain, [agg])
            assert len(v) == 1 and r == []
        entry = sub.precompute._epochs[0][(2, 0)]
        assert len(entry.corrections) == 1
        assert sub.precompute.stats["corrections"] == 2

    def test_forgery_matrix_rejected_identically_on_both_paths(self, env, sub):
        """Planted forgeries in a batch with an honest aggregate: a
        signature by a SUBSET of the claimed signers (full bits, one
        actual signer) and a signature over a TAMPERED message. Both
        survive the early checks, fail the pairing, and are attributed
        by bisection with the same verdict split on the flag-off and
        precompute paths."""
        good = _make_aggregate(env, 1)
        wrong_subset = _make_aggregate(
            env, 2, signers=[_committee(env, 2)[0]]
        )
        tampered = _make_aggregate(env, 3, sign_root=b"\xEE" * 32)
        batch = [wrong_subset, good, tampered]

        sub.enabled = False
        v_off, r_off = _verify(env.chain, batch)
        sub.enabled = True
        v_on, r_on = _verify(env.chain, batch)

        for verified, rejected in ((v_off, r_off), (v_on, r_on)):
            assert [v.signed_aggregate for v in verified] == [good]
            assert sorted(
                (id(a), reason) for a, reason in rejected
            ) == sorted(
                (id(a), "invalid signature")
                for a in (wrong_subset, tampered)
            )
        assert v_on[0].indexed_indices == v_off[0].indexed_indices

    def test_stale_shuffling_key_invalidates_and_falls_through(self, env, sub):
        """Simulated reorg that moved the attester shuffling: the cached
        entries' seed no longer matches the seed recomputed from the
        verifying state, so lookup drops the WHOLE epoch (counted as
        invalidations), the set misses past the precompute, and the
        aggregate still verifies on the normal path."""
        agg = _make_aggregate(env, 3)
        n_entries = len(sub.precompute._epochs[0])
        sub.precompute._keys[0] = b"\x00" * 32
        for entry in sub.precompute._epochs[0].values():
            entry.shuffling_key = b"\x00" * 32
        inval0 = sub.precompute.stats["invalidations"]
        miss0 = sub.precompute.stats["misses"]
        hits0 = sub.precompute.stats["full_hits"]

        v, r = _verify(env.chain, [agg])
        assert len(v) == 1 and r == []
        assert 0 not in sub.precompute._epochs
        assert sub.precompute.stats["invalidations"] == inval0 + n_entries
        assert sub.precompute.stats["misses"] == miss0 + 1
        assert sub.precompute.stats["full_hits"] == hits0


class TestSpeculativeScheduler:
    def test_confirm_on_arrival_drops_indexed_set(self, env, sub, captured):
        """A speculation pass pre-verifies the expected slot-3 aggregate;
        when the real one arrives the claim confirms by memo lookup and
        the dispatched batch carries only the selection-proof and
        aggregate-and-proof sets."""
        assert sub.verifier.speculate_slot(3) == 1
        assert sub.verifier.stats["preverified"] == 1

        agg = _make_aggregate(env, 3)
        v, r = _verify(env.chain, [agg])
        assert len(v) == 1 and r == []
        assert sub.verifier.stats["confirms"] == 1
        assert sub.verifier.stats["mismatches"] == 0
        assert len(captured[-1]) == 2
        assert all(len(s.pubkeys) == 1 for s in captured[-1])

    def test_valid_but_different_signature_is_never_trusted(self, env, sub):
        """Never trust-on-predict: an aggregate matching a memoized claim
        (same message, bits, committee) but carrying a DIFFERENT
        well-formed signature -- signed by a subset under full bits --
        counts a mismatch, re-verifies on the normal path, and is
        rejected."""
        assert sub.verifier.speculate_slot(3) == 1
        forged = _make_aggregate(env, 3, signers=[_committee(env, 3)[0]])

        v, r = _verify(env.chain, [forged])
        assert v == []
        assert len(r) == 1 and r[0][1] == "invalid signature"
        assert sub.verifier.stats["mismatches"] == 1
        assert sub.verifier.stats["confirms"] == 0

    def test_confirm_miss_falls_through(self, env, sub):
        """No speculation pass ran: arrival is a plain confirm-miss and
        the precompute still serves the aggregate pubkey."""
        agg = _make_aggregate(env, 3)
        v, r = _verify(env.chain, [agg])
        assert len(v) == 1 and r == []
        assert sub.verifier.stats["confirm_misses"] == 1
        assert sub.verifier.stats["confirms"] == 0

    def test_memo_prunes_stale_slots(self, env, sub):
        assert sub.verifier.speculate_slot(3) == 1
        assert len(sub.verifier) == 1
        sub.verifier.prune(5)
        assert len(sub.verifier) == 0

    def test_should_run_gates_on_processor_health(self, env, sub):
        busy = SimpleNamespace(
            health_snapshot=lambda: {
                "pending": 3, "deferred": 0, "busy_workers": 0,
            }
        )
        deferred = SimpleNamespace(
            health_snapshot=lambda: {
                "pending": 0, "deferred": 1, "busy_workers": 0,
            }
        )
        idle = SimpleNamespace(
            health_snapshot=lambda: {
                "pending": 0, "deferred": 0, "busy_workers": 0,
            }
        )
        v = sub.verifier
        v._wait_baseline = M.PROCESSOR_QUEUE_WAIT.snapshot()
        assert v.should_run(busy) is False
        assert v.should_run(deferred) is False
        assert v.should_run(idle) is True

    def test_queue_wait_pressure_defers_and_window_resets(self, env, sub):
        """Queue-wait p95 above the threshold skips the pass AND resets
        the window baseline, so one past storm doesn't gate speculation
        forever."""
        v = sub.verifier
        v._wait_baseline = M.PROCESSOR_QUEUE_WAIT.snapshot()
        M.PROCESSOR_QUEUE_WAIT.observe(10 * v.queue_wait_p95_max)
        assert v.should_run(None) is False
        # the skip re-based the window past the spike
        assert v.should_run(None) is True

    def test_idle_task_counts_runs_and_respects_disable(self, env, sub):
        idle = SimpleNamespace(
            health_snapshot=lambda: {
                "pending": 0, "deferred": 0, "busy_workers": 0,
            }
        )
        sub.processor = idle
        sub.verifier._wait_baseline = M.PROCESSOR_QUEUE_WAIT.snapshot()
        runs0 = sub.verifier.stats["idle_runs"]
        sub.idle_task()
        assert sub.verifier.stats["idle_runs"] == runs0 + 1
        sub.enabled = False
        sub.idle_task()
        assert sub.verifier.stats["idle_runs"] == runs0 + 1
