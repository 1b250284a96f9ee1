"""Resilience layer: deterministic primitives, seeded fault injection,
and graceful degradation across the four applied layers (BLS backend,
eth1 providers, engine API, VC beacon-node fallback) plus sync retries.

The determinism contract: same seed => same fault schedule => same
sequence of retries / breaker transitions / outcomes, asserted by
recording and comparing EventLogs across fresh runs. Chaos-marked tests
also run as a dedicated CI step (.github/workflows/ci.yml)."""

import random

import pytest

from lighthouse_tpu.resilience import (
    BreakerOpen,
    CircuitBreaker,
    EventLog,
    FaultInjected,
    FaultPlan,
    HealthTracker,
    InjectedHang,
    RetryExhausted,
    RetryPolicy,
    Timeout,
    TimeoutExceeded,
    VirtualClock,
)


class FlakyEndpoint:
    """Scriptable callee: fails until `fail_first` calls have happened."""

    def __init__(self, fail_first: int = 0):
        self.fail_first = fail_first
        self.calls = 0

    def fetch(self):
        self.calls += 1
        if self.calls <= self.fail_first:
            raise ConnectionError(f"down (call {self.calls})")
        return f"payload-{self.calls}"


# --- primitives --------------------------------------------------------------


class TestRetryPolicy:
    def test_retries_until_success_with_growing_backoff(self):
        clock = VirtualClock()
        events = EventLog()
        policy = RetryPolicy(
            max_attempts=4, base_delay_s=0.1, jitter=0.0,
            rng=random.Random(1), clock=clock, events=events,
        )
        ep = FlakyEndpoint(fail_first=2)
        assert policy.call(ep.fetch) == "payload-3"
        assert ep.calls == 3
        # exponential, jitter-free: 0.1 + 0.2 advanced on the clock
        assert clock.now() == pytest.approx(0.3)
        assert events.kinds() == ["retry", "backoff", "retry", "backoff"]

    def test_exhausted_budget_raises_chained(self):
        policy = RetryPolicy(max_attempts=2, clock=VirtualClock())
        ep = FlakyEndpoint(fail_first=10)
        with pytest.raises(RetryExhausted):
            policy.call(ep.fetch)
        assert ep.calls == 2  # bounded: the budget is real

    def test_non_retryable_error_propagates_immediately(self):
        policy = RetryPolicy(max_attempts=3, clock=VirtualClock())

        def boom():
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            policy.call(boom)

    def test_jitter_comes_from_injected_rng(self):
        a = RetryPolicy(jitter=0.5, rng=random.Random(9))
        b = RetryPolicy(jitter=0.5, rng=random.Random(9))
        assert [a.delay_for(i) for i in range(4)] == [
            b.delay_for(i) for i in range(4)
        ]


class TestTimeout:
    def test_injected_delay_trips_deadline(self):
        clock = VirtualClock()
        t = Timeout(clock, timeout_s=1.0)

        def slow():
            clock.advance(2.0)  # a FaultPlan delay advances the same way
            return "late"

        with pytest.raises(TimeoutExceeded):
            t.call(slow)
        assert t.call(lambda: "fast") == "fast"


class TestCircuitBreaker:
    def test_lifecycle_closed_open_halfopen_closed(self):
        clock = VirtualClock()
        events = EventLog()
        b = CircuitBreaker(
            clock=clock, failure_threshold=2, reset_timeout_s=10.0,
            events=events,
        )
        assert b.allow()
        b.record_failure()
        assert b.state == CircuitBreaker.CLOSED
        b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert not b.allow()  # re-probe budget not matured
        clock.advance(11.0)
        assert b.allow()  # half-open probe admitted
        assert b.state == CircuitBreaker.HALF_OPEN
        assert not b.allow()  # probe budget spent
        b.record_success()
        assert b.state == CircuitBreaker.CLOSED
        assert b.transitions == [
            ("closed", "open"), ("open", "half_open"), ("half_open", "closed")
        ]
        assert events.kinds() == ["breaker"] * 3

    def test_halfopen_failure_reopens(self):
        clock = VirtualClock()
        b = CircuitBreaker(clock=clock, failure_threshold=1, reset_timeout_s=5)
        b.record_failure()
        clock.advance(6)
        assert b.allow()
        b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert not b.allow()
        clock.advance(6)
        assert b.allow()  # the re-probe budget re-arms after reopening

    def test_clock_free_denied_budget(self):
        b = CircuitBreaker(failure_threshold=1, denied_budget=3)
        b.record_failure()
        denials = [b.allow() for _ in range(3)]
        assert denials == [False, False, True]  # 3rd maturation probes
        assert b.state == CircuitBreaker.HALF_OPEN

    def test_call_wrapper_raises_breaker_open(self):
        b = CircuitBreaker(clock=VirtualClock(), failure_threshold=1)
        with pytest.raises(ConnectionError):
            b.call(lambda: (_ for _ in ()).throw(ConnectionError("x")))
        with pytest.raises(BreakerOpen):
            b.call(lambda: "never runs")


class TestHealthTracker:
    def test_scores_and_ranking(self):
        t = HealthTracker(window=4, threshold=0.5)
        for _ in range(4):
            t.record("a", False)
        t.record("b", True)
        t.record("c", True)
        t.record("c", False)
        assert t.score("a") == 0.0 and not t.is_healthy("a")
        assert t.score("b") == 1.0
        assert t.score("c") == 0.5 and t.is_healthy("c")
        assert t.ranked(["a", "b", "c"])[:2] == ["b", "c"]
        assert t.ranked(["a", "b", "c"])[-1] == "a"  # demoted sinks

    def test_unknown_endpoint_is_optimistic(self):
        t = HealthTracker()
        assert t.score("fresh") == 1.0 and t.is_healthy("fresh")

    def test_demoted_reprobe_after_skips(self):
        t = HealthTracker(window=2, threshold=0.5, reprobe_after_skips=2)
        t.record("a", False)
        t.record("a", False)
        assert not t.eligible("a")
        t.ranked(["a"])  # skip 1
        t.ranked(["a"])  # skip 2 -> budget matured
        assert t.eligible("a")
        # recovery wins the ranking back
        t.record("a", True)
        t.record("a", True)
        assert t.is_healthy("a")

    def test_demoted_reprobe_after_clock_timeout(self):
        clock = VirtualClock()
        t = HealthTracker(
            clock=clock, window=2, threshold=0.5, reprobe_after_s=30.0
        )
        t.record("a", False)
        t.record("a", False)
        assert not t.eligible("a")
        clock.advance(31.0)
        assert t.eligible("a")


# --- fault injection ---------------------------------------------------------


@pytest.mark.chaos
class TestFaultPlan:
    def _drive(self, seed, calls=24):
        clock = VirtualClock()
        plan = FaultPlan(
            seed=seed, error_rate=0.3, delay_rate=0.2, hang_rate=0.1,
            delay_s=0.5, hang_s=60.0, clock=clock,
        )
        ep = plan.wrap(FlakyEndpoint(), "ep")
        outcomes = []
        for _ in range(calls):
            try:
                ep.fetch()
                outcomes.append("ok")
            except InjectedHang:
                outcomes.append("hang")
            except FaultInjected:
                outcomes.append("err")
        return outcomes, plan.events, clock.now()

    def test_same_seed_replays_identical_schedule(self):
        """The determinism contract: same seed => same fault schedule =>
        same outcome sequence AND identical recorded event logs."""
        out_a, log_a, t_a = self._drive(seed=42)
        out_b, log_b, t_b = self._drive(seed=42)
        assert out_a == out_b
        assert log_a == log_b
        assert t_a == t_b
        out_c, _, _ = self._drive(seed=43)
        assert out_a != out_c  # a different seed schedules differently

    def test_scripted_faults_consume_in_order(self):
        plan = FaultPlan(seed=0)
        plan.script("ep.fetch", ["error", "ok", ("delay", 2.0), "hang"])
        clock = VirtualClock()
        plan.clock = clock
        ep = plan.wrap(FlakyEndpoint(), "ep")
        with pytest.raises(FaultInjected):
            ep.fetch()  # the injected error never reaches the target
        assert ep.fetch() == "payload-1"
        assert ep.fetch() == "payload-2"  # scripted delay, then through
        assert clock.now() == pytest.approx(2.0)
        with pytest.raises(InjectedHang):
            ep.fetch()
        assert ep.fetch() == "payload-3"  # script spent; rng says ok

    def test_injected_faults_are_stdlib_transport_errors(self):
        """Narrow handlers in production code (ConnectionError/OSError)
        must treat injected faults like real ones."""
        assert issubclass(FaultInjected, ConnectionError)
        assert issubclass(InjectedHang, TimeoutError)
        assert issubclass(InjectedHang, OSError)

    def test_full_stack_replay_retry_breaker_faults(self):
        """Acceptance: fault schedule + retries + breaker transitions
        replay identically for the same seed (recorded event logs)."""

        def run(seed):
            clock = VirtualClock()
            events = EventLog()
            plan = FaultPlan(
                seed=seed, error_rate=0.45, clock=clock, events=events
            )
            ep = plan.wrap(FlakyEndpoint(), "ep")
            policy = RetryPolicy(
                max_attempts=3, rng=random.Random(seed), clock=clock,
                events=events,
            )
            breaker = CircuitBreaker(
                clock=clock, failure_threshold=2, reset_timeout_s=1.0,
                events=events,
            )
            outcomes = []
            for _ in range(12):
                clock.advance(0.25)
                if not breaker.allow():
                    outcomes.append("open")
                    continue
                try:
                    policy.call(ep.fetch)
                except RetryExhausted:
                    breaker.record_failure()
                    outcomes.append("fail")
                else:
                    breaker.record_success()
                    outcomes.append("ok")
            return outcomes, events

        out_a, log_a = run(7)
        out_b, log_b = run(7)
        assert out_a == out_b
        assert log_a == log_b
        assert len(log_a) > 0


# --- BLS backend graceful degradation ---------------------------------------


@pytest.mark.chaos
class TestBlsFallback:
    def _sets(self):
        from lighthouse_tpu.crypto.bls import SecretKey, SignatureSet

        rng = random.Random(99)
        sets = []
        for i in range(2):
            sk = SecretKey(rng.randrange(1, 2**200))
            msg = bytes([i]) * 32
            sets.append(
                SignatureSet.single_pubkey(sk.sign(msg), sk.public_key(), msg)
            )
        return sets

    def _fallback(self, plan, clock, events):
        """A breaker-guarded FallbackBackend whose primary, in the jax_tpu
        seat, answers through the cpu oracle: the breaker and the degrade
        path are under test here, not the device verifier
        (tests/test_bls_api.py faults the real jax_tpu module)."""
        from types import SimpleNamespace

        from lighthouse_tpu.crypto.bls.backends import cpu
        from lighthouse_tpu.crypto.bls.backends.fallback import (
            FallbackBackend,
        )

        primary = SimpleNamespace(
            verify_signature_sets=cpu.verify_signature_sets
        )
        wrapped = plan.wrap(primary, "jax_tpu")
        breaker = CircuitBreaker(
            clock=clock, failure_threshold=1, reset_timeout_s=10.0,
            events=events, name="bls_primary",
        )
        return FallbackBackend(
            primary=wrapped, fallback=cpu, breaker=breaker, events=events
        )

    def test_midbatch_fault_degrades_to_cpu_oracle_and_reprobes(self):
        """Killing the primary mid-batch completes verify_signature_sets()
        on the cpu backend with results identical to an unfaulted run, and
        the breaker re-probes back to the primary after recovery."""
        from lighthouse_tpu.crypto.bls.backends import cpu

        sets = self._sets()
        expected = cpu.verify_signature_sets(sets, seed=5)  # unfaulted oracle
        assert expected is True

        clock = VirtualClock()
        events = EventLog()
        plan = FaultPlan(seed=1, clock=clock, events=events)
        plan.fail_next("jax_tpu.verify_signature_sets", 1)
        backend = self._fallback(plan, clock, events)

        # batch 1: the injected device fault mid-batch degrades to cpu;
        # the result matches the unfaulted oracle run exactly
        assert backend.verify_signature_sets(sets, seed=5) is expected
        assert backend.breaker.state == CircuitBreaker.OPEN
        assert ("bls_fallback",) == tuple(
            k for k in events.kinds() if k == "bls_fallback"
        )

        # batch 2: breaker open -> straight to cpu, primary not probed
        tpu_calls_before = plan.calls
        assert backend.verify_signature_sets(sets, seed=5) is expected
        assert plan.calls == tpu_calls_before  # no jax_tpu attempt

        # recovery: the reset timeout matures, the half-open probe runs
        # the primary and wins the hot path back
        clock.advance(11.0)
        assert backend.verify_signature_sets(sets, seed=5) is expected
        assert backend.breaker.state == CircuitBreaker.CLOSED
        assert backend.active_backend_name() == "jax_tpu"

    def test_invalid_batch_stays_invalid_through_degradation(self):
        from lighthouse_tpu.crypto.bls import SignatureSet

        sets = self._sets()
        # tamper: swap messages between the two sets
        bad = [
            SignatureSet.single_pubkey(
                sets[0].signature, sets[0].pubkeys[0], sets[1].message
            ),
            sets[1],
        ]
        clock = VirtualClock()
        events = EventLog()
        plan = FaultPlan(seed=2, clock=clock, events=events)
        plan.fail_next("jax_tpu.verify_signature_sets", 1)
        backend = self._fallback(plan, clock, events)
        assert backend.verify_signature_sets(bad, seed=5) is False

    def test_set_backend_fallback_registered(self):
        from lighthouse_tpu.crypto.bls import get_backend_name, set_backend
        from lighthouse_tpu.crypto.bls.backends import fallback

        try:
            set_backend("fallback")
            assert get_backend_name() == "fallback"
            assert fallback.get_default() is fallback.get_default()
        finally:
            set_backend("jax_tpu")


# --- eth1 multi-provider fallback -------------------------------------------


def _deposit(spec, seed):
    from lighthouse_tpu.crypto.bls import SecretKey
    from lighthouse_tpu.types.chain_spec import DOMAIN_DEPOSIT
    from lighthouse_tpu.types.containers import DepositData, DepositMessage
    from lighthouse_tpu.types.helpers import compute_domain, compute_signing_root

    sk = SecretKey(seed)
    msg = DepositMessage(
        pubkey=sk.public_key().to_bytes(),
        withdrawal_credentials=b"\x00" * 32,
        amount=32 * 10**9,
    )
    domain = compute_domain(DOMAIN_DEPOSIT, spec.genesis_fork_version, bytes(32))
    sig = sk.sign(compute_signing_root(msg, domain))
    return DepositData(
        pubkey=msg.pubkey,
        withdrawal_credentials=msg.withdrawal_credentials,
        amount=msg.amount,
        signature=sig.to_bytes(),
    )


@pytest.mark.chaos
class TestEth1Fallback:
    def _twin_chains(self, spec, deposits_at=()):
        """Two MockEth1Providers fed identical add_block sequences hash
        identically (the mock's hash is (number, fork_salt))."""
        from lighthouse_tpu.eth1 import MockEth1Provider

        primary, fallback = MockEth1Provider(), MockEth1Provider()
        schedule = dict(deposits_at)
        for n in range(6):
            ds = schedule.get(n, [])
            primary.add_block(100 + n, ds)
            fallback.add_block(100 + n, ds)
        return primary, fallback

    def test_failover_ranks_and_reprobes(self):
        from lighthouse_tpu.crypto.bls import set_backend
        from lighthouse_tpu.eth1 import Eth1Service, FallbackEth1Provider
        from lighthouse_tpu.types import ChainSpec

        set_backend("fake")
        try:
            spec = ChainSpec.interop()
            d = _deposit(spec, 11)
            primary, fallback = self._twin_chains(spec, {1: [d]}.items())
            events = EventLog()
            plan = FaultPlan(seed=3, events=events)
            # threshold 0.75: ONE failure out of the 2-outcome window
            # demotes, so the dead primary demotes on first contact
            tracker = HealthTracker(
                window=2, threshold=0.75, reprobe_after_skips=1, name="eth1"
            )
            multi = FallbackEth1Provider(
                [plan.wrap(primary, "primary"), fallback],
                tracker=tracker, events=events,
            )
            svc = Eth1Service(multi, follow_distance=0)
            svc.update()
            assert multi.active_index == 0
            assert len(svc.block_cache) == 6

            # primary dies: calls fail over to the ranked fallback
            plan.fail_next("primary", 50)
            svc.update()
            assert multi.active_index == 1
            assert not tracker.is_healthy(0)
            assert len(svc.deposit_tree.leaves) == 1
            assert "eth1_endpoint_switch" in events.kinds()
        finally:
            set_backend("jax_tpu")

    def test_reorg_rewind_with_lagging_fallback(self):
        """Acceptance: the reorg rewind stays correct when the fallback
        endpoint is BEHIND the primary. Sequence: primary serves 6
        blocks; primary dies and the service fails over to a fallback
        that only has 4; both chains reorg; the primary recovers. The
        deposit tree must end exactly at the canonical logs -- the
        reorged-out deposit gone, the replacement present."""
        from lighthouse_tpu.crypto.bls import set_backend
        from lighthouse_tpu.eth1 import (
            DepositDataTree,
            Eth1Service,
            FallbackEth1Provider,
            MockEth1Provider,
        )
        from lighthouse_tpu.types import ChainSpec

        set_backend("fake")
        try:
            spec = ChainSpec.interop()
            d1, d2, d3 = (_deposit(spec, s) for s in (21, 22, 23))
            primary, fallback = MockEth1Provider(), MockEth1Provider()
            # identical first 4 blocks (d1 early); primary runs 2 ahead
            # with d2 in block 4
            for chain in (primary, fallback):
                chain.add_block(100, [d1])
                for n in range(1, 4):
                    chain.add_block(100 + n)
            primary.add_block(104, [d2])
            primary.add_block(105)

            plan = FaultPlan(seed=4)
            tracker = HealthTracker(
                window=2, threshold=0.5, reprobe_after_skips=1, name="eth1"
            )
            multi = FallbackEth1Provider(
                [plan.wrap(primary, "primary"), fallback], tracker=tracker
            )
            svc = Eth1Service(multi, follow_distance=0)
            svc.update()
            assert len(svc.block_cache) == 6
            assert len(svc.deposit_tree.leaves) == 2  # d1 + d2

            # primary dies; the lagging fallback (4 blocks, no d2) takes
            # over: the service sees the shorter view as a rewind and
            # truncates the tree back past d2
            plan.fail_next("primary", 50)
            svc.update()
            assert len(svc.block_cache) == 4
            assert len(svc.deposit_tree.leaves) == 1

            # both chains reorg the top 2 blocks of their shared prefix;
            # the canonical replacement carries d3. The primary recovers
            # (script exhausted) AFTER the fallback already served the
            # reorged view.
            primary.reorg(4)
            fallback.reorg(2)
            for chain in (primary, fallback):
                chain.add_block(110, [d3])
                chain.add_block(111)
            plan.clear_scripts()  # primary back up
            svc.update()
            svc.update()  # second poll re-extends after any mid-poll race

            canonical = DepositDataTree()
            canonical.push(d1)
            canonical.push(d3)
            assert svc.deposit_tree.root() == canonical.root()
            assert [b.hash for b in svc.block_cache] == [
                b.hash for b in primary.blocks
            ]
        finally:
            set_backend("jax_tpu")


# --- engine API retry / optimistic degrade ----------------------------------


@pytest.mark.chaos
class TestEngineRetry:
    def _engine_el(self, **kw):
        from lighthouse_tpu.execution_layer import ExecutionLayer
        from lighthouse_tpu.execution_layer.mock_engine import (
            MockExecutionEngine,
        )
        from lighthouse_tpu.types import MINIMAL, types_for

        engine = MockExecutionEngine(types_for(MINIMAL))
        el = ExecutionLayer(engine, **kw)
        return engine, el

    def _payload(self, engine, el):
        payload = el.get_payload(
            engine.genesis_hash, timestamp=7, prev_randao=b"\x01" * 32
        )
        return payload

    def test_syncing_retries_then_valid(self):
        """SYNCING drains through the re-poll budget: an engine that
        catches up within the backoff window yields VERIFIED instead of
        a needless optimistic import."""
        from lighthouse_tpu.execution_layer import PayloadVerificationStatus

        clock = VirtualClock()
        engine, el = self._engine_el(
            retry_policy=RetryPolicy(max_attempts=2, clock=clock, jitter=0.0),
            syncing_retry_attempts=2,
        )
        payload = self._payload(engine, el)
        engine.force_syncing = 2
        assert (
            el.notify_new_payload(payload)
            is PayloadVerificationStatus.VERIFIED
        )
        assert engine.force_syncing == 0
        assert clock.now() > 0  # backoff advanced the injected clock

    def test_syncing_budget_exhausted_degrades_optimistic(self):
        from lighthouse_tpu.execution_layer import PayloadVerificationStatus

        engine, el = self._engine_el(
            retry_policy=RetryPolicy(max_attempts=2, clock=VirtualClock()),
            syncing_retry_attempts=1,
        )
        payload = self._payload(engine, el)
        engine.force_syncing = 10
        assert (
            el.notify_new_payload(payload)
            is PayloadVerificationStatus.OPTIMISTIC
        )

    def test_transport_faults_retry_then_degrade_optimistic(self):
        from lighthouse_tpu.execution_layer import PayloadVerificationStatus

        clock = VirtualClock()
        engine, el = self._engine_el(
            retry_policy=RetryPolicy(max_attempts=3, clock=clock)
        )
        payload = self._payload(engine, el)
        plan = FaultPlan(seed=5, clock=clock)
        el.engine = plan.wrap(engine, "engine")

        # transient: one injected fault, the retry lands
        plan.fail_next("engine.new_payload", 1)
        assert (
            el.notify_new_payload(payload)
            is PayloadVerificationStatus.VERIFIED
        )
        # hard outage: budget exhausted -> optimistic, never an exception
        plan.fail_next("engine.new_payload", 10)
        assert (
            el.notify_new_payload(payload)
            is PayloadVerificationStatus.OPTIMISTIC
        )

    def test_production_path_fails_loudly_after_retries(self):
        from lighthouse_tpu.resilience import RetryExhausted

        clock = VirtualClock()
        engine, el = self._engine_el(
            retry_policy=RetryPolicy(max_attempts=2, clock=clock)
        )
        plan = FaultPlan(seed=6, clock=clock)
        el.engine = plan.wrap(engine, "engine")
        plan.fail_next("engine.forkchoice_updated", 10)
        with pytest.raises(RetryExhausted):
            self._payload(engine, el)


# --- VC beacon-node fallback -------------------------------------------------


class _StubNode:
    def __init__(self, name, healthy=True):
        self.name = name
        self._healthy = healthy
        self.calls = 0

    def is_healthy(self):
        return self._healthy

    def duty(self):
        self.calls += 1
        return self.name


@pytest.mark.chaos
class TestBeaconNodeFallback:
    def test_health_scored_ranking_demotes_failing_node(self):
        from lighthouse_tpu.validator_client import BeaconNodeFallback

        a, b = _StubNode("a"), _StubNode("b")
        fb = BeaconNodeFallback(
            [a, b],
            tracker=HealthTracker(
                window=2, threshold=0.5, reprobe_after_skips=10
            ),
        )

        def flaky_a(node):
            if node.name == "a":
                raise ConnectionError("a is down")
            return node.duty()

        # a fails -> demoted below b despite listing order
        assert fb.call(flaky_a) == "b"
        assert fb.call(flaky_a) == "b"
        assert fb.ranked()[0] is b
        # b keeps winning WITHOUT a eating the first try (a's re-probe
        # budget, 10 passes, has not matured)
        b_calls = b.calls
        assert fb.call(lambda n: n.duty()) == "b"
        assert b.calls == b_calls + 1

    def test_demoted_node_reprobes_and_recovers(self):
        from lighthouse_tpu.validator_client import BeaconNodeFallback

        a, b = _StubNode("a"), _StubNode("b")
        tracker = HealthTracker(window=2, threshold=0.5, reprobe_after_skips=1)
        fb = BeaconNodeFallback([a, b], tracker=tracker)
        tracker.record(0, False)
        tracker.record(0, False)
        assert fb.ranked()[0] is b  # demoted; this pass spends a's skip
        # the budget matured: the next ranking boosts a to the front for
        # one real probe, whose success immediately re-scores it
        assert fb.call(lambda n: n.duty()) == "a"
        assert tracker.score(0) > 0.0
        assert tracker.is_healthy(0)

    def test_in_process_node_health_is_scored(self):
        """The old test-only boolean now drives the real HealthTracker
        scoring path (validator_client/beacon_node.py)."""
        from lighthouse_tpu.crypto.bls import set_backend
        from lighthouse_tpu.harness import BeaconChainHarness
        from lighthouse_tpu.types import MINIMAL, ChainSpec
        from lighthouse_tpu.validator_client import InProcessBeaconNode

        set_backend("fake")
        try:
            h = BeaconChainHarness(16, MINIMAL, ChainSpec.interop())
            node = InProcessBeaconNode(h.chain)
            assert node.is_healthy()  # optimistic start
            node.healthy = False  # the toggle floods the outcome window
            assert not node.is_healthy()
            assert node.health.score("self") == 0.0
            node.record_health(True)  # partial recovery: 1/4 < threshold
            assert not node.is_healthy()
            node.healthy = True
            assert node.is_healthy()
            assert node.health.score("self") == 1.0
        finally:
            set_backend("jax_tpu")


# --- sync / range-request retries under injected faults ----------------------


@pytest.mark.chaos
class TestSyncChaos:
    def test_range_sync_retries_through_injected_bus_faults(self):
        """A late joiner syncs to head through a bus that injects
        deterministic transport faults into req/resp: the sync manager's
        peer rotation + retry budget absorbs them."""
        from lighthouse_tpu.chain.beacon_chain import BeaconChain
        from lighthouse_tpu.crypto.bls import set_backend
        from lighthouse_tpu.network import NetworkNode, Simulator
        from lighthouse_tpu.store.hot_cold import HotColdDB
        from lighthouse_tpu.store.kv import MemoryStore
        from lighthouse_tpu.types import (
            MINIMAL,
            ChainSpec,
            interop_genesis_state,
        )

        set_backend("fake")
        try:
            sim = Simulator(2, 64, MINIMAL, ChainSpec.interop())
            sim.run_epochs(2, attest=False)

            # a fault-injecting view of the SAME bus for the late joiner
            plan = FaultPlan(seed=8, error_rate=0.25)
            faulty_bus = plan.wrap(sim.bus, "bus", methods=("request",))
            genesis = interop_genesis_state(64, MINIMAL, sim.spec)
            store = HotColdDB(MemoryStore(), MINIMAL, sim.spec)
            chain = BeaconChain(store, genesis, MINIMAL, sim.spec)
            late = NetworkNode("late", chain, faulty_bus)

            # each round re-ranks peers (the per-slot sync tick); the
            # injected fault schedule is deterministic, so convergence
            # within the budget is a repeatable fact, not flakiness
            imported = 0
            for _ in range(6):
                imported += late.range_sync()
                if late.chain.head_root == sim.nodes[0].chain.head_root:
                    break
            assert imported > 0
            assert late.chain.head_root == sim.nodes[0].chain.head_root
            assert plan.injected > 0  # faults actually fired
        finally:
            set_backend("jax_tpu")

    def test_simulator_chaos_mode_wraps_bus(self):
        from lighthouse_tpu.network import Simulator
        from lighthouse_tpu.crypto.bls import set_backend
        from lighthouse_tpu.types import MINIMAL, ChainSpec

        set_backend("fake")
        try:
            plan = FaultPlan(seed=9, error_rate=0.0)
            sim = Simulator(2, 64, MINIMAL, ChainSpec.interop(), fault_plan=plan)
            sim.run_epochs(1, attest=False)
            sim.check_all_heads_equal()
        finally:
            set_backend("jax_tpu")
