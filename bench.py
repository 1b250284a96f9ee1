"""North-star benchmark: batched BLS signature-set verification on TPU.

Default mode measures on a TPU and nowhere else: without a chip it
exits non-zero and prints no chip-metric number. One process, no CPU
fallback, no retries. (`python chip_smoke.py` is the quickest proof that
the main path runs on the chip at all.)

Metric: aggregate-attestation signature sets verified per second on one
chip, against the BASELINE.md target ("batch-verify 10k aggregate
attestation signatures in <200 ms on a single TPU v4 chip", i.e. 50k
sets/s). vs_baseline = achieved_sets_per_s / 50_000.

Methodology: one warm jitted call over a bucket of synthetic
fast_aggregate_verify sets (distinct messages, multi-pubkey aggregates,
pre-marshaled device inputs -- steady-state marshaling is index gathers
from the device-resident pubkey table, so the kernel is the contract).
Fixtures come from the pure-Python oracle and are tiled to the
requested batch size (tiling valid sets keeps the batch valid and the
per-set device work identical).

The other modes (--profile, --speculate, --latency, --scale, --serving)
run where they are started, BENCH_PLATFORM=cpu forcing the CPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TARGET_SETS_PER_S = 10_000 / 0.200  # BASELINE.md north star


def _emit(payload: dict) -> None:
    print(json.dumps(payload))
    sys.stdout.flush()


def _force_platform() -> None:
    """Apply BENCH_PLATFORM=cpu through the live config (the non-default
    modes only; the default mode refuses anything but a TPU)."""
    if os.environ.get("BENCH_PLATFORM") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")


def measure() -> int:
    """The default mode: the chip metric, measured on a TPU or not at
    all."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(
            f"bench: no TPU (JAX platform {platform!r}); the chip metric "
            "is measured on a TPU only",
            file=sys.stderr,
        )
        return 2
    child()
    return 0


def child() -> None:
    n_sets = int(os.environ.get("BENCH_SETS", "1024"))
    k_pk = int(os.environ.get("BENCH_PUBKEYS_PER_SET", "2"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    distinct = int(os.environ.get("BENCH_DISTINCT", "32"))

    sys.path.insert(0, HERE)
    import jax

    from __graft_entry__ import _example_batch
    from lighthouse_tpu.utils.compile_cache import arm

    arm()
    from lighthouse_tpu.crypto.bls.backends.jax_tpu import (
        _bucket,
        verify_device,
    )

    t0 = time.perf_counter()
    args = _example_batch(n_sets, k_pk, distinct=distinct, dedup=True)
    fixture_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ok = bool(jax.block_until_ready(verify_device(*args)))
    compile_s = time.perf_counter() - t0
    assert ok, "bench batch failed to verify"

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(verify_device(*args))
        times.append(time.perf_counter() - t0)
    best = min(times)
    sets_per_s = n_sets / best

    # Pipelined throughput + counters: the same warm kernel driven
    # through the async VerifyPipeline (double-buffered submit_call), so
    # the artifact carries the pipeline's observable surface — depth,
    # occupancy high-water, batch count — next to the blocking number.
    # The run also exports a Chrome trace artifact (utils/tracing.py):
    # pipeline submit/resolve spans on a real wall clock, loadable in
    # Perfetto next to the JSON number.
    import random as _random

    from lighthouse_tpu.crypto.bls.pipeline import VerifyPipeline
    from lighthouse_tpu.obs import ledger as launch_ledger
    from lighthouse_tpu.utils import metrics as M
    from lighthouse_tpu.utils import tracing

    class _PerfClock:
        # bench is an injection boundary: wall time enters HERE and is
        # handed to the tracer as an injected clock
        def now(self):
            return time.perf_counter()

    tracer = tracing.configure(clock=_PerfClock(), rng=_random.Random(0))
    led = launch_ledger.configure()  # rides the same injected clock
    trace_path = os.path.join(HERE, ".bench_trace.json")

    pipe_batches = int(os.environ.get("BENCH_PIPELINE_BATCHES", "4"))
    pipe = VerifyPipeline(depth=2)  # spans ride the configured tracer
    t0 = time.perf_counter()
    with tracer.span("bench_pipeline", batches=pipe_batches, sets=n_sets):
        futs = [
            pipe.submit_call(verify_device, *args, n_sets=n_sets)
            for _ in range(pipe_batches)
        ]
        pipe_ok = all(f.result() for f in futs)
    pipe_s = time.perf_counter() - t0
    try:
        # one Perfetto document: the span "X" events plus the ledger's
        # per-kind counter tracks (real vs padded set counts over time)
        trace_doc = tracer.chrome_trace()
        trace_doc["traceEvents"].extend(led.chrome_counter_events())
        with open(trace_path, "w") as f:
            f.write(json.dumps(trace_doc, sort_keys=True))
        trace_events = tracer.status()["recorded"]
    except OSError:
        trace_path, trace_events = None, 0

    _emit(
        {
            "metric": "bls_signature_sets_verified_per_s_per_chip",
            "value": round(sets_per_s, 2),
            "unit": "sets/s",
            "vs_baseline": round(sets_per_s / TARGET_SETS_PER_S, 4),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "n_sets": n_sets,
            "pubkeys_per_set": k_pk,
            "distinct_messages": min(distinct, n_sets),
            "fixture_s": round(fixture_s, 2),
            "compile_s": round(compile_s, 2),
            # keyed by the dispatcher's bucketed shape (n x k x m x g;
            # g=0 is the per-set path) -- the same names `cli warm`
            # publishes on tpu_warm_compile_seconds
            "compile_s_per_bucket": {
                "x".join(
                    str(v)
                    for v in (
                        _bucket(n_sets),
                        _bucket(k_pk),
                        _bucket(min(distinct, n_sets)),
                        0,
                    )
                ): round(compile_s, 2)
            },
            "steady_s": round(best, 4),
            "pipeline": {
                "depth": int(M.BLS_PIPELINE_DEPTH.value),
                "batches": pipe_batches,
                "occupancy_peak": int(M.BLS_PIPELINE_OCCUPANCY_PEAK.value),
                "all_valid": bool(pipe_ok),
                "pipelined_sets_per_s": round(
                    pipe_batches * n_sets / pipe_s, 2
                ),
                "shard_mesh_devices": int(M.BLS_SHARD_MESH_SIZE.value),
                "bisection_calls": int(M.BLS_BISECTION_CALLS.value),
            },
            "device_telemetry": {
                "compile_cache_misses": int(
                    M.TPU_COMPILE_CACHE_MISSES.value
                ),
                "compile_cache_hits": int(M.TPU_COMPILE_CACHE_HITS.value),
                "transfer_bytes_total": int(M.TPU_TRANSFER_BYTES.value),
            },
            "trace_path": trace_path,
            "trace_events": trace_events,
        }
    )


def profile_child() -> None:
    """The mainnet-shaped traffic profile (ISSUE 6): one batch of n sets
    over d distinct messages through BOTH device layouts -- the per-set
    staged path (~n+1 Miller pairs) and the message-aggregated
    mega-pairing (~d+1 pairs) -- reporting pairing counts, the
    aggregation ratio, and the sets/s of each. Real attestation traffic
    is thousands of sets over a handful of messages, so the speedup here
    is the sets/s multiplier the aggregation buys at mainnet shapes."""
    sys.path.insert(0, HERE)
    import jax

    _force_platform()
    from __graft_entry__ import _example_batch
    from lighthouse_tpu.utils.compile_cache import arm

    arm()
    from lighthouse_tpu.crypto.bls.backends.jax_tpu import (
        _bucket,
        grid_bucket,
        verify_device,
        verify_device_aggregated,
    )

    from lighthouse_tpu.obs import ledger as launch_ledger

    led = launch_ledger.configure()

    platform = jax.devices()[0].platform
    # n/m = 64 on both defaults; the CPU shape is sized to compile inside
    # the profile time box (the TPU shape is the BASELINE.md mainnet one)
    default_n, default_d = ("1024", "16") if platform == "tpu" else ("128", "2")
    n = int(os.environ.get("BENCH_PROFILE_SETS", default_n))
    d = int(os.environ.get("BENCH_PROFILE_DISTINCT", default_d))
    k = int(os.environ.get("BENCH_PUBKEYS_PER_SET", "2"))
    reps = int(os.environ.get("BENCH_REPS", "3"))

    def timed(fn, args):
        """(compile+warm seconds, best steady seconds) of one layout."""
        t0 = time.perf_counter()
        ok = bool(jax.block_until_ready(fn(*args)))
        compile_s = time.perf_counter() - t0
        assert ok, "profile batch failed to verify"
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        return compile_s, min(times)

    unagg_compile, unagg_best = timed(
        verify_device, _example_batch(n, k, distinct=d, dedup=True)
    )
    agg_compile, agg_best = timed(
        verify_device_aggregated, _example_batch(n, k, distinct=d, agg=True)
    )
    # one warm-kind ledger record per timed layout, keyed like cli warm
    key_unagg = "x".join(str(v) for v in (_bucket(n), _bucket(k), _bucket(d), 0))
    key_agg = "x".join(
        str(v)
        for v in (_bucket(n), _bucket(k), _bucket(d), grid_bucket(_bucket(n)))
    )
    launch_ledger.record(
        "warm", bucket=key_unagg, real_sets=n, padded_sets=_bucket(n),
        compile_seconds=unagg_compile, cache_hit=False,
    )
    launch_ledger.record(
        "warm", bucket=key_agg, real_sets=n, padded_sets=_bucket(n),
        compile_seconds=agg_compile, cache_hit=False,
    )
    pairs_agg = _bucket(d) + 1
    _emit(
        {
            "profile": "mainnet_traffic_shape",
            "platform": platform,
            "n_sets": n,
            "distinct_messages": d,
            "pubkeys_per_set": k,
            "pairs_unaggregated": _bucket(n) + 1,
            "pairs_aggregated": pairs_agg,
            "aggregation_ratio": round(n / pairs_agg, 2),
            "unaggregated_sets_per_s": round(n / unagg_best, 2),
            "aggregated_sets_per_s": round(n / agg_best, 2),
            "speedup": round(unagg_best / agg_best, 2),
            "compile_s": {
                "unaggregated": round(unagg_compile, 2),
                "aggregated": round(agg_compile, 2),
            },
            # per-bucket compile wall next to the sets/s numbers, keyed
            # like the warm pass (n x k x m x g)
            "compile_s_per_bucket": {
                "x".join(
                    str(v)
                    for v in (_bucket(n), _bucket(k), _bucket(d), 0)
                ): round(unagg_compile, 2),
                "x".join(
                    str(v)
                    for v in (
                        _bucket(n),
                        _bucket(k),
                        _bucket(d),
                        grid_bucket(_bucket(n)),
                    )
                ): round(agg_compile, 2),
            },
            "ledger": led.stats(),
        }
    )


def speculate_bench() -> None:
    """`bench.py --speculate`: seeded slots of committee-shaped aggregate
    traffic through the REAL verification entrypoint
    (batch_verify_aggregates) with the duty-driven precompute ON vs OFF,
    reporting critical-path sets/s for both plus the
    hit/correction/miss/confirm ratios. Same artifact contract as the
    main bench: exactly ONE JSON line, exit 0 even on failure."""
    try:
        _speculate_bench_inner()
    except BaseException as exc:  # never lose the artifact
        _emit(
            {
                "metric": "speculate_aggregate_sets_per_s",
                "value": 0.0,
                "unit": "sets/s",
                "error": f"speculate bench: {type(exc).__name__}: {exc}",
            }
        )


def _speculate_bench_inner() -> None:
    sys.path.insert(0, HERE)
    _force_platform()
    from lighthouse_tpu.crypto.bls import set_backend

    # default: the pure-Python oracle backend -- every pairing is real,
    # sized small; the interesting delta (zero per-set pubkey aggregation
    # + confirm-by-lookup dropping the indexed set) is backend-agnostic
    set_backend(os.environ.get("BENCH_SPECULATE_BACKEND", "cpu"))
    from lighthouse_tpu.chain.attestation_verification import (
        batch_verify_aggregates,
    )
    from lighthouse_tpu.harness import BeaconChainHarness
    from lighthouse_tpu.pool import ObservedAggregates, ObservedAggregators
    from lighthouse_tpu.speculate import attach_speculation
    from lighthouse_tpu.state_transition import clone_state, process_slots
    from lighthouse_tpu.types import ChainSpec, MINIMAL

    validators = int(os.environ.get("BENCH_SPECULATE_VALIDATORS", "16"))
    slots = int(os.environ.get("BENCH_SPECULATE_SLOTS", "4"))
    reps = int(os.environ.get("BENCH_SPECULATE_REPS", "2"))
    seed = int(os.environ.get("BENCH_SPECULATE_SEED", "7"))

    h = BeaconChainHarness(
        validators, MINIMAL, ChainSpec.interop(), sign=True
    )
    h.extend_chain(slots + 1)
    chain = h.chain
    sub = attach_speculation(
        chain, signature_source=h.producer.aggregate_signature_source()
    )

    # seeded committee-shaped traffic: one signed aggregate per
    # (slot, committee) over the last `slots` slots, all inside the
    # gossip propagation window of the head
    state = process_slots(
        clone_state(chain.head_state),
        int(chain.head_state.slot) + 1,
        MINIMAL,
        h.spec,
    )
    from lighthouse_tpu.state_transition import ConsensusContext
    from lighthouse_tpu.types import compute_epoch_at_slot

    ctxt = ConsensusContext(MINIMAL, h.spec)
    traffic = []
    head_slot = int(chain.head_state.slot)
    for slot in range(head_slot - slots + 1, head_slot + 1):
        epoch = compute_epoch_at_slot(slot, MINIMAL)
        cache = ctxt.committee_cache(state, epoch)
        for index in range(cache.committees_per_slot):
            traffic.append(
                h.producer.make_signed_aggregate(state, slot, index)
            )
    sets_per_agg = 3  # selection proof + aggregate-and-proof + indexed

    def run_pass():
        t0 = time.perf_counter()
        verified, rejected = batch_verify_aggregates(
            chain, traffic, ObservedAggregates(), ObservedAggregators()
        )
        return time.perf_counter() - t0, len(verified), len(rejected)

    # OFF: the flag-off baseline (per-set host pubkey aggregation)
    sub.enabled = False
    off_times, off_ok = [], None
    for _ in range(reps):
        dt, nv, nr = run_pass()
        off_times.append(dt)
        off_ok = (nv, nr)

    # ON (precompute only): the memo is empty, so every aggregate rides
    # the committee-aggregate cache -- this pass yields the hit ratios
    sub.enabled = True
    pre_stats = dict(sub.precompute.stats)
    on_times, on_ok = [], None
    for _ in range(reps):
        dt, nv, nr = run_pass()
        on_times.append(dt)
        on_ok = (nv, nr)
    d_pre = {
        k: sub.precompute.stats[k] - pre_stats[k] for k in pre_stats
    }

    # ON (+speculation): pre-verify the traffic slots during "idle time",
    # then the same aggregates are confirmed by memo lookup on arrival
    ver_stats = dict(sub.verifier.stats)
    for slot in range(head_slot - slots + 1, head_slot + 1):
        sub.verifier.speculate_slot(slot)
    spec_times, spec_ok = [], None
    for _ in range(reps):
        dt, nv, nr = run_pass()
        spec_times.append(dt)
        spec_ok = (nv, nr)
    d_ver = {k: sub.verifier.stats[k] - ver_stats[k] for k in ver_stats}

    n = len(traffic)
    looked_up = max(
        1, d_pre["full_hits"] + d_pre["corrections"] + d_pre["misses"]
    )
    off_best = min(off_times)
    on_best = min(on_times)
    spec_best = min(spec_times)
    _emit(
        {
            "metric": "speculate_aggregate_sets_per_s",
            "value": round(n * sets_per_agg / spec_best, 2),
            "unit": "sets/s",
            "seed": seed,
            "validators": validators,
            "slots": slots,
            "aggregates": n,
            "verified": spec_ok,
            "verdicts_match_off_path": on_ok == off_ok == spec_ok,
            "off_sets_per_s": round(n * sets_per_agg / off_best, 2),
            "precompute_sets_per_s": round(n * sets_per_agg / on_best, 2),
            "speculate_sets_per_s": round(n * sets_per_agg / spec_best, 2),
            "precompute_speedup": round(off_best / on_best, 3),
            "speculate_speedup": round(off_best / spec_best, 3),
            "precompute": {
                "full_hit_ratio": round(d_pre["full_hits"] / looked_up, 3),
                "correction_ratio": round(
                    d_pre["corrections"] / looked_up, 3
                ),
                "miss_ratio": round(d_pre["misses"] / looked_up, 3),
            },
            "speculation": {
                "preverified": d_ver["preverified"],
                "confirms": d_ver["confirms"],
                "confirm_misses": d_ver["confirm_misses"],
                "mismatches": d_ver["mismatches"],
            },
        }
    )


def latency_bench() -> None:
    """`bench.py --latency`: bursty gossip arrivals through the
    continuous-batching scheduler vs the whole-batch baseline, reporting
    per-lane time-to-verdict p50/p95 against the replayed arrival clock
    plus the pad-waste ratio. Same artifact contract as the main bench:
    exactly ONE JSON line, exit 0 even on failure."""
    try:
        _latency_bench_inner()
    except BaseException as exc:  # never lose the artifact
        _emit(
            {
                "metric": "cont_batch_ttv_p95_speedup",
                "value": 0.0,
                "unit": "x",
                "error": f"latency bench: {type(exc).__name__}: {exc}",
            }
        )


def _percentile(samples: list[float], q: float) -> float:
    xs = sorted(samples)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def _poisson(rng, lam: float) -> int:
    """Knuth sampler -- small lambdas only (burst sizes)."""
    import math

    limit, k, p = math.exp(-lam), 0, 1.0
    while True:
        p *= rng.random()
        if p <= limit:
            return k
        k += 1


def _bursty_schedule(rng, slots: int, slot_s: float, burst: float):
    """Seeded arrival schedule: (offset_s, lane, n_sets, slot) tuples.
    Real lanes burst right after each slot boundary (Poisson burst
    sizes, exponentially clustered offsets -- the gossip shape); the
    block proposal lands mid-slot; speculation trickles uniformly."""
    schedule = []
    for slot in range(slots):
        t0 = slot * slot_s
        schedule.append((t0 + 0.35 * slot_s, "block", 4, slot))
        for lane, lam, spread in (
            ("aggregate", burst, 0.10),
            ("unaggregated", 2.0 * burst, 0.15),
            ("sync", 0.5 * burst, 0.10),
        ):
            for _ in range(_poisson(rng, lam)):
                off = min(rng.expovariate(1.0 / (spread * slot_s)), slot_s)
                schedule.append(
                    (t0 + off, lane, 1 + rng.randrange(3), slot)
                )
        for _ in range(2):
            schedule.append(
                (t0 + rng.random() * slot_s, "speculative", 1, slot)
            )
    schedule.sort(key=lambda a: a[0])
    return schedule


def _latency_bench_inner() -> None:
    import random
    import threading

    sys.path.insert(0, HERE)
    _force_platform()
    from lighthouse_tpu.crypto.bls import (
        SecretKey,
        SignatureSet,
        set_backend,
    )
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.crypto.bls import pipeline as bls_pipeline
    from lighthouse_tpu.crypto.bls import scheduler as bls_scheduler
    from lighthouse_tpu.obs import ledger as launch_ledger
    from lighthouse_tpu.utils import metrics as M

    # default: the fake backend. The bench measures QUEUEING dynamics
    # (batch-formation wait vs merge-at-next-boundary), which are
    # backend-agnostic; BENCH_LATENCY_BACKEND=cpu pays real pairings.
    set_backend(os.environ.get("BENCH_LATENCY_BACKEND", "fake"))

    slots = int(os.environ.get("BENCH_LATENCY_SLOTS", "8"))
    slot_s = float(os.environ.get("BENCH_LATENCY_SLOT_MS", "150")) / 1e3
    burst = float(os.environ.get("BENCH_LATENCY_BURST", "6"))
    seed = int(os.environ.get("BENCH_LATENCY_SEED", "7"))

    # a small pool of real signed sets, cycled across arrivals
    pool = []
    for i in range(16):
        sk = SecretKey(i + 1)
        msg = bytes([i]) * 32
        pool.append(
            SignatureSet.single_pubkey(sk.sign(msg), sk.public_key(), msg)
        )
    rng = random.Random(seed)
    schedule = _bursty_schedule(rng, slots, slot_s, burst)
    arrivals = [
        (off, lane, [pool[(i + j) % len(pool)] for j in range(n)], slot)
        for i, (off, lane, n, slot) in enumerate(schedule)
    ]

    def replay_baseline():
        """Whole-batch dispatch: every slot's arrivals wait for the slot
        to finish forming, then verify as ONE pipeline batch (the
        pre-scheduler seam shape). Per-arrival verdicts recover exactly
        as callers do: conjunction when True, per-arrival re-verify
        when False."""
        bls_pipeline.configure()
        lat = {i: None for i in range(len(arrivals))}
        verdicts = {}
        start = time.perf_counter()
        by_slot: dict[int, list[int]] = {}
        for i, (off, _lane, _sets, slot) in enumerate(arrivals):
            by_slot.setdefault(slot, []).append(i)
        for slot in sorted(by_slot):
            boundary = (slot + 1) * slot_s
            now = time.perf_counter() - start
            if now < boundary:
                time.sleep(boundary - now)
            merged = [s for i in by_slot[slot] for s in arrivals[i][2]]
            ok = bls_pipeline.default_pipeline().submit(merged).result()
            if not ok:
                for i in by_slot[slot]:
                    verdicts[i] = bool(
                        bls_api.verify_signature_sets(arrivals[i][2])
                    )
            done = time.perf_counter() - start
            for i in by_slot[slot]:
                verdicts.setdefault(i, bool(ok))
                lat[i] = done - arrivals[i][0]
        bls_pipeline.default_pipeline().drain()
        return lat, verdicts

    def replay_cont():
        """The same arrivals through the continuous-batching scheduler:
        the driver submits at each arrival offset, a resolver thread
        blocks on verdicts in arrival order -- every result() is a
        launch boundary that merges whatever queued behind it."""
        bls_pipeline.configure()
        sched = bls_scheduler.configure()
        lat = {i: None for i in range(len(arrivals))}
        verdicts = {}
        import queue as queue_mod

        q: queue_mod.Queue = queue_mod.Queue()
        start = time.perf_counter()

        def resolver():
            while True:
                item = q.get()
                if item is None:
                    return
                i, fut = item
                verdicts[i] = bool(fut.result())
                lat[i] = (time.perf_counter() - start) - arrivals[i][0]

        t = threading.Thread(target=resolver, daemon=True)
        t.start()
        for i, (off, lane, sets, slot) in enumerate(arrivals):
            now = time.perf_counter() - start
            if now < off:
                time.sleep(off - now)
            fut = bls_api.verify_signature_sets_async(
                sets, lane=lane, slot=slot
            )
            q.put((i, fut))
        q.put(None)
        t.join()
        sched.drain()
        return lat, verdicts, dict(sched.stats)

    prior = os.environ.get("LIGHTHOUSE_TPU_CONT_BATCH")
    os.environ["LIGHTHOUSE_TPU_CONT_BATCH"] = "1"
    try:
        # warm pass (unmeasured): compiles every shape the replay will
        # touch, so the measured pass is steady-state
        replay_cont()
        # fresh ledger so the artifact's launch accounting covers ONLY
        # the measured pass
        led = launch_ledger.configure()
        misses0 = M.TPU_COMPILE_CACHE_MISSES.value
        cont_lat, cont_verdicts, stats = replay_cont()
        cache_misses = M.TPU_COMPILE_CACHE_MISSES.value - misses0
        ledger_stats = led.stats()
    finally:
        if prior is None:
            os.environ.pop("LIGHTHOUSE_TPU_CONT_BATCH", None)
        else:
            os.environ["LIGHTHOUSE_TPU_CONT_BATCH"] = prior
    base_lat, base_verdicts = replay_baseline()

    lanes = {}
    for lane in bls_scheduler.LANES:
        idx = [i for i, a in enumerate(arrivals) if a[1] == lane]
        if not idx:
            continue
        c = [cont_lat[i] for i in idx]
        b = [base_lat[i] for i in idx]
        lanes[lane] = {
            "arrivals": len(idx),
            "p50_ms": round(1e3 * _percentile(c, 0.50), 2),
            "p95_ms": round(1e3 * _percentile(c, 0.95), 2),
            "baseline_p50_ms": round(1e3 * _percentile(b, 0.50), 2),
            "baseline_p95_ms": round(1e3 * _percentile(b, 0.95), 2),
        }
    real_idx = [
        i for i, a in enumerate(arrivals) if a[1] != "speculative"
    ]
    cont_p95 = _percentile([cont_lat[i] for i in real_idx], 0.95)
    base_p95 = _percentile([base_lat[i] for i in real_idx], 0.95)
    pad, real = stats["pad_sets"], stats["real_sets"]
    payload = {
        "metric": "cont_batch_ttv_p95_speedup",
        "value": round(base_p95 / cont_p95, 3) if cont_p95 else 0.0,
        "unit": "x",
        "seed": seed,
        "slots": slots,
        "slot_ms": round(1e3 * slot_s, 1),
        "arrivals": len(arrivals),
        "lanes": lanes,
        "pad_waste_ratio": (
            round(pad / (pad + real), 4) if (pad + real) else 0.0
        ),
        "scheduler": stats,
        "ledger": ledger_stats,
        "compile_cache_misses_after_warm": cache_misses,
        "verdicts_match_baseline": cont_verdicts == base_verdicts,
    }
    if cont_verdicts != base_verdicts:
        bad = [
            i
            for i in cont_verdicts
            if cont_verdicts.get(i) != base_verdicts.get(i)
        ]
        payload["error"] = (
            f"verdict divergence on {len(bad)} arrivals: {bad[:8]}"
        )
    _emit(payload)


def scale_bench() -> None:
    """`bench.py --scale`: million-validator state sharded over the mesh.
    Times the mesh-sharded epoch processor (per_epoch_mesh.py) over a
    validator-count curve up to 2M on a simulated multi-device CPU mesh,
    and measures the per-device pubkey-table bytes of the sharded table
    against whole-table replication. Same artifact contract as the main
    bench: exactly ONE JSON line, exit 0 even on failure."""
    try:
        _scale_bench_inner()
    except BaseException as exc:  # never lose the artifact
        _emit(
            {
                "metric": "epoch_transition_mesh_2m_s",
                "value": 0.0,
                "unit": "s",
                "error": f"scale bench: {type(exc).__name__}: {exc}",
            }
        )


def _scale_bench_inner() -> None:
    sys.path.insert(0, HERE)
    # the virtual mesh must be forced BEFORE the XLA backend initializes
    # (first jax.devices() call); if something already initialized it,
    # run with whatever device count exists and report it
    n_dev = int(os.environ.get("BENCH_SCALE_DEVICES", "4"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    _force_platform()
    import jax

    n_dev = len(jax.devices())

    import numpy as np

    from bench_local import _synthetic_state
    from lighthouse_tpu.crypto.bls.backends import jax_tpu
    from lighthouse_tpu.utils import metrics as M

    sizes = [
        int(s)
        for s in os.environ.get(
            "BENCH_SCALE_VALIDATORS", "250000,1000000,2000000"
        ).split(",")
    ]
    reps = int(os.environ.get("BENCH_SCALE_REPS", "2"))

    # --- pubkey-table HBM: sharded per-device bytes vs replication -------
    # The table contents are irrelevant to placement (limb rows are
    # opaque int32), so the 2M-row table is synthesized directly instead
    # of decompressing 2M real pubkeys on the host.
    table_rows = max(sizes)
    rng = np.random.default_rng(7)
    table = jax_tpu.PubkeyTable()
    table._host = rng.integers(
        0, 2**28, size=(table_rows, 3, jax_tpu.W), dtype=np.int64
    ).astype(np.int32)
    dev = table.device_table()
    bucket_rows = int(dev.shape[0])
    replicated_bytes = bucket_rows * 3 * jax_tpu.W * 4
    if table.sharded:
        per_device = max(
            M.TPU_PUBKEY_TABLE_BYTES.get(str(d.id))
            for d in dev.sharding.mesh.devices.flat
        )
    else:
        per_device = replicated_bytes
    gather_idx = rng.integers(0, table_rows, size=(1024,)).astype(np.int32)
    t0 = time.perf_counter()
    jax.block_until_ready(table.gather(gather_idx))
    gather_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(table.gather(gather_idx))
    gather_warm_s = time.perf_counter() - t0
    del table, dev  # free ~1 GB before the epoch states

    # --- epoch-transition curve over the sharded column passes -----------
    os.environ["LIGHTHOUSE_TPU_EPOCH_MESH"] = "1"
    from lighthouse_tpu.state_transition.per_epoch import process_epoch
    from lighthouse_tpu.types import MINIMAL, ChainSpec

    spec = ChainSpec.interop(altair_fork_epoch=0)
    curve = []
    for n in sizes:
        state = _synthetic_state(n, "altair")
        state.slot = 3 * MINIMAL.slots_per_epoch - 1
        times = []
        for _ in range(max(1, reps) + 1):
            t0 = time.perf_counter()
            process_epoch(state, MINIMAL, spec)
            times.append(time.perf_counter() - t0)
        curve.append(
            {
                "n_validators": n,
                # rep 0 pays program compiles + the cold column
                # extraction; a live node's steady state is the warm rep
                # (identity-cached columns, warm executables)
                "cold_s": round(times[0], 3),
                "warm_s": round(min(times[1:]), 3),
            }
        )
        del state

    # prove the curve went through the mesh programs, not a silent
    # VectorGuard fallback to the single-device vec path
    from lighthouse_tpu.state_transition import per_epoch_mesh

    top = curve[-1]
    _emit(
        {
            "metric": "epoch_transition_mesh_2m_s",
            "value": top["warm_s"],
            "unit": "s",
            "n_devices": n_dev,
            "mesh_path_used": bool(per_epoch_mesh._PROGRAMS),
            "slot_budget_s": 12.0,
            "within_slot": top["warm_s"] < 12.0,
            "curve": curve,
            "pubkey_table": {
                "rows": table_rows,
                "bucket_rows": bucket_rows,
                "replicated_bytes_per_device": replicated_bytes,
                "sharded_bytes_per_device": per_device,
                "per_device_fraction": round(
                    per_device / replicated_bytes, 4
                ),
                "gather_1k_cold_s": round(gather_cold_s, 4),
                "gather_1k_warm_s": round(gather_warm_s, 4),
            },
            "note": "virtual devices share one host CPU: correctness + "
            "per-device memory scaling, not a wall-clock speedup claim",
        }
    )


def serving_bench() -> None:
    """`bench.py --serving`: the serving-tier load generator (cached vs
    uncached requests/s over a real server). Same artifact contract as
    the BLS bench: exactly ONE JSON line, exit 0 even on failure."""
    argv = [a for a in sys.argv[1:] if a != "--serving"]
    try:
        from tools.serving_load import main as serving_main

        serving_main(argv)
    except BaseException as exc:  # never lose the artifact
        _emit(
            {
                "metric": "serving_cached_requests_per_s",
                "value": 0.0,
                "unit": "req/s",
                "error": f"serving bench: {type(exc).__name__}: {exc}",
            }
        )


def main() -> int:
    if "--serving" in sys.argv:
        serving_bench()
    elif "--speculate" in sys.argv:
        speculate_bench()
    elif "--latency" in sys.argv:
        latency_bench()
    elif "--scale" in sys.argv:
        scale_bench()
    elif "--profile" in sys.argv:
        profile_child()
    else:
        return measure()
    return 0


if __name__ == "__main__":
    sys.exit(main())
