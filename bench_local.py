"""Local (CPU-only) performance floor: numbers rounds can diff even when
no chip is at hand (VERDICT r3 weak-8). Writes BENCH_LOCAL.json at
the repo root with one entry per config from BASELINE.md:

  * verifier_mesh_sets_per_s -- the sharded batch verifier on the
    8-virtual-device CPU mesh (BASELINE config 5's local stand-in)
  * epoch_transition_s       -- process_slots across an epoch boundary
    on a synthetic N-validator state (BASELINE config 4)
  * cached_tree_hash_speedup -- steady-state re-root vs from-scratch
    merkleization at N validators (reference criterion benches)
  * op_pool_pack_s           -- max-cover packing over 4,096 pooled
    aggregates (BASELINE config 2/3)

Sizes shrink via BENCH_LOCAL_SCALE=mini for the in-suite smoke test.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _force_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass
    from lighthouse_tpu.utils.compile_cache import arm

    arm()


def bench_verifier_mesh(n_sets: int = 8) -> dict:
    """Sharded verify on the 8-device CPU mesh, warm, one set/device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from __graft_entry__ import _example_batch
    from lighthouse_tpu.parallel import make_sharded_verify, sets_mesh

    devices = jax.devices("cpu")[:8]
    mesh = sets_mesh(devices)
    fn = make_sharded_verify(mesh)
    args = _example_batch(n_sets=n_sets, k_pk=2, distinct=min(n_sets, 8))
    sharding = NamedSharding(mesh, PartitionSpec("sets"))
    args = tuple(jax.device_put(a, sharding) for a in args)
    t0 = time.perf_counter()
    ok = bool(fn(*args))  # compile (cached) + run
    compile_s = time.perf_counter() - t0
    assert ok
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        bool(fn(*args))
        times.append(time.perf_counter() - t0)
    best = min(times)
    return {
        "metric": "verifier_mesh_sets_per_s",
        "value": round(n_sets / best, 2),
        "n_sets": n_sets,
        "n_devices": 8,
        "compile_s": round(compile_s, 2),
    }


def bench_verifier_mesh_curve(per_device_sets: int = 1) -> dict:
    """Weak-scaling curve over mesh sizes 1/2/4/8 (BASELINE config 5,
    block_signature_verifier.rs:374-384's rayon analogue): fixed per-device
    sets, growing mesh. NOTE the honest caveat: these virtual devices share
    ONE host CPU, so wall time GROWS with mesh size here — the curve
    demonstrates sharding correctness and bounded collective overhead, not
    speedup. Linear-throughput claims need real chips; the driver's
    dryrun_multichip validates the same program compiles and executes on
    an N-device mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from __graft_entry__ import _example_batch
    from lighthouse_tpu.parallel import make_sharded_verify, sets_mesh

    devices = jax.devices("cpu")
    curve = []
    for n_dev in (1, 2, 4, 8):
        if len(devices) < n_dev:
            break
        n_sets = per_device_sets * n_dev
        mesh = sets_mesh(devices[:n_dev])
        fn = make_sharded_verify(mesh)
        args = _example_batch(
            n_sets=n_sets, k_pk=2, distinct=min(n_sets, 8)
        )
        sharding = NamedSharding(mesh, PartitionSpec("sets"))
        d_args = tuple(jax.device_put(a, sharding) for a in args)
        t0 = time.perf_counter()
        ok = bool(fn(*d_args))
        compile_s = time.perf_counter() - t0
        assert ok, f"mesh={n_dev} rejected a valid batch"
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            bool(fn(*d_args))
            times.append(time.perf_counter() - t0)
        best = min(times)
        curve.append(
            {
                "n_devices": n_dev,
                "n_sets": n_sets,
                "steady_s": round(best, 3),
                "sets_per_s": round(n_sets / best, 2),
                "compile_s": round(compile_s, 2),
            }
        )
    return {
        "metric": "verifier_mesh_weak_scaling",
        "value": curve[-1]["sets_per_s"] if curve else 0.0,
        "curve": curve,
        "note": "virtual devices share one host CPU: correctness + "
        "overhead curve, not a speedup claim",
    }


def _synthetic_state(n_validators: int, fork: str = "phase0"):
    from lighthouse_tpu.types import MINIMAL, types_for
    from lighthouse_tpu.types.chain_spec import FAR_FUTURE_EPOCH
    from lighthouse_tpu.types.containers import Validator, state_class_for

    t = types_for(MINIMAL)
    state = state_class_for(t, fork).default()
    rng = random.Random(7)
    state.validators = tuple(
        Validator(
            pubkey=rng.randbytes(48),
            withdrawal_credentials=rng.randbytes(32),
            effective_balance=32 * 10**9,
            exit_epoch=FAR_FUTURE_EPOCH,
            withdrawable_epoch=FAR_FUTURE_EPOCH,
        )
        for _ in range(n_validators)
    )
    state.balances = tuple(32 * 10**9 for _ in range(n_validators))
    if fork != "phase0":
        # ~98% full participation, a sprinkle of partials — production-like
        state.previous_epoch_participation = tuple(
            7 if rng.random() < 0.98 else rng.choice([0, 1, 3])
            for _ in range(n_validators)
        )
        state.current_epoch_participation = tuple(
            7 if rng.random() < 0.98 else 0 for _ in range(n_validators)
        )
        state.inactivity_scores = (0,) * n_validators
    return state


def bench_epoch_transition(
    n_validators: int = 100_000, fork: str = "phase0"
) -> dict:
    """One epoch boundary via process_slots (BASELINE config 4). The
    altair variant exercises the vectorized participation-flag path
    (state_transition/per_epoch_vec.py); phase0 is the PendingAttestation
    loop oracle. Cost includes the incremental-hash cache build."""
    from lighthouse_tpu.state_transition import process_slots
    from lighthouse_tpu.types import MINIMAL, ChainSpec

    if fork == "phase0":
        spec = ChainSpec.interop(
            altair_fork_epoch=None, bellatrix_fork_epoch=None
        )
    else:
        spec = ChainSpec.interop(altair_fork_epoch=0)
    state = _synthetic_state(n_validators, fork)
    # start late enough that justification weighing runs (epoch > 1)
    start = 3 * MINIMAL.slots_per_epoch - 1
    state.slot = start
    # steady-state: a live node's incremental-hash cache is always warm;
    # the cold build is a one-time cost measured by cached_tree_hash below
    from lighthouse_tpu.ssz import cached_root

    cached_root(state)
    t0 = time.perf_counter()
    process_slots(state, start + 2, MINIMAL, spec)
    dt = time.perf_counter() - t0
    return {
        "metric": f"epoch_transition_{fork}_s",
        "value": round(dt, 3),
        "n_validators": n_validators,
    }


def bench_block_replay(
    n_validators: int = 500_000, n_slots: int = 8, fork: str = "altair"
) -> dict:
    """Empty-slot block-range replay rate at scale (BASELINE config 4's
    historical-replay shape; reference block_replayer.rs): slots/s through
    process_slots incl. one epoch boundary, steady-state hash cache."""
    from lighthouse_tpu.state_transition import process_slots
    from lighthouse_tpu.types import MINIMAL, ChainSpec

    spec = ChainSpec.interop(altair_fork_epoch=0)
    state = _synthetic_state(n_validators, fork)
    start = 3 * MINIMAL.slots_per_epoch - 1
    state.slot = start
    # build the incremental-hash cache outside the timed region (a replayer
    # holds its state across the whole range; the build amortizes away)
    from lighthouse_tpu.ssz import cached_root

    cached_root(state)
    t0 = time.perf_counter()
    process_slots(state, start + n_slots, MINIMAL, spec)
    dt = time.perf_counter() - t0
    return {
        "metric": "block_replay_slots_per_s",
        "value": round(n_slots / dt, 2),
        "n_validators": n_validators,
        "n_slots": n_slots,
    }


def bench_cached_tree_hash(n_validators: int = 16_384) -> dict:
    from lighthouse_tpu.ssz import cached_root

    state = _synthetic_state(n_validators)
    t0 = time.perf_counter()
    fresh = state.tree_hash_root()
    fresh_s = time.perf_counter() - t0
    assert cached_root(state) == fresh  # cold cache build
    bal = list(state.balances)
    for i in random.Random(1).sample(range(n_validators), 10):
        bal[i] += 1
    state.balances = tuple(bal)
    t0 = time.perf_counter()
    cached_root(state)
    cached_s = time.perf_counter() - t0
    return {
        "metric": "cached_tree_hash_speedup",
        "value": round(fresh_s / max(cached_s, 1e-9), 1),
        "fresh_s": round(fresh_s, 3),
        "cached_s": round(cached_s, 5),
        "n_validators": n_validators,
    }


def bench_op_pool_pack(n_attestations: int = 4096, validators: int = 256) -> dict:
    from lighthouse_tpu.harness.chain import StateHarness
    from lighthouse_tpu.pool import OperationPool
    from lighthouse_tpu.state_transition import clone_state, process_slots
    from lighthouse_tpu.types import MINIMAL, types_for

    h = StateHarness(validators, MINIMAL, sign=False)
    t = types_for(MINIMAL)
    target_slot = 2 * MINIMAL.slots_per_epoch
    state = process_slots(
        clone_state(h.state), target_slot, MINIMAL, h.spec
    )
    pool = OperationPool(MINIMAL, h.spec)
    rng = random.Random(3)
    # fill until the pool RETAINS n_attestations distinct aggregates
    # (subset variants are deduped on insert), with an attempt cap
    attempts = 0
    while pool.num_attestations() < n_attestations and attempts < 20 * n_attestations:
        slot = rng.randrange(state.slot - MINIMAL.slots_per_epoch + 1, state.slot)
        for att in h.attestations_for_slot(state, slot):
            bits = [rng.random() < 0.5 for _ in att.aggregation_bits]
            if not any(bits):
                bits[0] = True
            pool.insert_attestation(
                t.Attestation(
                    aggregation_bits=bits,
                    data=att.data,
                    signature=att.signature,
                )
            )
            attempts += 1
            if pool.num_attestations() >= n_attestations:
                break
    t0 = time.perf_counter()
    packed = pool.get_attestations(state)
    dt = time.perf_counter() - t0
    return {
        "metric": "op_pool_pack_s",
        "value": round(dt, 3),
        "pooled": pool.num_attestations(),
        "packed": len(packed),
    }


def main() -> None:
    mini = os.environ.get("BENCH_LOCAL_SCALE") == "mini"
    _force_cpu()
    results = []
    if not mini:
        # compile-bound (minutes when the XLA cache is cold): full runs only
        results.append(bench_verifier_mesh(8))
        results.append(bench_verifier_mesh_curve())
    results += [
        bench_epoch_transition(2_000 if mini else 100_000),
        bench_epoch_transition(2_000 if mini else 500_000, fork="altair"),
        bench_block_replay(2_000 if mini else 500_000),
        bench_cached_tree_hash(2_048 if mini else 16_384),
        bench_op_pool_pack(256 if mini else 4096, 64 if mini else 256),
    ]
    payload = {
        "scale": "mini" if mini else "full",
        "platform": "cpu",
        "results": results,
    }
    out = os.path.join(HERE, "BENCH_LOCAL.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
