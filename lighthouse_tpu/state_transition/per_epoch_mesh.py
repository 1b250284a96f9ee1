"""Mesh-sharded altair/bellatrix per-epoch processing: the validator
columns of per_epoch_vec.py (balances, participation, inactivity scores,
registry flags) lifted onto the device mesh.

Columns shard their validator-index dimension over the `validators` mesh
axis (parallel/verify_sharded.validators_mesh -- the same physical
devices the MeshVerifier shards batches over, so a fixed-size mesh
absorbs registry growth for state processing AND signature
verification). Two `shard_map` programs do the per-validator work:

  * a REDUCE pass whose only collectives are the genuinely-global
    reductions -- the total-active-balance sum (the
    `integer_squareroot` input), the per-flag participating-balance
    sums (justification weighing + flag-reward increments), and the
    active-validator count (the activation-queue churn limit) -- each
    one int64 psum of a per-shard partial;
  * an elementwise UPDATE pass (inactivity scores, flag rewards and
    penalties, balance application) with NO collectives at all.

Both run with int64/uint64 semantics identical to the numpy path (the
passes execute under `jax.enable_x64(True)`; floor division of
non-negative 64-bit quantities matches the spec's integer arithmetic
exactly). Rare per-validator paths -- ejections, the FIFO activation
queue, slashing hits, hysteresis crossers -- and the surgical tree-cache
writeback are SHARED with per_epoch_vec.py, so the bit-exactness
contract against the per_epoch.py oracle carries over unchanged,
including the pre-mutation VectorGuard overflow fallback
(tests/test_sharded_state.py holds mesh sizes 1/2/4 equal to the
oracle).

Shapes bucket to powers of two (floor 256) so a live node compiles a
handful of small programs per mesh, never one per registry size.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..types import FAR_FUTURE_EPOCH, GENESIS_EPOCH
from ..types.presets import Preset
from ..utils.math import integer_squareroot
from .participation import (
    PARTICIPATION_FLAG_WEIGHTS,
    TIMELY_HEAD_FLAG_INDEX,
    TIMELY_TARGET_FLAG_INDEX,
    WEIGHT_DENOMINATOR,
)
from .per_epoch_vec import (
    VectorGuard,
    _cached_col,
    _columns_for,
    _effective_balance_updates_vec,
    _registry_updates_vec,
    _total_with_floor,
)

_N_FLAGS = len(PARTICIPATION_FLAG_WEIGHTS)
_PAD_FLOOR = 256

# mesh + compiled shard_map programs, one per device set (jit itself
# re-specializes per padded shape, so shapes never key these dicts)
_MESHES: dict[tuple, object] = {}
_PROGRAMS: dict[tuple, tuple] = {}


def _mesh_for(devices):
    from ..parallel.verify_sharded import validators_mesh

    if devices is None:
        devices = jax.devices()
    key = tuple(d.id for d in devices)
    mesh = _MESHES.get(key)
    if mesh is None:
        mesh = _MESHES[key] = validators_mesh(devices)
    return mesh


def _pad_bucket(n: int, n_shards: int) -> int:
    """Power-of-two row bucket, floor 256, always divisible by the
    (power-of-two) shard count."""
    b = max(_PAD_FLOOR, n_shards)
    while b < n:
        b *= 2
    return b


def _flag_mask(part, active, slashed, flag_index: int):
    """Spec get_unslashed_participating_indices as a device mask."""
    return active & ((part & jnp.uint8(1 << flag_index)) != 0) & ~slashed


def _build_programs(mesh):
    """The two shard_map programs for `mesh` (see module docstring)."""
    from ..parallel.verify_sharded import VALIDATOR_AXIS, shard_map

    def psum_i64(x):
        return jax.lax.psum(jnp.sum(x), VALIDATOR_AXIS)

    def sums_body(eff, activation, exit_e, slashed, part_prev, part_cur, ep):
        prev_e, cur_e = ep[0], ep[1]
        active_prev = (activation <= prev_e) & (prev_e < exit_e)
        active_cur = (activation <= cur_e) & (cur_e < exit_e)
        zero = jnp.int64(0)
        out = [
            psum_i64(jnp.where(active_cur, eff, zero)),
            psum_i64(active_cur.astype(jnp.int64)),
        ]
        for f in range(_N_FLAGS):
            m = _flag_mask(part_prev, active_prev, slashed, f)
            out.append(psum_i64(jnp.where(m, eff, zero)))
        cur_target = _flag_mask(
            part_cur, active_cur, slashed, TIMELY_TARGET_FLAG_INDEX
        )
        out.append(psum_i64(jnp.where(cur_target, eff, zero)))
        return jnp.stack(out)

    def update_body(
        eff, activation, exit_e, withdrawable, slashed, part_prev,
        scores, balances, pu, pi, in_leak,
    ):
        prev_e, bias, recovery = pu[0], pu[1], pu[2]
        base_per_inc, act_incr, denom, incr = pi[0], pi[1], pi[2], pi[3]
        part_inc = pi[4 : 4 + _N_FLAGS]
        active_prev = (activation <= prev_e) & (prev_e < exit_e)
        eligible = active_prev | (
            slashed & (prev_e + jnp.uint64(1) < withdrawable)
        )
        flags = [
            _flag_mask(part_prev, active_prev, slashed, f)
            for f in range(_N_FLAGS)
        ]
        prev_target = flags[TIMELY_TARGET_FLAG_INDEX]

        # inactivity scores (spec process_inactivity_updates); the
        # inactivity penalty below reads the UPDATED scores
        one = jnp.uint64(1)
        hit = eligible & prev_target
        miss = eligible & ~prev_target
        scores = jnp.where(hit, scores - jnp.minimum(one, scores), scores)
        scores = jnp.where(miss, scores + bias, scores)
        scores = jnp.where(
            eligible & ~in_leak,
            scores - jnp.minimum(recovery, scores),
            scores,
        )

        # flag rewards/penalties (spec get_flag_index_deltas): products
        # are guarded < 2**62 BEFORE dispatch, so the masked lanes are
        # overflow-free exactly like the numpy fancy-indexed path
        base = (eff // incr) * base_per_inc
        rewards = jnp.zeros_like(eff)
        penalties = jnp.zeros_like(eff)
        wden = jnp.int64(WEIGHT_DENOMINATOR)
        zero = jnp.int64(0)
        for f, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
            w = jnp.int64(weight)
            rewards = rewards + jnp.where(
                eligible & flags[f] & ~in_leak,
                base * w * part_inc[f] // (act_incr * wden),
                zero,
            )
            if f != TIMELY_HEAD_FLAG_INDEX:
                penalties = penalties + jnp.where(
                    eligible & ~flags[f], base * w // wden, zero
                )
        penalties = penalties + jnp.where(
            eligible & ~prev_target,
            eff * scores.astype(jnp.int64) // denom,
            zero,
        )
        # apply_balance_deltas semantics: add rewards, clamp at zero
        b = balances + rewards
        balances = jnp.where(penalties > b, zero, b - penalties)
        return scores, balances

    col, rep = P(VALIDATOR_AXIS), P()

    def wrap(body, n_col_args, n_rep_args, n_out_cols):
        specs = (col,) * n_col_args + (rep,) * n_rep_args
        out_specs = rep if n_out_cols == 0 else (col,) * n_out_cols
        mapped = shard_map(
            body, mesh=mesh, in_specs=specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(mapped)

    sums = wrap(sums_body, 6, 1, 0)
    update = wrap(update_body, 8, 3, 2)
    return sums, update


def _programs_for(mesh):
    key = tuple(int(d.id) for d in np.ravel(mesh.devices))
    progs = _PROGRAMS.get(key)
    if progs is None:
        progs = _PROGRAMS[key] = _build_programs(mesh)
    return progs


def _pad(arr: np.ndarray, n_pad: int, fill) -> np.ndarray:
    out = np.full((n_pad,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def process_epoch_altair_mesh(state, preset: Preset, spec, devices=None) -> None:
    """Drop-in replacement for process_epoch_altair_vec with the column
    passes sharded over the device mesh. Raises VectorGuard when a
    magnitude guard (or an unsupported corner: the genesis epochs) needs
    the single-device/oracle path; the caller falls back."""
    from .per_epoch import (
        _current_epoch,
        _previous_epoch,
        _process_eth1_data_reset,
        _process_historical_roots_update,
        _process_randao_mixes_reset,
        _process_slashings_reset,
        _process_sync_committee_updates,
        _weigh_justification_and_finalization,
    )

    current_epoch = _current_epoch(state, preset)
    previous_epoch = _previous_epoch(state, preset)
    if current_epoch <= GENESIS_EPOCH + 1:
        # the genesis epochs skip justification/inactivity phases; they
        # run once per chain -- not worth a second program variant
        raise VectorGuard("mesh epoch path starts past genesis+1")
    original_validators = state.validators
    cols = _columns_for(state, preset)
    n = cols.n
    incr = spec.effective_balance_increment

    part_prev = _cached_col(
        state, "_lh_part_prev", state.previous_epoch_participation, np.uint8
    )
    part_cur = _cached_col(
        state, "_lh_part_cur", state.current_epoch_participation, np.uint8
    )
    scores0 = _cached_col(
        state, "_lh_scores", state.inactivity_scores, np.uint64
    )
    balances0 = _cached_col(state, "_lh_bal", state.balances, np.int64)

    mesh = _mesh_for(devices)
    n_shards = int(np.ravel(mesh.devices).size)
    n_pad = _pad_bucket(max(n, 1), n_shards)
    sums_fn, update_fn = _programs_for(mesh)

    from ..parallel.verify_sharded import VALIDATOR_AXIS

    col_sharding = NamedSharding(mesh, P(VALIDATOR_AXIS))
    rep_sharding = NamedSharding(mesh, P())

    with jax.enable_x64(True):
        def shard(arr):
            return jax.device_put(arr, col_sharding)

        def rep(arr):
            return jax.device_put(arr, rep_sharding)

        # padding rows: never active, never eligible, zero balance --
        # they vanish from every sum and the update pass is identity
        d_eff = shard(_pad(cols.eff, n_pad, 0))
        d_act = shard(_pad(cols.activation, n_pad, np.uint64(FAR_FUTURE_EPOCH)))
        d_exit = shard(_pad(cols.exit, n_pad, np.uint64(FAR_FUTURE_EPOCH)))
        d_wd = shard(_pad(cols.withdrawable, n_pad, np.uint64(0)))
        d_slashed = shard(_pad(cols.slashed, n_pad, False))
        d_part_prev = shard(_pad(part_prev, n_pad, np.uint8(0)))
        d_part_cur = shard(_pad(part_cur, n_pad, np.uint8(0)))
        d_scores = shard(_pad(scores0, n_pad, np.uint64(0)))
        d_balances = shard(_pad(balances0, n_pad, 0))

        epochs = rep(
            np.array([previous_epoch, current_epoch], dtype=np.uint64)
        )
        sums = np.asarray(
            sums_fn(
                d_eff, d_act, d_exit, d_slashed, d_part_prev, d_part_cur,
                epochs,
            )
        )

    total_eff = int(sums[0])
    active_count = int(sums[1])
    flag_sums = [int(v) for v in sums[2 : 2 + _N_FLAGS]]
    cur_target_sum = int(sums[-1])
    total_balance = _total_with_floor(total_eff, spec)

    # ALL magnitude guards run before any state mutation (the vec
    # contract): a guard that tripped mid-flight would hand the fallback
    # a half-processed state
    sqrt_total = integer_squareroot(total_balance)
    base_per_inc = incr * spec.base_reward_factor // sqrt_total
    active_increments = total_balance // incr
    if base_per_inc * 32 * max(PARTICIPATION_FLAG_WEIGHTS) * max(
        1, active_increments
    ) >= 2**62:
        raise VectorGuard("flag reward product near int64")
    if n and int(scores0.max(initial=0)) + spec.inactivity_score_bias >= 2**28:
        raise VectorGuard("inactivity score near overflow")

    # 1. justification & finalization from the psum'd balances
    prev_target_bal = _total_with_floor(
        flag_sums[TIMELY_TARGET_FLAG_INDEX], spec
    )
    cur_target_bal = _total_with_floor(cur_target_sum, spec)
    _weigh_justification_and_finalization(
        state, total_balance, prev_target_bal, cur_target_bal, preset
    )

    finality_delay = previous_epoch - state.finalized_checkpoint.epoch
    in_leak = finality_delay > spec.min_epochs_to_inactivity_penalty
    part_increments = [_total_with_floor(s, spec) // incr for s in flag_sums]
    denom = (
        spec.inactivity_score_bias
        * spec.inactivity_penalty_quotient_for(state.fork_name)
    )

    # 2-3. inactivity scores + flag rewards/penalties: ONE elementwise
    # sharded pass, no collectives
    with jax.enable_x64(True):
        pu = jax.device_put(
            np.array(
                [
                    previous_epoch,
                    spec.inactivity_score_bias,
                    spec.inactivity_score_recovery_rate,
                ],
                dtype=np.uint64,
            ),
            rep_sharding,
        )
        pi = jax.device_put(
            np.array(
                [base_per_inc, active_increments, denom, incr]
                + part_increments,
                dtype=np.int64,
            ),
            rep_sharding,
        )
        leak = jax.device_put(np.bool_(in_leak), rep_sharding)
        d_new_scores, d_new_balances = update_fn(
            d_eff, d_act, d_exit, d_wd, d_slashed, d_part_prev,
            d_scores, d_balances, pu, pi, leak,
        )
        scores = np.array(d_new_scores)[:n]
        balances = np.array(d_new_balances)[:n]

    new_scores = tuple(scores.tolist())
    state.inactivity_scores = new_scores
    state.__dict__["_lh_scores"] = (new_scores, scores)

    # 4. registry updates: eligibility marking / ejections / activation
    # queue are the SAME host-side rare paths as the vec module; the
    # churn limit consumes the mesh's psum'd active count
    active_cur = cols.active_at(current_epoch)
    assert int(active_cur.sum()) == active_count
    changed = _registry_updates_vec(
        state, cols, active_cur, current_epoch, preset, spec
    )

    # 5. slashings (rare hits, exact Python ints per hit -- shared
    # semantics with per_epoch_vec)
    slash_sum = sum(state.slashings)
    adjusted = min(
        slash_sum * spec.proportional_slashing_multiplier_for(state.fork_name),
        total_balance,
    )
    hits = np.nonzero(
        cols.slashed
        & (
            np.uint64(current_epoch + preset.epochs_per_slashings_vector // 2)
            == cols.withdrawable
        )
    )[0]
    for i in hits.tolist():
        penalty = (
            int(cols.eff[i]) // incr * adjusted // total_balance * incr
        )
        balances[i] = 0 if penalty > balances[i] else balances[i] - penalty

    # 6-7. eth1 + effective-balance hysteresis (balances are final now)
    _process_eth1_data_reset(state, preset)
    changed |= _effective_balance_updates_vec(state, cols, balances, spec)

    new_bal = tuple(balances.tolist())
    state.balances = new_bal
    state.__dict__["_lh_bal"] = (new_bal, balances)

    if changed or state.validators is not original_validators:
        from ..ssz.cached import surgical_list_update

        final = tuple(list(state.validators))
        surgical_list_update(
            state, "validators", original_validators, final, sorted(changed)
        )
    state.__dict__["_lh_epoch_cols"] = (state.validators, preset, cols)

    # 8-10. resets, historical roots, rotation, sync committees
    _process_slashings_reset(state, preset)
    _process_randao_mixes_reset(state, preset)
    _process_historical_roots_update(state, preset)
    rotated = state.current_epoch_participation
    state.previous_epoch_participation = rotated
    new_cur = (0,) * n
    state.current_epoch_participation = new_cur
    state.__dict__["_lh_part_prev"] = (rotated, part_cur)
    state.__dict__["_lh_part_cur"] = (new_cur, np.zeros(n, dtype=np.uint8))
    _process_sync_committee_updates(state, preset, spec)
