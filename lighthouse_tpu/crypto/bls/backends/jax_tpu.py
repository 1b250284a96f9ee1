"""TPU batch-verification backend -- the blst replacement (north star).

Reproduces `verify_multiple_aggregate_signatures` semantics (reference
crypto/bls/src/impls/blst.rs:36-119) as ONE jitted XLA program per
(set-bucket, pubkey-bucket) shape:

  host:   structural checks, SHA-256 field draws, random 64-bit weights
  device: hash-to-G2 map, per-set pubkey aggregation (log-depth tree of
          Jacobian adds), G2 subgroup checks, weight ladders on both sides,
          batched Miller loops, ONE shared final exponentiation.

Batch shapes are padded to power-of-two buckets so recompilation is rare
(warm shapes; the reference's analogue is its fixed <=64 gossip batch,
beacon_processor/mod.rs:189-190). Padded sets get weight 0, which makes
their pairing contribution exactly neutral and is masked out of validity
checks.

Marshaling cost is amortized exactly like the reference's
ValidatorPubkeyCache (validator_pubkey_cache.rs:10-23): decompressed limb
tensors are cached on key/signature objects and, for indexed validators,
in a device-resident `PubkeyTable` so steady-state host->device traffic is
indices + messages + signatures only.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ....obs import ledger as launch_ledger
from ....utils import compile_cache, metrics, tracing
from .. import aggregation as AG
from ..tpu import curve as TC
from ..tpu import hash_to_curve as THC
from ..tpu import limbs as L
from ..tpu import pairing as TP
from ..tpu import tower as T

W = L.W


# --- packing helpers (cached on the api objects) ---------------------------


def _pk_limbs(pk) -> np.ndarray:
    """PublicKey -> (3, W) projective limbs (Z = 1), cached on the object."""
    cached = getattr(pk, "_tpu_limbs", None)
    if cached is None:
        pt = pk.point
        cached = np.stack(
            [L.to_limbs(pt.x.n), L.to_limbs(pt.y.n), L.to_limbs(1)]
        ).astype(np.int32)
        try:
            pk._tpu_limbs = cached
        except AttributeError:
            pass  # __slots__ without the attr; recompute next time
    return cached


def _sig_limbs(sig) -> np.ndarray:
    """Signature -> (3, 2, W) projective limbs (infinity -> (0, 1, 0)),
    cached."""
    cached = getattr(sig, "_tpu_limbs", None)
    if cached is None:
        pt = sig.point
        out = np.zeros((3, 2, W), np.int32)
        if pt.inf:
            out[1, 0] = L.to_limbs(1)
        else:
            out[0, 0] = L.to_limbs(pt.x.c0.n)
            out[0, 1] = L.to_limbs(pt.x.c1.n)
            out[1, 0] = L.to_limbs(pt.y.c0.n)
            out[1, 1] = L.to_limbs(pt.y.c1.n)
            out[2, 0] = L.to_limbs(1)
        cached = out
        try:
            sig._tpu_limbs = cached
        except AttributeError:
            pass
    return cached


_INF_G1 = np.zeros((3, W), np.int32)  # projective infinity (0, 1, 0)
_INF_G1[1, 0] = 1


_draws_cache: dict[bytes, np.ndarray] = {}


def _field_draws_cached(message: bytes) -> np.ndarray:
    """Gossip batches repeat messages (same attestation data across sets);
    cache draws by message with a simple size cap."""
    key = bytes(message)
    hit = _draws_cache.get(key)
    if hit is None:
        hit = THC.hash_to_field([key])[0]
        if len(_draws_cache) > 8192:
            _draws_cache.clear()
        _draws_cache[key] = hit
    return hit


# --- device kernel ----------------------------------------------------------


_sum_points = TC.sum_points


# -G1 generator, affine, built host-side at import (a lazily jnp-computed
# constant would leak a tracer when first touched inside a jit trace).
from ..constants import G1_X as _G1_X, G1_Y as _G1_Y, P as _P  # noqa: E402

_NEG_G1_GEN_AFF = jnp.asarray(
    np.stack([L.to_limbs(_G1_X), L.to_limbs(_P - _G1_Y)])
)  # (2, W)


def _neg_g1_gen_aff():
    return _NEG_G1_GEN_AFF


def verify_body(u, pk_jac, sig_jac, scalars, real, axis_name=None):
    """The full batch-verify computation on one shard of sets.

    With `axis_name`, the two cross-set reductions (the weighted-signature
    point sum and the Miller-loop product) ride XLA collectives over the
    device mesh (all_gather + local tree-reduce: the reduced values are a
    single point / Fp12 element, tiny on the wire), and the final
    exponentiation runs replicated. This is the multi-chip sharding of the
    reference's rayon map-reduce (block_signature_verifier.rs:374-384).
    """
    # per-set pubkey aggregation: (n, k, 3, W) -> (n, 3, W)
    agg_pk = _sum_points(jnp.moveaxis(pk_jac, 1, 0), TC.FP)
    agg_pk_bad = TC.is_infinity(agg_pk, TC.FP) & real

    # signature subgroup membership (padded sets hold infinity: passes)
    sig_ok = TC.g2_subgroup_check(sig_jac)

    # message mapping H(m): (n, 3, 2, W)
    h = THC.map_to_g2(u)
    h_aff, h_inf = TC.to_affine_g2(h)

    # weight ladders: r_i * agg_pk_i and r_i * sig_i (r = 0 on padding)
    rpk = TC.scalar_mul_u64(agg_pk, scalars, TC.FP)
    rpk_aff, rpk_inf = TC.to_affine_g1(rpk)
    rsig = TC.scalar_mul_u64(sig_jac, scalars, TC.FP2)
    ssum = _sum_points(rsig, TC.FP2)
    if axis_name is not None:
        ssum = _sum_points(
            jax.lax.all_gather(ssum, axis_name, axis=0), TC.FP2
        )
    ssum_aff, ssum_inf = TC.to_affine_g2(ssum[None])

    # pairs: n x (r*pk, H(m)) plus (-g1, sum r*sig); the generator pair is
    # counted once globally -- shards beyond the first mask it to infinity.
    include_gen = jnp.asarray(True)
    if axis_name is not None:
        include_gen = jax.lax.axis_index(axis_name) == 0
    p_aff = jnp.concatenate([rpk_aff, _neg_g1_gen_aff()[None]], axis=0)
    p_inf = jnp.concatenate([rpk_inf, ~include_gen[None]], axis=0)
    q_aff = jnp.concatenate([h_aff, ssum_aff], axis=0)
    q_inf = jnp.concatenate([h_inf, ssum_inf | ~include_gen], axis=0)
    if axis_name is None:
        ok = TP.multi_pairing_is_one(p_aff, p_inf, q_aff, q_inf)
    else:
        f = TP.miller_loop(p_aff, p_inf, q_aff, q_inf)
        fprod = TP.fp12_prod(f, axis=0)
        fprod = TP.fp12_prod(
            jax.lax.all_gather(fprod, axis_name, axis=0), axis=0
        )
        ok = T.fp12_is_one(TP.final_exponentiation(fprod))
    valid = ok & jnp.all(sig_ok) & ~jnp.any(agg_pk_bad)
    if axis_name is not None:
        valid = jnp.all(jax.lax.all_gather(valid, axis_name))
    return valid


# One module-level jitted verifier: jax.jit itself caches one executable
# per input-shape bucket, and never evicts warm shapes.
verify_jit = jax.jit(verify_body)


def verify_body_grouped(
    u, pk_jac, sig_jac, scalars, real, member, msg_real, axis_name=None
):
    """The batch-verify computation with the PER-MESSAGE group reduction,
    shardable across a device mesh.

    The mega-pairing identity (crypto/bls/aggregation.py) collapses every
    set sharing a message into one Miller pair; `verify_device_aggregated`
    exploits it on a single chip via the gather grid. This body is its
    multi-chip form: the per-set arrays (pk/sig/scalars/real) and the
    (n, m) membership mask shard on the sets axis; each shard reduces its
    LOCAL weighted per-set pubkeys into per-message PARTIAL sums (a
    masked broadcast + one scanned halving body), one all_gather ships
    the m_b partial points (tiny: m_b is a handful) and every chip sums
    them into the full per-message pubkeys. The m_b + 1 Miller pairs then
    run REPLICATED on every chip -- at mega-batch sizes the per-set work
    (ladders, subgroup checks) dominates and m_b + 1 pairs are cheaper
    than a second collective round for fprod, so sharded mega-batches pay
    ~m Miller pairs instead of ~n.

    `u` holds the batch's DISTINCT message draws, `msg_real` masks padded
    message rows; both are replicated. Verdicts are bit-identical to the
    single-device aggregated path for the same weights.
    """
    # per-set prep: identical to verify_body
    agg_pk = _sum_points(jnp.moveaxis(pk_jac, 1, 0), TC.FP)
    agg_pk_bad = TC.is_infinity(agg_pk, TC.FP) & real
    sig_ok = TC.g2_subgroup_check(sig_jac)

    # distinct-message mapping H(m): replicated, m_b rows
    h = THC.map_to_g2(u)
    h_aff, h_inf = TC.to_affine_g2(h)

    # weight ladder, kept PROJECTIVE for the group sums
    rpk = TC.scalar_mul_u64(agg_pk, scalars, TC.FP)

    # local per-message partial sums: mask each set's weighted pubkey into
    # its message row (non-members -> infinity), then one halving body
    inf_g1 = TC.infinity(TC.FP)
    rows = jnp.where(
        jnp.moveaxis(member, 0, -1)[..., None, None], rpk[None], inf_g1
    )  # (m_b, n_loc, 3, W)
    part = _sum_points(jnp.moveaxis(rows, 1, 0), TC.FP)  # (m_b, 3, W)
    if axis_name is not None:
        # (shards, m_b, 3, W) -> full per-message pubkeys on every chip
        part = _sum_points(
            jax.lax.all_gather(part, axis_name, axis=0), TC.FP
        )
    gpk_aff, gpk_inf = TC.to_affine_g1(part)

    rsig = TC.scalar_mul_u64(sig_jac, scalars, TC.FP2)
    ssum = _sum_points(rsig, TC.FP2)
    if axis_name is not None:
        ssum = _sum_points(
            jax.lax.all_gather(ssum, axis_name, axis=0), TC.FP2
        )
    ssum_aff, ssum_inf = TC.to_affine_g2(ssum[None])

    # m_b + 1 pairs, replicated: every chip computes the SAME product, so
    # no fprod collective is needed (padded message rows are already
    # infinity partial sums; ~msg_real is belt-and-braces)
    p_aff = jnp.concatenate([gpk_aff, _neg_g1_gen_aff()[None]], axis=0)
    p_inf = jnp.concatenate([gpk_inf | ~msg_real, jnp.zeros((1,), bool)], axis=0)
    q_aff = jnp.concatenate([h_aff, ssum_aff], axis=0)
    q_inf = jnp.concatenate([h_inf, ssum_inf], axis=0)
    ok = TP.multi_pairing_is_one(p_aff, p_inf, q_aff, q_inf)

    valid = ok & jnp.all(sig_ok) & ~jnp.any(agg_pk_bad)
    if axis_name is not None:
        valid = jnp.all(jax.lax.all_gather(valid, axis_name))
    return valid


# the grouped monolith for a mesh of one (the single-chip survivor path)
verify_grouped_jit = jax.jit(verify_body_grouped)


# --- staged pipeline --------------------------------------------------------
#
# The monolithic verify_body is ONE very large XLA program. The staged
# pipeline splits the same computation into four separately-jitted
# programs: each compiles on its own (for a v5e, tens of seconds each:
# hash-to-G2 and the final exponentiation are the largest), each lands
# in the persistent compilation cache on its own, and the shape-
# independent ones (_stage_final) or message-keyed ones (_stage_hash,
# _stage_miller) are shared across batch shapes and paths. Steady state
# chains the stages on device (JAX dispatches asynchronously, so the
# pipeline costs a few enqueues, not four blocking round trips).


@jax.jit
def _stage_hash(u):
    """Message mapping H(m): field elements -> affine G2 points."""
    return TC.to_affine_g2(THC.map_to_g2(u))


@jax.jit
def _stage_prep(pk_jac, sig_jac, scalars, real):
    """Pubkey aggregation, subgroup checks, weight ladders, signature sum."""
    agg_pk = _sum_points(jnp.moveaxis(pk_jac, 1, 0), TC.FP)
    agg_pk_bad = TC.is_infinity(agg_pk, TC.FP) & real
    sig_ok = TC.g2_subgroup_check(sig_jac)
    rpk = TC.scalar_mul_u64(agg_pk, scalars, TC.FP)
    rpk_aff, rpk_inf = TC.to_affine_g1(rpk)
    rsig = TC.scalar_mul_u64(sig_jac, scalars, TC.FP2)
    ssum = _sum_points(rsig, TC.FP2)
    ssum_aff, ssum_inf = TC.to_affine_g2(ssum[None])
    flags_ok = jnp.all(sig_ok) & ~jnp.any(agg_pk_bad)
    return rpk_aff, rpk_inf, ssum_aff, ssum_inf, flags_ok


@jax.jit
def _stage_miller(rpk_aff, rpk_inf, h_aff, h_inf, ssum_aff, ssum_inf):
    """Pair assembly (incl. the -g1 generator pair), batched Miller loops,
    halving-scan product."""
    p_aff = jnp.concatenate([rpk_aff, _neg_g1_gen_aff()[None]], axis=0)
    p_inf = jnp.concatenate([rpk_inf, jnp.zeros((1,), bool)], axis=0)
    q_aff = jnp.concatenate([h_aff, ssum_aff], axis=0)
    q_inf = jnp.concatenate([h_inf, ssum_inf], axis=0)
    f = TP.miller_loop(p_aff, p_inf, q_aff, q_inf)
    return TP.fp12_prod(f, axis=0)


@jax.jit
def _stage_final(fprod, flags_ok):
    """ONE shared final exponentiation + the validity combine."""
    return T.fp12_is_one(TP.final_exponentiation(fprod)) & flags_ok


STAGES = (_stage_hash, _stage_prep, _stage_miller, _stage_final)


def verify_device(u, h_idx, pk_jac, sig_jac, scalars, real):
    """The staged batch verify, chained across the four stage executables
    (device-resident intermediates).

    `u` holds field draws for the batch's DISTINCT messages only and
    `h_idx` (n,) maps each set to its row: gossip batches repeat messages
    heavily (unaggregated attestations share attestation data -- the whole
    reason naive_aggregation_pool exists; aggregate batches repeat data
    across aggregators), and H(m) depends only on m, so hash-to-curve work
    scales with distinct messages, not sets. The per-set expansion is an
    eager device gather BETWEEN stages, so the prep/miller/final
    executables keep their warm per-set shapes regardless of how many
    distinct messages a batch carries."""
    h_aff_u, h_inf_u = _stage_hash(u)
    h_aff = jnp.take(h_aff_u, h_idx, axis=0)
    h_inf = jnp.take(h_inf_u, h_idx, axis=0)
    rpk_aff, rpk_inf, ssum_aff, ssum_inf, flags_ok = _stage_prep(
        pk_jac, sig_jac, scalars, real
    )
    fprod = _stage_miller(rpk_aff, rpk_inf, h_aff, h_inf, ssum_aff, ssum_inf)
    return _stage_final(fprod, flags_ok)


# --- message-aggregated (mega-pairing) stages -------------------------------
#
# The staged pipeline above pays one Miller pair PER SET (+1 generator
# pair). Mainnet traffic is thousands of sets over a handful of distinct
# messages, and the RLC check is bilinear in the G1 side, so the weighted
# per-set pubkeys of every set sharing a message collapse into ONE point
# (crypto/bls/aggregation.py derives the identity). The aggregated path
# REUSES _stage_prep verbatim (weights, subgroup checks, signature sum --
# its executable is already warm from the per-set path; per-shape compile
# cost is the scarce resource here) and inserts one small new program, the
# per-message group reduction, BEFORE pair assembly; _stage_miller /
# _stage_final then run at m_b + 1 pairs instead of n_b + 1 -- pairing
# cost scales with distinct messages, not sets -- and are shared verbatim
# with the per-set and aggregate_verify paths, so a warm (m_b + 1)-pair
# executable serves all three.


@jax.jit
def _stage_group(rpk_aff, rpk_inf, grid_idx, grid_real):
    """Per-message pubkey aggregation: gather the weighted per-set G1
    points into the (m_b, g_b) group grid (padding slots masked to
    infinity), lift to projective, and sum each message's row -- ONE
    scanned halving body over the group axis, batched over messages.
    Returns affine points + inf mask sized for the m_b-pair Miller
    stage."""
    rows_aff = jnp.take(rpk_aff, grid_idx, axis=0)  # (m_b, g_b, 2, W)
    rows_inf = jnp.take(rpk_inf, grid_idx, axis=0) | ~grid_real
    rows = TC.from_affine(rows_aff, rows_inf, TC.FP)
    gpk = TC.sum_points(jnp.moveaxis(rows, 1, 0), TC.FP)  # (m_b, 3, W)
    return TC.to_affine_g1(gpk)


def verify_device_aggregated(
    u, pk_jac, sig_jac, scalars, real, grid_idx, grid_real
):
    """The message-aggregated batch verify: the SAME per-set prep as
    `verify_device`, then a per-message group reduction, then ONE
    multi-pairing over m_b + 1 pairs (m_b = bucketed distinct messages).
    Accept/reject is algebraically identical to `verify_device` for the
    same weights -- the grouped product IS the per-set product by
    bilinearity -- so the CPU-oracle parity contract carries over
    unchanged (tests/test_bls_aggregation.py)."""
    h_aff, h_inf = _stage_hash(u)
    rpk_aff, rpk_inf, ssum_aff, ssum_inf, flags_ok = _stage_prep(
        pk_jac, sig_jac, scalars, real
    )
    gpk_aff, gpk_inf = _stage_group(rpk_aff, rpk_inf, grid_idx, grid_real)
    fprod = _stage_miller(
        gpk_aff, gpk_inf, h_aff, h_inf, ssum_aff, ssum_inf
    )
    return _stage_final(fprod, flags_ok)


def _bucket(n: int, floor: int = 4) -> int:
    """Next power-of-two shape bucket with a floor of 4: small batches all
    share ONE compiled kernel shape (the reference's warm-shape concern;
    its analogue is the fixed <=64 gossip batch)."""
    b = floor
    while b < n:
        b *= 2
    return b


def grid_bucket(n_b: int) -> int:
    """Aggregation-grid group-axis bucket: PINNED to the set bucket. A
    message can have at most n <= n_b member sets, so an (m_b, n_b) grid
    always fits every grouping; pinning removes the traffic-dependent
    max-group axis from the shape space entirely. The compile-shape key
    collapses from (n_b, k_b, m_b, g_b ~ traffic) to the fixed family
    (n_b, k_b, m_b) -- which is what makes the exhaustive deploy-time
    warm pass (`warm_compile` / `cli warm`) possible: a fresh node can
    enumerate and pre-compile EVERY shape it will ever see."""
    return n_b


def _common_table(sets):
    """The shared pubkey table if EVERY pubkey in the batch is tagged with
    the same one (by the chain's ValidatorPubkeyCache), else None."""
    table = None
    for s in sets:
        for key in s.pubkeys:
            t = getattr(key, "table", None)
            if t is None:
                return None
            if table is None:
                table = t
            elif t is not table:
                return None
    return table


# bucketed shapes marshalled so far: the observable face of jax.jit's
# executable cache -- a NEW bucket means XLA compiles, a seen one reuses
# the warm executable (the warm-shape contract of _bucket)
_seen_shape_buckets: set[tuple] = set()


def _count_shape_bucket(n_b: int, k_b: int, m_b: int, g_b: int = 0):
    """Count this batch's bucketed shape against the in-process and
    persistent compile caches. Keyed on the bucketed DEVICE-ARG shapes
    only: the gather and host-packed paths feed identically-shaped args
    to the same jit executables, so switching paths at a warm shape is a
    cache HIT (g_b = 0 marks the per-set path; nonzero the aggregated
    grid). Returns the shape key when an XLA compile is expected (cold
    in-process AND on disk) so the dispatcher can register it with the
    persistent registry AFTER the compile actually completes -- a
    process killed mid-compile must not leave a phantom 'warm' entry."""
    key = (n_b, k_b, m_b, g_b)
    if key in _seen_shape_buckets:
        metrics.TPU_COMPILE_CACHE_HITS.inc()
        return None
    _seen_shape_buckets.add(key)
    if compile_cache.shape_on_disk(key):
        # process-cold but DISK-warm: the persistent compilation cache
        # (utils/compile_cache.py) serves the
        # executables, so no XLA compile happens
        metrics.TPU_COMPILE_CACHE_HITS.inc()
        return None
    metrics.TPU_COMPILE_CACHE_MISSES.inc()
    return key


def _count_transfer(*arrays) -> None:
    """Host->device traffic of one batch (the np arrays actually shipped;
    the gather path ships indices, not limb rows)."""
    total = sum(int(a.nbytes) for a in arrays)
    metrics.TPU_TRANSFER_BYTES.inc(total)
    metrics.TPU_MARSHAL_BATCH_BYTES.set(total)


@dataclass
class Marshalled:
    """One marshalled batch: the device args of every dispatch path plus
    the aggregation grid (None on the per-set path) and host-side batch
    facts the dispatcher's metrics need."""

    u: object
    h_idx: object
    pk: object
    sig: object
    scalars: object
    real: object
    grid_idx: object  # (m_b, g_b) int32 device array, or None
    grid_real: object  # (m_b, g_b) bool device array, or None
    member: object  # (n_b, m_b) bool membership mask (grouped mesh), or None
    msg_real: object  # (m_b,) bool real-message mask (grouped mesh), or None
    n_sets: int
    n_messages: int
    # shape key to register as compiled once dispatch returns (None when
    # the shape was already warm in-process or on disk)
    new_shape_key: tuple | None = None


def _msg_agg_enabled() -> bool:
    """Message aggregation (the mega-pairing) is ON unless explicitly
    disabled; read per call so benches/tests flip it without reimport."""
    return os.environ.get("LIGHTHOUSE_TPU_MSG_AGG", "1") != "0"


def _mesh_eligible(n_b: int) -> bool:
    """Mirrors the dispatch routing: bucketed batches at/above the shard
    threshold go to the device mesh (per-set layout), so marshalling
    skips the aggregation grid for them."""
    threshold = _shard_min_sets()
    return bool(threshold) and n_b >= threshold and len(jax.devices()) > 1


def _pack_index_batch(sets, n_b: int, k_b: int):
    """The (n_b, k_b) validator-index / pubkey-count mask pack of one
    fully table-tagged batch -- the host loop of the gather path, split
    out so the pipeline can run it pre-marshal on the submit thread."""
    idx = np.zeros((n_b, k_b), np.int32)
    mask = np.zeros((n_b, k_b), bool)
    for i, s in enumerate(sets):
        for j, key in enumerate(s.pubkeys):
            idx[i, j] = key.validator_index
        mask[i, : len(s.pubkeys)] = True
    return idx, mask


def prepack_indices(sets):
    """Pipeline pre-marshal hook: the gather path's (idx, mask) pack when
    EVERY pubkey in the batch is tagged with the same device table, else
    None (the batch will host-pack limb rows instead). Pure host work --
    safe off the dispatch thread."""
    for s in sets:
        if not s.pubkeys or s.signature.point.inf:
            return None
    if _common_table(sets) is None:
        return None
    n_b = _bucket(len(sets))
    k_b = _bucket(max(len(s.pubkeys) for s in sets))
    return _pack_index_batch(sets, n_b, k_b)


def _marshal_batch(sets, seed=None, groups=None, index_pack=None, pad_to=None):
    """Host-side marshalling for one batch: shape bucketing, distinct-
    message grouping, limb packing (or device-table index gather),
    weights, and -- when the batch repeats messages -- the per-message
    aggregation grid for the mega-pairing path. Returns a `Marshalled`,
    or None when a structural check already decides the batch (empty
    pubkeys / infinity signature -> invalid, no device work). `groups`
    is an optional precomputed `aggregation.MessageGroups` and
    `index_pack` an optional precomputed `prepack_indices` result (the
    pipeline computes both pre-marshal on the submit thread).

    `pad_to` raises the set bucket to a WARMED capacity (the continuous-
    batching scheduler's re-batching contract): n_b is padded up to
    `_bucket(pad_to)` and, when the natural message bucket lands strictly
    between the warm family's {floor, n_b} endpoints, m_b is forced to
    n_b -- trading the mega-pairing's pair savings on that launch for a
    shape that is guaranteed warm (padded rows are masked projective
    infinities either way, so verdicts are unchanged)."""
    # host-side structural checks (cheap; device work is all-or-nothing)
    key_validate = _key_validate()
    for s in sets:
        if not s.pubkeys or s.signature.point.inf:
            return None
        if key_validate:
            # G1-side key_validate before any point reaches the device:
            # low-order cofactor components are pairing-INVISIBLE
            # (e(T, Q) == 1 for cofactor-order T), so the device pairing
            # cannot reject them — the host check here is the only gate.
            # Cached per object: chain pubkeys come through
            # PublicKey.from_bytes and answer for free.
            for pk in s.pubkeys:
                if not _api().pubkey_subgroup_ok(pk):
                    return None

    n = len(sets)
    k = max(len(s.pubkeys) for s in sets)
    n_b = _bucket(n)
    k_b = _bucket(k)
    if pad_to:
        n_b = max(n_b, _bucket(int(pad_to)))
        if index_pack is not None and index_pack[0].shape != (n_b, k_b):
            index_pack = None  # prepacked at the natural bucket; repack

    # Distinct-message grouping: maps each set to a row of the unique-
    # message draw tensor (hash-to-curve cost scales with distinct
    # messages; see verify_device) and names each message's member sets
    # (the aggregated path's group reduction). Padded sets point at row
    # 0 -- their pairing contribution is masked by weight 0 regardless.
    if groups is None:
        with tracing.span("bls_aggregate", sets=n):
            groups = AG.group_sets(sets)
    m = groups.n_messages
    h_idx = np.zeros((n_b,), np.int32)
    h_idx[:n] = groups.set_message
    m_b = _bucket(m)
    if pad_to and 4 < m_b < n_b:
        # the warm family only enumerates m_b in {floor, n_b}: a merged
        # launch whose distinct-message bucket lands in between takes the
        # (warm) per-set staged shape instead of a cold aggregated grid
        m_b = n_b
    u = np.zeros((m_b, 2, 2, W), np.int32)
    for j, msg in enumerate(groups.messages):
        u[j] = _field_draws_cached(msg)

    sig = np.zeros((n_b, 3, 2, W), np.int32)
    sig[:, 1, 0, 0] = 1  # projective infinity (0, 1, 0) on padded rows
    for i, s in enumerate(sets):
        sig[i] = _sig_limbs(s.signature)

    # Aggregation layout: only when grouping actually collapses BUCKETED
    # pairs (m_b < n_b -- the Miller stage runs at bucketed shapes, so
    # m < n inside the same power-of-two bucket would pay the group
    # reduction and a fresh compile shape for zero pair savings). The
    # group axis is PINNED to n_b (grid_bucket) so the shape family stays
    # enumerable for the warm pass. Single-chip batches take the gather
    # grid (verify_device_aggregated); mesh-eligible batches instead ship
    # an (n_b, m_b) membership mask that SHARDS with the sets axis -- the
    # grouped mesh body reduces per-message pubkey partial sums per shard
    # and all-gathers m_b points, paying ~m Miller pairs instead of ~n.
    grid_idx = grid_real = None
    member = msg_real = None
    g_b = 0
    if _msg_agg_enabled() and m_b < n_b:
        g_b = grid_bucket(n_b)
        if _mesh_eligible(n_b):
            member = np.zeros((n_b, m_b), bool)
            member[np.arange(n), groups.set_message] = True
            msg_real = np.zeros((m_b,), bool)
            msg_real[:m] = True
        else:
            grid_idx, grid_real = AG.group_grid(groups.members, m_b, g_b)

    table = _common_table(sets)
    new_shape_key = _count_shape_bucket(n_b, k_b, m_b, g_b)
    if table is not None:
        # Steady-state marshaling (validator_pubkey_cache.rs:10-23):
        # host->device traffic is validator INDICES; limb rows are gathered
        # from the device-resident (possibly mesh-sharded) table. The
        # eager gather feeds the same warm verify_jit executable as the
        # host-packed path.
        metrics.BLS_GATHER_HITS.inc()
        if index_pack is not None:
            idx, mask = index_pack
        else:
            idx, mask = _pack_index_batch(sets, n_b, k_b)
        rows = table.gather(idx)
        pk_dev = jnp.where(
            jnp.asarray(mask)[..., None, None], rows, jnp.asarray(_INF_G1)
        )
        pk_traffic = (idx, mask)
    else:
        metrics.BLS_GATHER_MISSES.inc()
        pk = np.broadcast_to(_INF_G1, (n_b, k_b, 3, W)).copy()
        for i, s in enumerate(sets):
            for j, key in enumerate(s.pubkeys):
                pk[i, j] = _pk_limbs(key)
        pk_dev = jnp.asarray(pk)
        pk_traffic = (pk,)

    scalars = _draw_weight_scalars(seed, n, n_b)

    real = np.zeros((n_b,), bool)
    real[:n] = True
    grid_traffic = () if grid_idx is None else (grid_idx, grid_real)
    group_traffic = () if member is None else (member, msg_real)
    _count_transfer(
        u, h_idx, sig, scalars, real,
        *grid_traffic, *group_traffic, *pk_traffic,
    )

    return Marshalled(
        u=jnp.asarray(u),
        h_idx=jnp.asarray(h_idx),
        pk=pk_dev,
        sig=jnp.asarray(sig),
        scalars=jnp.asarray(scalars),
        real=jnp.asarray(real),
        grid_idx=None if grid_idx is None else jnp.asarray(grid_idx),
        grid_real=None if grid_real is None else jnp.asarray(grid_real),
        member=None if member is None else jnp.asarray(member),
        msg_real=None if msg_real is None else jnp.asarray(msg_real),
        n_sets=n,
        n_messages=m,
        new_shape_key=new_shape_key,
    )


def _api():
    """Lazy api import (api imports backends lazily, so the cycle never
    bites, but keeping it out of module import time is free)."""
    from .. import api

    return api


def _key_validate() -> bool:
    return _api().key_validate_enabled()


def _draw_weight_scalars(seed, n: int, n_b: int, rng=None) -> np.ndarray:
    """Per-DISPATCH random-linear-combination weights for the device
    ladder: (n_b, 2) uint32 halves per set. The `| 1` on the second half
    guarantees every real weight is nonzero; this guard additionally
    redraws any 64-bit weight that COLLIDES with another in the same
    batch (two equal weights let a forged pair cancel inside the
    linear combination — crypto/bls/adversary.py builds exactly that
    batch) and counts redraws on bls_weight_redraws_total. Weights are
    drawn fresh from `seed` on every call — per dispatch, never per
    batch shape. `rng` is injectable so tests force collisions
    deterministically."""
    rng = rng if rng is not None else np.random.default_rng(seed)
    scalars = np.zeros((n_b, 2), np.uint32)
    if n:
        scalars[:n, 0] = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
        scalars[:n, 1] = rng.integers(0, 1 << 32, size=n, dtype=np.uint32) | 1
        w = scalars[:n, 0].astype(np.uint64) | (
            scalars[:n, 1].astype(np.uint64) << np.uint64(32)
        )
        while True:
            _, first = np.unique(w, return_index=True)
            dup = np.ones(n, bool)
            dup[first] = False
            d = int(dup.sum())
            if not d:
                break
            metrics.BLS_WEIGHT_REDRAWS.inc(d)
            lo = rng.integers(0, 1 << 32, size=d, dtype=np.uint32)
            hi = rng.integers(0, 1 << 32, size=d, dtype=np.uint32) | 1
            scalars[:n][dup, 0] = lo
            scalars[:n][dup, 1] = hi
            w[dup] = lo.astype(np.uint64) | (
                hi.astype(np.uint64) << np.uint64(32)
            )
    return scalars


def _shard_min_sets() -> int:
    """Bucketed-batch size at or above which the batch shards across the
    device mesh (0 disables sharding). Read per call: tests and operators
    retune it without reimporting."""
    return int(os.environ.get("LIGHTHOUSE_TPU_SHARD_MIN_SETS", "512"))


def _mesh_verifier():
    """Lazy module-level MeshVerifier (parallel/verify_sharded.py): one
    per process, so per-device breaker state and compiled shard programs
    persist across batches."""
    global _MESH
    if _MESH is None:
        from ....parallel.verify_sharded import MeshVerifier

        _MESH = MeshVerifier()
    return _MESH


_MESH = None


def _count_pairs(n_sets: int, pairs: int, aggregated: bool) -> None:
    """The pairing-cost telemetry of one dispatched batch: Miller-pair
    count (the latency driver the aggregation attacks) and sets-per-pair
    aggregation ratio (1.0-ish unaggregated; ~n/m on the mega-pairing)."""
    metrics.BLS_MILLER_PAIRS.inc(pairs)
    metrics.BLS_MILLER_PAIRS_LAST.set(pairs)
    metrics.BLS_AGGREGATION_RATIO.set(n_sets / pairs)
    if aggregated:
        metrics.BLS_AGGREGATED_BATCHES.inc()


def dispatch_verify_signature_sets(
    sets, seed=None, groups=None, index_pack=None, pad_to=None
):
    """Async half of `verify_signature_sets`: marshal + enqueue, NO host
    sync. Returns a zero-dim device bool (materialise with `bool()`), or
    a plain python bool when a structural check or the monolith/sharded
    path already decided the batch. The pipeline (crypto/bls/pipeline.py)
    overlaps the next batch's marshalling with this batch's device work
    and passes the message `groups` and gather `index_pack` it computed
    pre-marshal; `pad_to` pads the set bucket to a warmed capacity (the
    continuous-batching scheduler's zero-JIT re-batching contract).
    """
    with tracing.span("bls_marshal", sets=len(sets)):
        mb = _marshal_batch(
            sets, seed=seed, groups=groups, index_pack=index_pack, pad_to=pad_to
        )
    if mb is None:
        return False

    n_b = int(mb.real.shape[0])
    pairs = n_b + 1  # per-set default; aggregated branches override
    with tracing.span("bls_dispatch", bucket=n_b):
        if _mesh_eligible(n_b):
            # Multi-chip hot path: shard the per-set axis over the device
            # mesh; a chip fault shrinks the mesh over survivors (per-
            # device breakers) and raises MeshEmpty only when no device
            # is usable -- which the FallbackBackend degrades to the cpu
            # oracle. When marshalling built the membership mask the mesh
            # runs the GROUPED body: sharded mega-batches pay ~m Miller
            # pairs instead of ~n.
            if mb.member is not None:
                pairs = int(mb.u.shape[0]) + 1
                _count_pairs(mb.n_sets, pairs, aggregated=True)
                out = _mesh_verifier().verify(
                    (
                        mb.u, mb.pk, mb.sig, mb.scalars, mb.real,
                        mb.member, mb.msg_real,
                    )
                )
            else:
                _count_pairs(mb.n_sets, n_b + 1, aggregated=False)
                out = _mesh_verifier().verify(
                    (
                        jnp.take(mb.u, mb.h_idx, axis=0),
                        mb.pk, mb.sig, mb.scalars, mb.real,
                    )
                )
        elif os.environ.get("LIGHTHOUSE_TPU_MONOLITH") == "1":
            # the monolithic program takes per-set draws (no dedup axis)
            _count_pairs(mb.n_sets, n_b + 1, aggregated=False)
            out = verify_jit(
                jnp.take(mb.u, mb.h_idx, axis=0),
                mb.pk, mb.sig, mb.scalars, mb.real,
            )
        elif mb.grid_idx is not None:
            # mega-pairing: Miller-pair count rides the MESSAGE bucket
            pairs = int(mb.u.shape[0]) + 1
            _count_pairs(mb.n_sets, pairs, aggregated=True)
            out = verify_device_aggregated(
                mb.u, mb.pk, mb.sig, mb.scalars, mb.real,
                mb.grid_idx, mb.grid_real,
            )
        else:
            _count_pairs(mb.n_sets, n_b + 1, aggregated=False)
            out = verify_device(
                mb.u, mb.h_idx, mb.pk, mb.sig, mb.scalars, mb.real
            )
    if mb.new_shape_key is not None:
        # the jitted calls above return only once tracing + compile are
        # done (execution stays async), so the shape's executables now
        # exist and are persisted: safe to register for future processes
        compile_cache.record_shape(mb.new_shape_key)
    launch_ledger.record(
        "dispatch",
        bucket=n_b,
        real_sets=mb.n_sets,
        padded_sets=n_b,
        n_messages=mb.n_messages,
        miller_pairs=pairs,
        cache_hit=mb.new_shape_key is None,
    )
    return out


def verify_signature_sets(sets, seed=None) -> bool:
    return bool(dispatch_verify_signature_sets(sets, seed=seed))


# The shape families a fresh node sees in steady state: gossip batches
# (<= 64 sets, mostly distinct messages -> m_b == n_b, per-set staged
# path) and aggregate/backfill mega-batches (repeated messages -> m_b
# collapsed to the floor, aggregated path). The 512 bucket sits at the
# _shard_min_sets default, so on a multi-chip node it warms the MESH
# bodies (grouped + per-set) the dispatcher routes mega-batches to.
# k_b stays at the bucket floor for the dominant 1-pubkey sets;
# operators with heavier committee shapes pass their own bucket list to
# `warm_compile`.
DEFAULT_WARM_BUCKETS: tuple = tuple(
    sorted({(n_b, 4, m_b) for n_b in (4, 16, 64, 256, 512) for m_b in (4, n_b)})
)


def warm_compile(buckets=None, runner=None):
    """AOT bucket warm-up: compile (or load from the armed persistent
    cache) the backend executables for every shape bucket in `buckets`,
    so a fresh node never JITs during a slot.

    Each (n_b, k_b, m_b) bucket drives the SAME jitted entry points the
    dispatcher routes to -- the sharded mesh bodies when the bucket sits
    at/above the shard threshold on a multi-chip node (grouped when
    message aggregation collapses m_b below n_b, per-set otherwise), the
    aggregated grid path when message aggregation is on and m_b < n_b,
    else the per-set staged path -- with structurally-valid all-padding
    batches (XLA compilation is shape-keyed; values are irrelevant:
    padded rows hold projective infinities and zero scalars exactly like
    real padding). Shapes are scored and registered exactly like
    dispatched batches: cold shapes count on
    tpu_compile_cache_misses_total and land in the persistent registry
    after the executable exists, warm ones count hits. Per-bucket wall
    seconds are published on tpu_warm_compile_seconds (and returned) so
    deploys can budget the pass.

    `runner` is injectable for tests: called as runner(kind, args) with
    kind in {"staged", "aggregated", "mesh", "mesh-grouped"}; the
    default drives the real executables and blocks until compile + run
    complete. Returns a list of {"bucket", "seconds", "compiled"} dicts.
    """
    if buckets is None:
        buckets = DEFAULT_WARM_BUCKETS
    if runner is None:
        def runner(kind, args):
            if kind.startswith("mesh"):
                bool(_mesh_verifier().verify(args))
                return
            if kind == "aggregated":
                out = verify_device_aggregated(*args)
            else:
                out = verify_device(*args)
            jax.block_until_ready(out)

    report = []
    for n_b, k_b, m_b in buckets:
        aggregated = _msg_agg_enabled() and m_b < n_b
        g_b = grid_bucket(n_b) if aggregated else 0
        mesh = _mesh_eligible(n_b)
        u = jnp.zeros((m_b, 2, 2, W), jnp.int32)
        pk = jnp.broadcast_to(
            jnp.asarray(_INF_G1), (n_b, k_b, 3, W)
        ).astype(jnp.int32)
        sig = jnp.zeros((n_b, 3, 2, W), jnp.int32).at[:, 1, 0, 0].set(1)
        scalars = jnp.zeros((n_b, 2), jnp.uint32)
        real = jnp.zeros((n_b,), bool)
        new_key = _count_shape_bucket(n_b, k_b, m_b, g_b)
        t0 = time.monotonic()
        if mesh and aggregated:
            member = jnp.zeros((n_b, m_b), bool)
            msg_real = jnp.zeros((m_b,), bool)
            runner(
                "mesh-grouped",
                (u, pk, sig, scalars, real, member, msg_real),
            )
        elif mesh:
            u_set = jnp.zeros((n_b, 2, 2, W), jnp.int32)
            runner("mesh", (u_set, pk, sig, scalars, real))
        elif aggregated:
            grid_idx = jnp.zeros((m_b, g_b), jnp.int32)
            grid_real = jnp.zeros((m_b, g_b), bool)
            runner(
                "aggregated",
                (u, pk, sig, scalars, real, grid_idx, grid_real),
            )
        else:
            h_idx = jnp.zeros((n_b,), jnp.int32)
            runner("staged", (u, h_idx, pk, sig, scalars, real))
        seconds = time.monotonic() - t0
        if new_key is not None:
            compile_cache.record_shape(new_key)
        key = (n_b, k_b, m_b, g_b)
        metrics.TPU_WARM_COMPILE_SECONDS.set(
            "x".join(str(v) for v in key), seconds
        )
        launch_ledger.record(
            "warm",
            bucket="x".join(str(v) for v in key),
            real_sets=0,  # warm batches are all padding by construction
            padded_sets=n_b,
            compile_seconds=seconds,
            cache_hit=new_key is None,
        )
        report.append(
            {"bucket": key, "seconds": seconds, "compiled": new_key is not None}
        )
    return report


@jax.jit
def _stage_agg_prep(pk_jac, sig_jac, real):
    """Aggregate-verify prep: affine pubkeys (padding masked to infinity),
    signature subgroup check + affine. Small program; the heavy stages
    are shared with the batch verifier below."""
    pk_aff, pk_inf = TC.to_affine_g1(pk_jac)
    sig_ok = TC.g2_subgroup_check(sig_jac[None])[0]
    sig_aff, sig_inf = TC.to_affine_g2(sig_jac[None])
    return pk_aff, pk_inf | ~real, sig_aff, sig_inf, sig_ok


def aggregate_verify(signature, pubkeys, messages) -> bool:
    """Reference generic_aggregate_signature.rs aggregate_verify:
    prod_i e(pk_i, H(m_i)) * e(-g1, sig) == 1.

    Runs through the SAME staged executables as the batch verifier --
    _stage_miller's pair layout (per-row G1 points + the generator pair,
    per-row G2 points + one trailing G2 point) is exactly the aggregate
    pair structure, so only the tiny _stage_agg_prep is unique to this
    path. This staging is also load-bearing for robustness: the previous
    monolithic hash+Miller+final program was large enough to crash
    XLA:CPU's executable serializer when the persistent compile cache
    tried to store it."""
    # structural checks (lengths, empty, infinity) live in the api layer
    if _key_validate():
        for pk in pubkeys:
            if not _api().pubkey_subgroup_ok(pk):
                return False
    k = len(pubkeys)
    k_b = _bucket(k)
    u = np.zeros((k_b, 2, 2, W), np.int32)
    pk = np.broadcast_to(_INF_G1, (k_b, 3, W)).copy()
    for i, (key, msg) in enumerate(zip(pubkeys, messages)):
        u[i] = _field_draws_cached(bytes(msg))
        pk[i] = _pk_limbs(key)
    real = np.zeros((k_b,), bool)
    real[:k] = True
    real_dev = jnp.asarray(real)
    pk_aff, pk_inf, sig_aff, sig_inf, sig_ok = _stage_agg_prep(
        jnp.asarray(pk), jnp.asarray(_sig_limbs(signature)), real_dev
    )
    h_aff, h_inf = _stage_hash(jnp.asarray(u))
    fprod = _stage_miller(
        pk_aff, pk_inf, h_aff, h_inf | ~real_dev, sig_aff, sig_inf
    )
    return bool(_stage_final(fprod, sig_ok))


# --- device-resident pubkey table ------------------------------------------


def _shard_table_enabled() -> bool:
    """Mesh-sharding of the validator pubkey table is ON unless explicitly
    disabled; read per call so tests/benches flip it without reimport."""
    return os.environ.get("LIGHTHOUSE_TPU_SHARD_TABLE", "1") != "0"


class PubkeyTable:
    """Decompressed validator pubkeys resident on device, keyed by validator
    index -- the TPU analogue of the reference's ValidatorPubkeyCache
    (beacon_node/beacon_chain/src/validator_pubkey_cache.rs:10-23,131).
    Upload once per import of new validators; per-batch traffic is indices.

    Tables past a size floor shard their validator-index dimension over
    the `validators` mesh axis (parallel/verify_sharded.validators_mesh):
    each device holds a contiguous ~1/N slice of the bucketed rows instead
    of a full replica, so registry growth costs per-device HBM that scales
    DOWN with mesh size. Batches then pull exactly their indices through a
    shard_map gather (each index is owned by exactly one shard; a masked
    local take + psum lands the rows on every participating chip). Small
    tables -- below one 8-row shard floor per device, e.g. the committee-
    aggregate family -- stay replicated on the default device: a
    collective per batch would cost more than the bytes saved.

    `import_new_pubkeys` only invalidates: the next `device_table()` call
    re-places the grown bucket across the mesh, which re-balances the
    shards evenly (contiguous rows re-split N ways) rather than appending
    to the last shard.
    """

    def __init__(self):
        self._host = np.zeros((0, 3, W), np.int32)
        self._dev = None
        self._gather = None

    def __len__(self) -> int:
        return self._host.shape[0]

    @property
    def sharded(self) -> bool:
        self.device_table()
        return self._gather is not None

    def import_new_pubkeys(self, pubkeys) -> None:
        """Append validated pubkeys (mirrors import_new_pubkeys,
        validator_pubkey_cache.rs:79). The import is the device table's
        key_validate seam (blst runs it at decompression): every key is
        checked on-curve / in-subgroup / not-infinity BEFORE any limb
        row is packed, and the whole import is refused atomically on the
        first bad key — a low-order or infinity point must never become
        gatherable by validator index. Chain imports arrive through
        ValidatorPubkeyCache (PublicKey.from_bytes) with the verdict
        cached, so the steady-state cost is an attribute read per key."""
        if not pubkeys:
            return
        pubkeys = list(pubkeys)
        if _key_validate():
            for i, pk in enumerate(pubkeys):
                try:
                    ok = _api().pubkey_subgroup_ok(pk)
                except Exception:  # noqa: BLE001 -- malformed key object
                    ok = False
                if not ok:
                    raise _api().BlsError(
                        f"pubkey table import refused: key {i} failed "
                        "key_validate (malformed, infinity, or outside "
                        "the r-torsion subgroup)"
                    )
        rows = np.stack([_pk_limbs(pk) for pk in pubkeys])
        self._host = np.concatenate([self._host, rows], axis=0)
        self._dev = None  # re-place (and re-balance shards) lazily
        self._gather = None

    def device_table(self):
        if self._dev is None:
            from ....parallel.verify_sharded import (
                VALIDATOR_AXIS,
                make_sharded_gather,
                pow2_device_prefix,
                validators_mesh,
            )

            n = len(self._host)
            b = _bucket(max(n, 1), floor=8)
            padded = np.broadcast_to(_INF_G1, (b, 3, W)).copy()
            padded[:n] = self._host
            devs = pow2_device_prefix()
            n_dev = len(devs)
            if _shard_table_enabled() and n_dev > 1 and b >= n_dev * 8:
                from jax.sharding import NamedSharding, PartitionSpec

                mesh = validators_mesh(devs)
                self._dev = jax.device_put(
                    padded, NamedSharding(mesh, PartitionSpec(VALIDATOR_AXIS))
                )
                self._gather = make_sharded_gather(mesh)
                per_dev = padded.nbytes // n_dev
                for d in devs:
                    metrics.TPU_PUBKEY_TABLE_BYTES.set(str(d.id), per_dev)
            else:
                self._dev = jnp.asarray(padded)
                self._gather = None
                dev_id = next(iter(self._dev.devices())).id
                metrics.TPU_PUBKEY_TABLE_BYTES.set(str(dev_id), padded.nbytes)
        return self._dev

    def gather(self, indices):
        """Validator indices (any shape) -> (..., 3, W) device points.
        Out-of-range indices clip to the last bucketed row (marshalling
        masks them to infinity anyway). Routes through the shard_map
        gather when the table is mesh-sharded."""
        table = self.device_table()
        idx = jnp.asarray(indices, dtype=jnp.int32)
        metrics.TPU_PUBKEY_GATHER_BATCHES.inc()
        metrics.TPU_PUBKEY_GATHER_BYTES.inc(int(idx.size) * 3 * W * 4)
        if self._gather is None:
            return jnp.take(table, idx, axis=0, mode="clip")
        rows = self._gather(table, idx.reshape((-1,)))
        return rows.reshape(idx.shape + (3, W))


# --- speculative verification: committee aggregate residency ----------------
#
# The speculate/ subsystem precomputes one aggregate pubkey per
# (slot, committee) at the epoch boundary. Those synthetic keys live here,
# device-resident NEXT TO the validator PubkeyTable: registration packs
# each aggregate's limb tensor once (cached on the key object, so the
# host-pack marshal path ships a precomputed array instead of converting
# coordinates on the critical path) and parks the whole family on device
# for the staged subtract/correct program below.

_committee_table: PubkeyTable | None = None


def committee_table() -> PubkeyTable:
    global _committee_table
    if _committee_table is None:
        _committee_table = PubkeyTable()
    return _committee_table


def set_committee_aggregates(pubkeys) -> None:
    """Replace the device-resident committee-aggregate family (called per
    precompute refresh; entries are epoch-scoped so the table is rebuilt,
    not grown). Also warms each key's cached `_tpu_limbs`."""
    global _committee_table
    table = PubkeyTable()
    table.import_new_pubkeys(list(pubkeys))
    _committee_table = table
    if len(table):
        n = len(table)
        b = _bucket(max(n, 1), floor=8)
        metrics.SPECULATE_TABLE_BYTES.set(b * 3 * W * 4)


def _speculate_device_enabled() -> bool:
    return os.environ.get("LIGHTHOUSE_TPU_SPECULATE_DEVICE", "0") != "0"


@jax.jit
def _stage_correct(full, absent, absent_real):
    """full (3, W) projective aggregate; absent (k_b, 3, W) padded member
    points with a (k_b,) real mask -> affine corrected point
    (full - sum(absent)) + infinity flag."""
    F = TC.FP
    masked = TC.point_select(
        absent_real, absent, TC.infinity(F, absent.shape[:1]), F
    )
    s = _sum_points(masked, F)
    corrected = TC.add(full, TC.neg(s, F), F)
    aff, inf = TC.to_affine_g1(corrected[None])
    return aff[0], inf[0]


def correct_aggregate_device(full_pk, absent_pks):
    """Incremental correction on device: cached full-committee aggregate
    minus the absent members' points, as one staged program bucketed on
    the absent count (warm-executable reuse per the verifier's _bucket
    contract). Returns an oracle affine Point, or None on the degenerate
    all-absent result (caller falls back to host aggregation)."""
    from ..curve_ref import Point
    from ..fields_ref import Fp

    k = len(absent_pks)
    k_b = _bucket(max(k, 1))
    absent = np.broadcast_to(_INF_G1, (k_b, 3, W)).copy()
    for i, pk in enumerate(absent_pks):
        absent[i] = _pk_limbs(pk)
    real = np.zeros(k_b, bool)
    real[:k] = True
    aff, inf = _stage_correct(
        jnp.asarray(_pk_limbs(full_pk)), jnp.asarray(absent), jnp.asarray(real)
    )
    if bool(inf):
        return None
    aff = np.asarray(aff)
    return Point(Fp(L.to_int(aff[0])), Fp(L.to_int(aff[1])), False)
