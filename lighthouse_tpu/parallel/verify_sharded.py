"""Signature-set batch verification sharded across a device mesh.

The TPU-native replacement for the reference's rayon parallel batch verify
(consensus/state_processing/src/per_block_processing/block_signature_verifier.rs:374-384,
rayon chunks -> per-chunk blst multi-pairing): signature sets are sharded
over a 1-D `sets` mesh axis with `shard_map`; each chip runs hash-to-G2,
ladders, and Miller loops for its shard; the two tiny cross-set reductions
(one G2 point, one Fp12 element) ride ICI all_gathers; the shared final
exponentiation is replicated.

This is the "v4-8 pod / 1M-validator synthetic network" configuration of
BASELINE.md: throughput scales with mesh size because per-set work
dominates and the collective payload is constant (~4.6 KB per chip).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crypto.bls.backends.jax_tpu import (
    verify_body,
    verify_body_grouped,
    verify_grouped_jit,
    verify_jit,
)
from ..obs import ledger as launch_ledger
from ..resilience.primitives import CircuitBreaker, EventLog
from ..utils import metrics, tracing

AXIS = "sets"

# The VALIDATOR-STATE axis: the pubkey table and the epoch-transition
# columns shard their validator-index dimension over this 1-D mesh
# (ROADMAP million-validator item). It is a different *name* from the
# batch `sets` axis -- batches shard by set, state shards by validator
# index -- but rides the same physical devices as the MeshVerifier.
VALIDATOR_AXIS = "validators"


def sets_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices, axis name 'sets'."""
    devices = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devices), (AXIS,))


def pow2_device_prefix(devices=None) -> list:
    """The largest power-of-two prefix of the device list: sharded state
    divides bucketed (power-of-two) row counts evenly, so odd device
    counts park the remainder devices rather than pad unevenly."""
    devices = list(jax.devices()) if devices is None else list(devices)
    size = 1
    while size * 2 <= len(devices):
        size *= 2
    return devices[:size]


def validators_mesh(devices=None) -> Mesh:
    """1-D validator-state mesh over a power-of-two device prefix."""
    return Mesh(np.array(pow2_device_prefix(devices)), (VALIDATOR_AXIS,))


def make_sharded_gather(mesh: Mesh):
    """Returns a jitted gather over `mesh`: the table (rows, 3, W) shards
    its leading validator-index axis; the (m,) index vector replicates.
    Each shard gathers the indices it OWNS (its contiguous row block)
    and zero-fills the rest; one int32 psum of the (m, 3, W) gathered
    rows -- every index is owned by exactly one shard -- completes the
    batch's rows on every chip. Out-of-range indices clip to the last
    row (the callers' `mode="clip"` contract; clipped rows are padding
    and masked downstream).

    The returned callable carries ``arg_specs`` so DeviceExecutor-style
    placement helpers know the table shards while indices replicate."""

    n_shards = int(mesh.devices.size)

    def gather_fn(table_shard, idx):
        rows = table_shard.shape[0]
        idx = jnp.clip(idx, 0, rows * n_shards - 1)
        off = jax.lax.axis_index(VALIDATOR_AXIS).astype(jnp.int32) * rows
        local = idx - off
        owned = (local >= 0) & (local < rows)
        vals = jnp.take(table_shard, jnp.where(owned, local, 0), axis=0)
        vals = jnp.where(owned[:, None, None], vals, 0)
        return jax.lax.psum(vals, VALIDATOR_AXIS)

    specs = (P(VALIDATOR_AXIS), P())
    kw = dict(mesh=mesh, in_specs=specs, out_specs=P())
    body = shard_map(gather_fn, check_vma=False, **kw)
    fn = jax.jit(body)

    # a plain wrapper because jit objects reject attribute assignment
    def call(table, idx):
        return fn(table, idx)

    call.arg_specs = specs
    return call


def make_sharded_verify(mesh: Mesh):
    """Returns a jitted verifier over `mesh`: inputs are globally-shaped
    arrays sharded on their leading (set) axis; output is a replicated
    scalar bool. Set counts must divide evenly by the mesh size (callers
    pad to bucket sizes, which are powers of two)."""

    spec = P(AXIS)
    rep = P()

    shard_fn = lambda u, pk, sig, r, real: verify_body(  # noqa: E731
        u, pk, sig, r, real, axis_name=AXIS
    )
    kw = dict(mesh=mesh, in_specs=(spec,) * 5, out_specs=rep)
    body = shard_map(shard_fn, check_vma=False, **kw)
    return jax.jit(body)


def make_sharded_verify_grouped(mesh: Mesh):
    """The GROUPED (mega-pairing) sharded verifier over `mesh`: per-set
    arrays and the (n, m) membership mask shard on the sets axis, while
    the distinct-message draws and real-message mask replicate -- the
    layout `verify_body_grouped` expects. Each shard reduces its local
    per-message pubkey partial sums; one all_gather of m tiny G1 points
    completes them, so the batch pays ~m Miller pairs instead of ~n.

    The returned callable carries ``arg_specs`` (one PartitionSpec per
    argument) so :class:`DeviceExecutor` can place the mixed
    sharded/replicated argument list correctly."""

    spec = P(AXIS)
    rep = P()
    specs = (rep, spec, spec, spec, spec, spec, rep)

    def shard_fn(u, pk, sig, r, real, member, msg_real):
        return verify_body_grouped(
            u, pk, sig, r, real, member, msg_real, axis_name=AXIS
        )

    kw = dict(mesh=mesh, in_specs=specs, out_specs=rep)
    body = shard_map(shard_fn, check_vma=False, **kw)
    fn = jax.jit(body)

    # a plain wrapper because jit objects reject attribute assignment
    def call(*args):
        return fn(*args)

    call.arg_specs = specs
    return call


# -- the resilient mesh (per-device breakers; ROADMAP pmap open item) --------


class MeshEmpty(ConnectionError):
    """Every device's breaker is open: there is no mesh to shard over.
    The FallbackBackend treats this like any other primary fault and
    degrades the batch to the cpu oracle -- the ONLY condition that
    should ever trip the whole backend off the accelerator."""


class DeviceExecutor:
    """Places globally-shaped batch arrays onto the mesh sharding and
    runs the compiled program. A separate object so chaos tests can wrap
    it in a FaultyProxy (resilience/faults.py) and inject a chip fault
    at exactly this boundary."""

    def run(self, fn, args, devices):
        if len(devices) == 1:
            placed = tuple(jax.device_put(a, devices[0]) for a in args)
        else:
            # per-set programs shard every arg; grouped programs publish
            # per-arg specs (replicated message draws + sharded masks)
            specs = getattr(fn, "arg_specs", None) or (P(AXIS),) * len(args)
            mesh = sets_mesh(devices)
            placed = tuple(
                jax.device_put(a, NamedSharding(mesh, s))
                for a, s in zip(args, specs)
            )
        return fn(*placed)


class DeviceProber:
    """Post-fault chip attribution: a trivial transfer + add on one
    device proves the chip (and its transport) is alive. Wrapped by
    chaos tests to script which chip 'died'."""

    def probe(self, device) -> bool:
        try:
            out = jax.device_put(jnp.zeros((), jnp.int32), device) + 1
            return int(out) == 1
        except Exception:  # noqa: BLE001 -- ANY device/transport fault
            # means this chip is unusable; the caller opens its breaker
            return False


class MeshVerdict:
    """Async verdict of a sharded batch: device work is enqueued;
    ``bool()`` blocks for the answer, and a chip fault surfacing at
    materialisation re-shards the batch over survivors before
    answering. ``is_ready()`` polls the underlying device buffer so
    schedulers (VerifyFuture.done) never have to block to ask."""

    __slots__ = ("_mesh", "_args", "_devs", "_out", "_value")

    def __init__(self, mesh, args, devs, out):
        self._mesh, self._args = mesh, args
        self._devs, self._out = devs, out
        self._value = None

    def is_ready(self) -> bool:
        if self._value is not None:
            return True
        ready = getattr(self._out, "is_ready", None)
        return bool(ready()) if callable(ready) else True

    def __bool__(self) -> bool:
        if self._value is None:
            self._value = self._mesh._materialize(
                self._devs, self._out, self._args
            )
        return self._value


class MeshVerifier:
    """Sharded batch verification with per-device circuit breakers.

    The resilience upgrade over `make_sharded_verify`: a chip fault
    mid-batch must cost one re-shard, not the whole accelerator backend
    (ROADMAP open item). Each device carries its own ``CircuitBreaker``
    (the mesh-agnostic primitives from ``resilience/``); a failed batch
    probes the participating chips, opens the breakers of the dead ones,
    and re-runs the SAME global batch over the surviving devices -- the
    shard programs are pure functions of globally-shaped arrays, so
    results are bit-identical at every mesh size (test_multichip's
    contract). Open breakers mature half-open on their denied budget, so
    a recovered chip re-probes back into the mesh automatically.

    Mesh sizes are powers of two (bucketed batches divide evenly); one
    eligible device runs the plain single-device program -- the "mesh of
    one" IS the single-chip path. No eligible device raises
    :class:`MeshEmpty`.
    """

    def __init__(
        self,
        devices=None,
        events: EventLog | None = None,
        breaker_factory=None,
        executor=None,
        prober=None,
        program_factory=None,
        grouped_program_factory=None,
    ):
        self.devices = (
            list(jax.devices()) if devices is None else list(devices)
        )
        self.events = events
        self.executor = executor or DeviceExecutor()
        self.prober = prober or DeviceProber()
        # devices-tuple -> compiled program; injectable so fake-device
        # unit tests never touch shard_map/Mesh
        self.program_factory = program_factory or (
            lambda devs: make_sharded_verify(sets_mesh(list(devs)))
        )
        self.grouped_program_factory = grouped_program_factory or (
            lambda devs: make_sharded_verify_grouped(sets_mesh(list(devs)))
        )
        if breaker_factory is None:
            # clock-free: after `denied_budget` skipped batches the lost
            # chip gets one half-open probe batch (tests inject clocked
            # or tighter-budget breakers)
            def breaker_factory(device):
                return CircuitBreaker(
                    failure_threshold=1,
                    denied_budget=8,
                    half_open_probes=1,
                    name=f"bls_mesh/{device.id}",
                    events=events,
                )

        self.breakers = {
            d.id: breaker_factory(d) for d in self.devices
        }
        self._compiled: dict[tuple, object] = {}

    # -- mesh formation ------------------------------------------------------

    def _select_mesh(self, n_sets: int, include_recovering=True) -> list:
        """The devices for this batch: healthy (closed-breaker) chips
        first, then recovering ones whose breaker admits a half-open
        probe -- the probe batch IS the re-probe. Power-of-two sized so
        bucketed batches divide evenly. Empty means no usable device.

        ``include_recovering=False`` is the post-fault re-shard path:
        recovery probes belong to FUTURE batches -- re-admitting a
        maturing chip while re-sharding around a fault would let a
        small-budget breaker wedge the batch on the same dead chip."""
        closed, recovering = [], []
        for d in self.devices:
            b = self.breakers[d.id]
            if b.state == CircuitBreaker.CLOSED:
                closed.append(d)
            elif include_recovering and b.allow():
                # allow() consumes the denied budget / probe slot
                recovering.append(d)
        mesh_devs = self._pow2_prefix(closed + recovering, n_sets)
        seated = {d.id for d in mesh_devs}
        unseated = [d for d in recovering if d.id not in seated]
        if unseated and mesh_devs:
            # a matured probe is GUARANTEED a seat: when the closed set
            # alone already fills the pow2 mesh, swap probes in for tail
            # seats (same mesh size). Otherwise a recovered chip whose
            # maturity never coincides with a mesh-size boundary would
            # burn its probe slot forever and the mesh would stay pinned
            # below the healthy device count.
            k = min(len(unseated), max(1, len(mesh_devs) // 2))
            mesh_devs = mesh_devs[: len(mesh_devs) - k] + unseated[:k]
            unseated = unseated[k:]
        for d in unseated:
            # probe slot spent with no seat available this batch: reopen
            # so the budget machinery keeps cycling instead of wedging
            # half-open with zero probes left
            self.breakers[d.id].record_failure()
        return mesh_devs

    @staticmethod
    def _pow2_prefix(devices, n_sets: int) -> list:
        if not devices:
            return []
        size = 1
        while size * 2 <= len(devices) and size * 2 <= n_sets:
            size *= 2
        return devices[:size]

    def _program(self, mesh_devices: tuple, grouped: bool = False):
        key = (("grouped",) if grouped else ()) + tuple(
            d.id for d in mesh_devices
        )
        fn = self._compiled.get(key)
        if fn is None:
            factory = (
                self.grouped_program_factory
                if grouped
                else self.program_factory
            )
            fn = self._compiled[key] = factory(mesh_devices)
        return fn

    @staticmethod
    def _n_sets(args) -> int:
        """The batch's bucketed set count: `real`'s length. The grouped
        7-arg layout carries it at position 4 (the trailing args are the
        membership and message masks); the per-set 5-arg layout last."""
        return int(args[4].shape[0] if len(args) == 7 else args[-1].shape[0])

    # -- verification --------------------------------------------------------

    def verify(self, args):
        """One batch over the current mesh: `args` is the 5-tuple of
        globally-shaped per-set arrays (u, pk, sig, scalars, real), or
        the grouped 7-tuple (u, pk, sig, scalars, real, member,
        msg_real) for the per-message group reduction.
        Dispatches the device work NOW and returns a :class:`MeshVerdict`
        whose ``bool()`` materialises the answer -- JAX surfaces
        execution faults at materialisation, not dispatch, so breaker
        accounting and survivor re-sharding both live behind the verdict
        (a fault at either point re-shards the SAME batch over the
        surviving devices before answering). Raises MeshEmpty when no
        device remains."""
        n_sets = self._n_sets(args)
        mesh_devs = self._select_mesh(n_sets)
        if not mesh_devs:
            raise MeshEmpty(
                f"all {len(self.devices)} mesh devices are broken open"
            )
        try:
            out = self._dispatch(mesh_devs, args)
        except Exception as exc:  # noqa: BLE001 -- placement/compile/
            # transport fault at dispatch: attribute by probing and fall
            # through to the blocking re-shard loop
            self._on_mesh_fault(mesh_devs, exc)
            return self._verify_blocking(args)
        return MeshVerdict(self, args, mesh_devs, out)

    def tracer(self):
        # the PROCESS tracer (see pipeline.tracer): mesh spans must land
        # in the same ring as the pipeline spans that dispatched them
        return tracing.default_tracer()

    def _dispatch(self, mesh_devs, args):
        metrics.BLS_SHARD_MESH_SIZE.set(len(mesh_devs))
        # a mesh of one runs the plain single-chip program: same
        # computation, no shard_map/collective overhead, and the
        # "survivor" path is literally the single-chip path
        grouped = len(args) == 7
        if len(mesh_devs) == 1:
            fn = verify_grouped_jit if grouped else verify_jit
        else:
            fn = self._program(tuple(mesh_devs), grouped)
        with self.tracer().span("mesh_dispatch", devices=len(mesh_devs)):
            return self.executor.run(fn, args, mesh_devs)

    def _record_chip_timing(
        self, mesh_devs, seconds: float, n_sets: int | None = None
    ) -> None:
        """Per-chip shard timing: a sharded batch is one collective, so
        every participating chip is charged the batch wall (tracer
        clock); the per-chip labels make a straggling chip visible as a
        LARGER last-batch wall once the mesh drops it. Also the mesh's
        launch-ledger seam: the per-chip wall is only known here, at
        materialisation."""
        for d in mesh_devs:
            metrics.MESH_CHIP_BATCH_SECONDS.set(str(d.id), seconds)
        launch_ledger.record(
            "mesh",
            bucket=n_sets,
            padded_sets=n_sets,
            devices=len(mesh_devs),
            chip_seconds=seconds,
        )

    def _materialize(self, mesh_devs, out, args) -> bool:
        """Block on a dispatched verdict; success/failure lands on the
        participating breakers HERE, because this is where XLA actually
        reports a chip death. A fault re-runs the batch on survivors."""
        tracer = self.tracer()
        t0 = tracer.clock.now()
        try:
            with tracer.span("mesh_materialize", devices=len(mesh_devs)):
                out = jax.block_until_ready(out)
        except Exception as exc:  # noqa: BLE001 -- a chip died between
            # dispatch and materialisation; re-shard the same batch
            self._on_mesh_fault(mesh_devs, exc)
            return self._verify_blocking(args)
        self._record_chip_timing(
            mesh_devs, tracer.clock.now() - t0, n_sets=self._n_sets(args)
        )
        self._record_mesh_success(mesh_devs)
        return bool(out)

    def _verify_blocking(self, args) -> bool:
        """The post-fault path: re-shard over survivors until the batch
        completes, materialising each attempt before trusting it. Fault
        rounds are bounded by the device count: recovery probes belong
        to FUTURE batches, so one batch can never spin on a mesh whose
        breakers keep maturing mid-call."""
        n_sets = self._n_sets(args)
        # lint: allow[retry-no-backoff] -- not a retry of the same
        # resource: each round runs on a DIFFERENT (shrunken) mesh, and
        # waiting out a backoff would stall consensus on a healthy
        # survivor set; pacing for the lost chip is the breaker budget
        for _ in range(len(self.devices) + 1):
            mesh_devs = self._select_mesh(n_sets, include_recovering=False)
            if not mesh_devs:
                break
            tracer = self.tracer()
            t0 = tracer.clock.now()
            try:
                with tracer.span(
                    "mesh_materialize", devices=len(mesh_devs)
                ):
                    out = jax.block_until_ready(
                        self._dispatch(mesh_devs, args)
                    )
            except Exception as exc:  # noqa: BLE001 -- any failure here
                # is a device/runtime fault (injected or real);
                # attribution happens by probing, never by parsing the
                # exception
                self._on_mesh_fault(mesh_devs, exc)
                continue
            self._record_chip_timing(
                mesh_devs, tracer.clock.now() - t0, n_sets=n_sets
            )
            self._record_mesh_success(mesh_devs)
            return bool(out)
        raise MeshEmpty(
            f"all {len(self.devices)} mesh devices are broken open"
        )

    def _record_mesh_success(self, mesh_devs) -> None:
        for d in mesh_devs:
            self.breakers[d.id].record_success()
        metrics.BLS_SHARDED_BATCHES.inc()
        if self.events is not None:
            self.events.record("mesh_verify", devices=len(mesh_devs))

    def _probe_ok(self, device) -> bool:
        try:
            return bool(self.prober.probe(device))
        except Exception:  # noqa: BLE001 -- a probe that RAISES (real
            # transport error or injected FaultyProxy fault) is a dead
            # chip, same as one that returns False
            return False

    def _on_mesh_fault(self, mesh_devs, exc) -> None:
        dead = [d for d in mesh_devs if not self._probe_ok(d)]
        if not dead:
            # unattributable fault (e.g. a compile error): charge every
            # participant so a persistent failure still opens the mesh
            # instead of looping forever
            dead = list(mesh_devs)
        for d in dead:
            self.breakers[d.id].record_failure()
        metrics.BLS_MESH_SHRINKS.inc()
        if self.events is not None:
            self.events.record(
                "mesh_shrink",
                error=type(exc).__name__,
                lost=len(dead),
                was=len(mesh_devs),
            )
