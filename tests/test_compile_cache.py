"""Persistent compile cache (utils/compile_cache.py): partition keying,
the shapes.json registry, and its surfacing through the existing
tpu_compile_cache_{hits,misses}_total telemetry.

arm() itself is NOT exercised against the live JAX config here — the
suite runs with LIGHTHOUSE_TPU_COMPILE_CACHE=0 (conftest) precisely so
no pytest process ever loads another process's AOT entries; these tests
drive the registry with explicit directories instead.
"""

import json
import os

import pytest

from lighthouse_tpu.utils import compile_cache as CC
from lighthouse_tpu.utils.metrics import (
    TPU_COMPILE_CACHE_HITS,
    TPU_COMPILE_CACHE_MISSES,
)


class TestShapeRegistry:
    def test_lookup_miss_then_recorded_hit(self, tmp_path):
        part = str(tmp_path)
        key = (8, 4, 4, 0)
        assert CC.shape_on_disk(key, part=part) is False
        CC.record_shape(key, part=part)  # "the compile completed"
        # a fresh process consulting the same file sees it warm
        assert CC.shape_on_disk(key, part=part) is True
        assert CC.seen_shapes(part) == {"8x4x4x0"}

    def test_distinct_shapes_accumulate(self, tmp_path):
        part = str(tmp_path)
        CC.record_shape((4, 4, 4, 0), part=part)
        CC.record_shape((4, 4, 4, 4), part=part)  # aggregated-grid variant
        assert CC.seen_shapes(part) == {"4x4x4x0", "4x4x4x4"}

    def test_corrupt_registry_treated_as_empty(self, tmp_path):
        part = str(tmp_path)
        with open(os.path.join(part, "shapes.json"), "w") as f:
            f.write("{not json")
        assert CC.seen_shapes(part) == set()
        assert CC.shape_on_disk((4, 4, 4, 0), part=part) is False
        CC.record_shape((4, 4, 4, 0), part=part)
        with open(os.path.join(part, "shapes.json")) as f:
            assert json.load(f) == ["4x4x4x0"]

    def test_unarmed_process_registry_is_inert(self):
        # with no armed partition every shape is "new" and nothing is
        # written anywhere
        saved = CC._ARMED_DIR
        CC._ARMED_DIR = None
        try:
            assert CC.shape_on_disk((99, 4, 4, 0)) is False
            CC.record_shape((99, 4, 4, 0))  # no-op, no crash
            assert CC.seen_shapes() == set()
        finally:
            CC._ARMED_DIR = saved

    def test_arm_refused_by_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("LIGHTHOUSE_TPU_COMPILE_CACHE", "0")
        saved = CC._ARMED_DIR
        assert CC.arm() == ""
        assert CC._ARMED_DIR == saved  # untouched: nothing was armed

    @pytest.mark.parametrize("from_env", [True, False])
    def test_arm_location(self, tmp_path, monkeypatch, from_env):
        """JAX_COMPILATION_CACHE_DIR, when set, is the cache and the only
        one; otherwise the checkout's fixed .jax_cache partition."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.delenv("LIGHTHOUSE_TPU_COMPILE_CACHE", raising=False)
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            want = str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            want = CC.partition(os.path.join(root, ".jax_cache"))
        saved = (CC._ARMED_DIR, jax.config.jax_compilation_cache_dir)
        try:
            assert CC.arm() == want
            assert CC.armed_dir() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isdir(want)
        finally:
            CC._ARMED_DIR = saved[0]
            jax.config.update("jax_compilation_cache_dir", saved[1])
            compilation_cache.reset_cache()

    def test_partition_is_platform_keyed(self, tmp_path):
        part = CC.partition(str(tmp_path))
        # conftest forces the cpu platform: the partition must carry the
        # host fingerprint so foreign AOT entries can never be loaded
        assert os.path.basename(part).startswith("cpu-")
        assert os.path.dirname(part) == str(tmp_path)


class TestTelemetrySurfacing:
    def test_disk_warm_shape_counts_as_compile_cache_hit(self, tmp_path):
        """A shape this process never marshalled, but a previous process
        finished compiling: tpu_compile_cache_hits_total, not a miss."""
        from lighthouse_tpu.crypto.bls.backends import jax_tpu

        part = str(tmp_path)
        key = (512, 8, 16, 0)
        CC.record_shape(key, part=part)  # "a previous process compiled it"
        saved_dir = CC._ARMED_DIR
        saved_seen = set(jax_tpu._seen_shape_buckets)
        CC._ARMED_DIR = part
        jax_tpu._seen_shape_buckets.discard(key)
        hits = TPU_COMPILE_CACHE_HITS.value
        misses = TPU_COMPILE_CACHE_MISSES.value
        try:
            assert jax_tpu._count_shape_bucket(*key) is None
            assert TPU_COMPILE_CACHE_HITS.value == hits + 1
            assert TPU_COMPILE_CACHE_MISSES.value == misses
            # and the second marshal of the same shape is an in-process hit
            assert jax_tpu._count_shape_bucket(*key) is None
            assert TPU_COMPILE_CACHE_HITS.value == hits + 2
        finally:
            CC._ARMED_DIR = saved_dir
            jax_tpu._seen_shape_buckets.clear()
            jax_tpu._seen_shape_buckets.update(saved_seen)

    def test_warm_pass_leaves_fresh_process_with_zero_misses(self, tmp_path):
        """The `cli warm` contract: after warm_compile registers every
        default bucket, a FRESH process (simulated by clearing the
        in-process bucket set; the disk registry survives) marshalling
        ANY warmed bucket scores only hits -- zero
        tpu_compile_cache_misses_total during slots. The injected runner
        keeps real XLA compiles (70s+ each) out of tier-1; the routing
        it records still proves each bucket drove the path the
        dispatcher would."""
        from lighthouse_tpu.crypto.bls.backends import jax_tpu

        part = str(tmp_path)
        saved_dir = CC._ARMED_DIR
        saved_seen = set(jax_tpu._seen_shape_buckets)
        CC._ARMED_DIR = part
        jax_tpu._seen_shape_buckets.clear()
        calls = []
        try:
            report = jax_tpu.warm_compile(
                runner=lambda kind, args: calls.append(
                    (kind, tuple(a.shape for a in args))
                )
            )
            assert len(report) == len(jax_tpu.DEFAULT_WARM_BUCKETS)
            assert all(row["compiled"] for row in report)
            for (n_b, k_b, m_b), (kind, shapes) in zip(
                jax_tpu.DEFAULT_WARM_BUCKETS, calls
            ):
                mesh = jax_tpu._mesh_eligible(n_b)
                if m_b < n_b and mesh:
                    # shard-threshold bucket on the multi-device test
                    # mesh: the grouped mesh body, membership mask
                    # sharded with the sets axis
                    assert kind == "mesh-grouped"
                    assert shapes[-2:] == ((n_b, m_b), (m_b,))
                elif mesh:
                    assert kind == "mesh"
                    assert shapes[0][0] == n_b  # per-set draws, expanded
                elif m_b < n_b:  # message aggregation collapses the bucket
                    assert kind == "aggregated"
                    # the grid's group axis is PINNED to n_b: the warmed
                    # shape is exactly what _marshal_batch produces
                    assert shapes[-1] == (m_b, jax_tpu.grid_bucket(n_b))
                else:
                    assert kind == "staged"
            # simulated fresh process: in-process set gone, disk registry
            # (what `cli warm` persisted under the datadir) remains
            jax_tpu._seen_shape_buckets.clear()
            misses = TPU_COMPILE_CACHE_MISSES.value
            hits = TPU_COMPILE_CACHE_HITS.value
            for row in report:
                assert jax_tpu._count_shape_bucket(*row["bucket"]) is None
            assert TPU_COMPILE_CACHE_MISSES.value == misses
            assert TPU_COMPILE_CACHE_HITS.value == hits + len(report)
        finally:
            CC._ARMED_DIR = saved_dir
            jax_tpu._seen_shape_buckets.clear()
            jax_tpu._seen_shape_buckets.update(saved_seen)

    def test_warm_buckets_cover_marshal_keys(self):
        """The default warm set covers the dispatcher's key family: the
        aggregated-grid key of every default bucket is (n, k, m, n) --
        grid_bucket pins the group axis -- and the per-set key is
        (n, k, n, 0)."""
        from lighthouse_tpu.crypto.bls.backends import jax_tpu

        for n_b, k_b, m_b in jax_tpu.DEFAULT_WARM_BUCKETS:
            g_b = jax_tpu.grid_bucket(n_b) if m_b < n_b else 0
            assert g_b in (0, n_b)  # never a traffic-dependent value

    def test_scheduler_churn_after_warm_scores_zero_misses(self, tmp_path):
        """The continuous-batching zero-JIT contract: after `cli warm`
        registers the default bucket family, SEEDED CHURN across every
        scheduler lane -- random batch sizes, random lanes, random
        message reuse, launches forced at random boundaries -- must
        marshal only warm shapes: zero tpu_compile_cache_misses_total,
        because merged launches pad to the nearest warmed grid capacity
        (`pad_to`) instead of inventing traffic-dependent shapes. The
        backend stub runs the REAL `_marshal_batch` (the shape-count
        seat) and skips only the device dispatch."""
        import random

        from lighthouse_tpu.crypto.bls import SecretKey, SignatureSet
        from lighthouse_tpu.crypto.bls import pipeline as bls_pipeline
        from lighthouse_tpu.crypto.bls import scheduler as bls_scheduler
        from lighthouse_tpu.crypto.bls.backends import jax_tpu

        class MarshalOnlyBackend:
            @staticmethod
            def dispatch_verify_signature_sets(
                sets, seed=None, groups=None, index_pack=None, pad_to=None
            ):
                jax_tpu._marshal_batch(
                    sets, seed=seed, groups=groups, pad_to=pad_to
                )
                return True

        part = str(tmp_path)
        saved_dir = CC._ARMED_DIR
        saved_seen = set(jax_tpu._seen_shape_buckets)
        CC._ARMED_DIR = part
        jax_tpu._seen_shape_buckets.clear()
        try:
            jax_tpu.warm_compile(runner=lambda kind, args: None)
            # simulated fresh process: the disk registry survives, the
            # in-process executable set does not
            jax_tpu._seen_shape_buckets.clear()
            pipe = bls_pipeline.configure(backend=MarshalOnlyBackend)
            sched = bls_scheduler.configure(pipeline=pipe)
            # marshal never verifies here, so one real signature serves
            # every (pubkey, message) combination in the churn pool
            sk = SecretKey(3)
            sig = sk.sign(b"\x42" * 32)
            pool = [
                SignatureSet.single_pubkey(sig, sk.public_key(), bytes([m]) * 32)
                for m in range(8)
            ]
            rng = random.Random(1234)
            misses = TPU_COMPILE_CACHE_MISSES.value
            hits = TPU_COMPILE_CACHE_HITS.value
            futs = []
            for step in range(60):
                lane = bls_scheduler.LANES[
                    rng.randrange(len(bls_scheduler.LANES))
                ]
                batch = [
                    pool[rng.randrange(len(pool))]
                    for _ in range(1 + rng.randrange(6))
                ]
                futs.append(sched.submit(batch, lane=lane, slot=step // 8))
                if rng.random() < 0.3:  # random launch boundary
                    assert futs[-1].result() is True
            for f in futs:
                assert f.result() is True
            sched.drain()
            assert sched.stats["launches"] > 0
            assert (
                TPU_COMPILE_CACHE_MISSES.value == misses
            ), "churn through the scheduler compiled a new shape"
            assert TPU_COMPILE_CACHE_HITS.value > hits
        finally:
            bls_scheduler.configure()
            bls_pipeline.configure()
            CC._ARMED_DIR = saved_dir
            jax_tpu._seen_shape_buckets.clear()
            jax_tpu._seen_shape_buckets.update(saved_seen)

    def test_cold_shape_is_a_miss_and_registers_only_after_dispatch(
        self, tmp_path
    ):
        """The marshal-time count returns the key for a cold shape but
        does NOT write the registry -- a process killed mid-compile must
        not leave a phantom warm entry. The dispatcher registers the key
        once the compile has actually completed."""
        from lighthouse_tpu.crypto.bls.backends import jax_tpu

        part = str(tmp_path)
        key = (1024, 8, 16, 32)
        saved_dir = CC._ARMED_DIR
        saved_seen = set(jax_tpu._seen_shape_buckets)
        CC._ARMED_DIR = part
        jax_tpu._seen_shape_buckets.discard(key)
        misses = TPU_COMPILE_CACHE_MISSES.value
        try:
            assert jax_tpu._count_shape_bucket(*key) == key
            assert TPU_COMPILE_CACHE_MISSES.value == misses + 1
            assert CC.seen_shapes(part) == set()  # not yet: compile pending
            CC.record_shape(key)  # what dispatch does after returning
            assert "1024x8x16x32" in CC.seen_shapes(part)
        finally:
            CC._ARMED_DIR = saved_dir
            jax_tpu._seen_shape_buckets.clear()
            jax_tpu._seen_shape_buckets.update(saved_seen)
