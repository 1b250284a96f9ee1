"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

The suite runs on the CPU: multi-chip sharding paths (shard_map over a
Mesh) are exercised on virtual CPU devices, and Pallas kernels run in
interpret mode. The chip is driven by `chip_smoke.py` through the chip
tool (`--chips 4` for the mesh); tests/test_chip_compile.py compiles the
main-path kernels for a described v5e without one.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache: DISABLED for pytest by default. XLA:CPU's
# executable deserializer segfaults non-deterministically when a pytest
# process LOADS scan-heavy entries that another process wrote (observed at
# tower.py fp_pow_static eager-scan executables and the staged verifier
# stages; in-process compiles never crash). Suite processes therefore
# compile in-memory, and any code path that would arm the persistent
# cache mid-suite (e.g. a cli `bn` invocation) is refused, so pytest
# processes can never load another process's AOT entries. Set
# LIGHTHOUSE_TPU_TEST_CACHE=1 to re-enable for cache debugging.
if os.environ.get("LIGHTHOUSE_TPU_TEST_CACHE") == "1":
    from lighthouse_tpu.utils.compile_cache import arm  # noqa: E402

    arm()
else:
    os.environ.setdefault("LIGHTHOUSE_TPU_COMPILE_CACHE", "0")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running scale benchmark")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (resilience layer); "
        "CI also runs these as a dedicated step",
    )
    config.addinivalue_line(
        "markers",
        "crash: deterministic crash-injection matrix (store WAL recovery); "
        "CI also runs these as a dedicated step",
    )
    config.addinivalue_line(
        "markers",
        "scenario: deterministic adversarial scenario harness runs "
        "(partitions/churn/storms/non-finality/crash-recovery); the "
        "dedicated scenario CI job runs the full matrix including slow",
    )
    config.addinivalue_line(
        "markers",
        "speculate: duty-driven precompute & speculative verification "
        "(speculate/): forgery/property suite plus the storm scenario "
        "with speculation attached; CI runs these as a dedicated step",
    )
    config.addinivalue_line(
        "markers",
        "fuzz: seeded scenario-plan fuzzing (harness/fuzz.py) — corpus "
        "replay runs in tier-1, the budgeted search rides the fuzz CI job",
    )
    config.addinivalue_line(
        "markers",
        "wire: scenario runs over the real wire transport (length-framed "
        "sockets, snappy frames, SSZ) instead of the in-memory bus",
    )
    config.addinivalue_line(
        "markers",
        "adversary: aggregation-soundness probes (rogue-key, RLC weight "
        "collision, subgroup/small-order, grouping cancellation, "
        "speculation poisoning) — tier-1 runs the fast cpu-oracle subset, "
        "the adversary CI job runs the full five-path matrix",
    )
    config.addinivalue_line(
        "markers",
        "cont_batch: bursty traffic through the continuous-batching "
        "scheduler (crypto/bls/scheduler.py): launch-audit invariants "
        "(no speculation ahead of queued validator lanes, deadline "
        "admission order) plus bit-identical replay; CI runs these as "
        "a dedicated step",
    )
    config.addinivalue_line(
        "markers",
        "kernels: Pallas kernel parity matrix (interpret mode on CPU); "
        "the fused tower/Miller kernels compile slowly in interpret "
        "mode, so these also carry `slow` and run in the dedicated "
        "kernels CI job, keeping tier-1 fast",
    )


def pytest_collection_modifyitems(session, config, items):
    """Run EVERY XLA-compiling test file FIRST, before anything that
    spawns server/daemon threads. XLA:CPU compilation (and executable
    deserialization) segfaults non-deterministically late in a long
    pytest process once network tests have left daemon threads behind --
    observed three times at ~60-85% progress inside backend_compile /
    get_executable_and_time, always under an eager kernel call that runs
    fine standalone or early. Front-loading all compile-heavy files gives
    them the young-process slot; pure-Python consensus/network tests run
    after."""
    compile_heavy = (
        "test_chip_compile",  # v5e AOT compiles: libtpu's compiler
        "test_multichip",  # biggest programs: keep the freshest slot
        "test_sharded_state",  # shard_map gather + mesh epoch programs
        "test_tpu_",
        "test_pallas_kernels",
        "test_bls_api",
        "test_bls_aggregation",  # compiles the mega-pairing group stage
        "test_bls_edge_matrix",
        "test_bls_adversary",  # slow matrix compiles the staged verifier
        "test_pubkey_table",
        "test_known_vectors",
        "test_ef_vectors",
        "test_pipeline",
    )

    def rank(item):
        path = str(item.fspath)
        for i, frag in enumerate(compile_heavy):
            if frag in path:
                return i
        return len(compile_heavy)

    items.sort(key=rank)
