"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

The suite runs on the CPU: multi-chip sharding paths (shard_map over a
Mesh) are exercised on virtual CPU devices, and Pallas kernels run in
interpret mode. The chip is driven by `chip_smoke.py` through the chip
tool (`--chips 4` for the mesh); tests/test_chip_compile.py compiles the
main-path kernels for a described v5e without one.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading

import pytest

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


# Per-test time limits: a test whose set-up or call runs past its limit
# fails with a message and a dump of every thread's stack, instead of
# holding its worker until the whole run is killed. Set-up gets twice the
# call's limit, as the `staged_verifier` session fixture compiles there
# (250-315 s with six workers on 8 CPU cores). The alarm's handler runs
# only between Python bytecodes, so a test stuck inside C++ (an XLA
# compile) is not failed at its limit: at twice the limit its stacks are
# dumped and the process ends, which pytest-xdist reports as that test's
# failure. LIGHTHOUSE_TPU_TEST_TIMEOUT_S changes the limits (0 = off).
TEST_TIMEOUT_S = int(os.environ.get("LIGHTHOUSE_TPU_TEST_TIMEOUT_S", "300"))
SETUP_TIMEOUT_S = 2 * TEST_TIMEOUT_S
# stacks go to the stderr the process started with, which pytest's output
# capture does not swallow
_STDERR_FD = os.dup(2)


@contextlib.contextmanager
def _time_limit(item, phase, limit):
    if limit <= 0 or threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        faulthandler.dump_traceback(file=_STDERR_FD)
        pytest.fail(
            f"{item.nodeid}: {phase} ran past its {limit} s time limit "
            "(LIGHTHOUSE_TPU_TEST_TIMEOUT_S)"
        )

    previous = signal.signal(signal.SIGALRM, expired)
    faulthandler.dump_traceback_later(2 * limit, exit=True, file=_STDERR_FD)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "set-up", SETUP_TIMEOUT_S):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call", TEST_TIMEOUT_S):
        yield


@pytest.fixture(scope="session")
def staged_verifier():
    """The staged device verifier compiled at its smallest shape bucket (4
    sets, 4 keys, 4 messages), once per worker and during set-up, so that
    a device-path test spends its time limit on its own shapes only."""
    from lighthouse_tpu.crypto.bls import SecretKey, SignatureSet
    from lighthouse_tpu.crypto.bls.backends import jax_tpu

    sk, msg = SecretKey(7), bytes(32)
    s = SignatureSet.single_pubkey(sk.sign(msg), sk.public_key(), msg)
    assert jax_tpu.verify_signature_sets([s], seed=0)



# Test files that compile large XLA programs, in groups that share their
# programs; the first two share the staged verifier. Without an order,
# five of the six workers compiled `staged_verifier` (265-315 s of set-up
# each) and `pytest tests/ -m 'not slow' -n 6 --dist load` took 565-614 s
# on 8 CPU cores; with one group a worker, two compile it and it took
# 445 s, the slowest worker being the first group's (418 s; the others
# 274-339 s).
COMPILE_GROUPS = (
    ("test_bls_api", "test_bls_aggregation"),
    ("test_bls_edge_matrix", "test_pubkey_table", "test_ef_vectors"),
    ("test_tpu_hash_to_curve", "test_tpu_pairing", "test_known_vectors"),
    ("test_tpu_tower", "test_tpu_curve", "test_tpu_limbs"),
    ("test_pallas_kernels", "test_chip_compile"),
    ("test_sharded_state", "test_multichip"),
)


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(session, config, items):
    """Start each pytest-xdist worker on one group of compile-heavy files.

    Under `--dist load` worker i first runs the i-th run of
    len(items) // workers // 4 consecutive tests (pytest-xdist's
    LoadScheduling.schedule), and only what follows goes to whichever
    worker is free. Putting one group at the head of each first run keeps
    each group on one worker, so no two workers compile the same
    programs. A file missing from the table that requests
    `staged_verifier` joins the second group. Runs after `-m` deselection,
    whose count sets the run length."""
    workers = int(os.environ.get("PYTEST_XDIST_WORKER_COUNT", "0"))
    if workers < 2:
        return
    run = max(len(items) // workers // 4, 2)
    group_of = {f: g for g, files in enumerate(COMPILE_GROUPS) for f in files}
    groups = [[] for _ in COMPILE_GROUPS]
    light = []
    for item in items:
        staged = "staged_verifier" in getattr(item, "fixturenames", ())
        g = group_of.get(item.path.stem, 1 if staged else None)
        (light if g is None else groups[g]).append(item)
    ordered, spill = [], []
    for g in range(workers):
        head = groups[g] if g < len(groups) else []
        spill += head[run:]
        fill = run - len(head[:run])
        ordered += head[:run] + light[:fill]
        light = light[fill:]
    spill += [i for g in groups[workers:] for i in g]
    items[:] = ordered + spill + light
