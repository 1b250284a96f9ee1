"""Persistent on-disk XLA compilation cache, generalized into the backend.

The verifier's stage programs take tens of seconds each to compile for
a TPU, so every entry point arms JAX's persistent compilation cache
before its first jit. This module is the ONE place that decides where
that cache lives:

  * ``JAX_COMPILATION_CACHE_DIR`` set: the cache is exactly that
    directory, and no other directory is set in code.
  * Otherwise: the checkout's fixed ``.jax_cache``, partitioned by the
    platform the process compiles for (``tpu``, or ``cpu-<host CPU
    fingerprint>``: XLA:CPU's AOT loader aborts on entries compiled for
    another machine's feature set). The path never carries a temp name,
    a pid or a time -- the path is part of the cache's key, so a
    directory that moves never hits.

A sidecar ``shapes.json`` registry in the same directory records every
bucketed batch shape whose executables a process finished compiling:
the backend LOOKS a shape up at marshal time (``shape_on_disk``,
feeding ``tpu_compile_cache_hits_total`` for process-cold but disk-warm
shapes) and WRITES it only after the shape's first dispatch has
returned (``record_shape``) -- jit compilation is synchronous at call
time, so by then the executables exist and are persisted. A process
killed mid-compile therefore never registers the shape, and the next
process honestly counts a miss.

Registry updates are atomic-rename writes; concurrent processes can lose
an update (the next completed dispatch re-records it), which only ever
under-counts hits -- never corrupts the registry or the cache.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading

_ARMED_DIR: str | None = None


def host_cpu_fingerprint() -> str:
    """Stable short hash of the host's CPU feature flags (the AOT-entry
    compatibility domain of XLA:CPU executables)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha256(line.encode()).hexdigest()[:10]
    except OSError:
        pass
    import platform as _platform

    return hashlib.sha256(_platform.processor().encode()).hexdigest()[:10]


# the checkout root: three levels above lighthouse_tpu/utils/
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def partition(root: str) -> str:
    """The backend-keyed cache partition under ``root`` for the platform
    the current process will compile for (resolved WITHOUT initializing
    the backend: a device query here would freeze the platform before an
    entry point's own forcing could take effect)."""
    import jax

    platform = (
        jax.config.jax_platforms
        or os.environ.get("JAX_PLATFORMS")
        or "device"
    ).split(",")[0]
    sub = f"cpu-{host_cpu_fingerprint()}" if platform == "cpu" else "tpu"
    return os.path.join(root, sub)


def cache_dir() -> str:
    """Where this process's persistent compile cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the platform partition
    of the checkout's ``.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or partition(
        CHECKOUT_CACHE
    )


def arm() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()` and
    remember it for shape-registry lookups. Returns the directory. Set
    ``LIGHTHOUSE_TPU_COMPILE_CACHE=0`` to refuse (test suites,
    debugging)."""
    global _ARMED_DIR
    if os.environ.get("LIGHTHOUSE_TPU_COMPILE_CACHE") == "0":
        return ""
    import jax

    part = cache_dir()
    os.makedirs(part, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", part)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _ARMED_DIR = part
    return part


def armed_dir() -> str | None:
    return _ARMED_DIR


def _registry_path(part: str) -> str:
    return os.path.join(part, "shapes.json")


def seen_shapes(part: str | None = None) -> set[str]:
    part = part if part is not None else _ARMED_DIR
    if not part:
        return set()
    try:
        with open(_registry_path(part)) as f:
            loaded = json.load(f)
    except (OSError, json.JSONDecodeError):
        return set()
    return set(loaded) if isinstance(loaded, list) else set()


def _shape_name(key: tuple) -> str:
    return "x".join(str(int(v)) for v in key)


def shape_on_disk(key: tuple, part: str | None = None) -> bool:
    """True when a previous process finished compiling this bucketed
    shape under the armed partition (the persistent cache holds its
    executables: a hit for a process-cold shape). False when it is new
    here or no cache is armed. Read-only."""
    part = part if part is not None else _ARMED_DIR
    if not part:
        return False
    return _shape_name(key) in seen_shapes(part)


def record_shape(key: tuple, part: str | None = None) -> None:
    """Register one bucketed shape as COMPILED under the partition. Call
    only after the shape's first dispatch has returned -- that is the
    point at which its executables exist and have been persisted, so a
    crash/timeout mid-compile never leaves a phantom registry entry."""
    part = part if part is not None else _ARMED_DIR
    if not part:
        return
    shapes = seen_shapes(part)
    name = _shape_name(key)
    if name in shapes:
        return
    shapes.add(name)
    path = _registry_path(part)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "w") as f:
            json.dump(sorted(shapes), f)
        os.replace(tmp, path)
    except OSError:
        pass  # registry is advisory telemetry; never block dispatch
