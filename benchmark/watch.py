"""What the process spent on JAX's tracing and compiling, and on the
garbage collector, inside one phase of a run (the warm-up, the window).

JAX reports each trace, lowering and backend compile, and each hit and
write of the persistent compilation cache, through `jax.monitoring`;
the collector reports each collection through `gc.callbacks`. A Watch
registers its listeners once and counts only while a phase is open.
"""

from __future__ import annotations

import gc
import time

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_WRITE = "/jax/compilation_cache/cache_misses"
_DURATIONS = {TRACE: "trace", LOWER: "lower", COMPILE: "compile"}
_EVENTS = {CACHE_HIT: "cache_hits", CACHE_WRITE: "cache_writes"}


class Watch:
    def __init__(self):
        import jax

        self._open = False
        self._gc_start = None
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _reset(self) -> None:
        self._durations = {kind: {} for kind in _DURATIONS.values()}
        self._events = {kind: 0 for kind in _EVENTS.values()}
        self._gc = {}

    def _on_duration(self, event, duration, **kw) -> None:
        kind = _DURATIONS.get(event)
        if self._open and kind is not None:
            acc = self._durations[kind].setdefault(str(kw.get("fun_name")), [0, 0.0])
            acc[0] += 1
            acc[1] += duration

    def _on_event(self, event, **kw) -> None:
        kind = _EVENTS.get(event)
        if self._open and kind is not None:
            self._events[kind] += 1

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._open and self._gc_start is not None:
            pause = time.perf_counter() - self._gc_start
            acc = self._gc.setdefault(f"gen{info['generation']}", [0, 0.0, 0.0])
            acc[0] += 1
            acc[1] += pause
            acc[2] = max(acc[2], pause)

    def start(self) -> None:
        self._reset()
        self._open = True

    def stop(self) -> dict:
        """The phase's counts: per kind of JAX work its number and
        seconds and the three programs that took longest; the cache's
        hits and writes; per collector generation the number of
        collections, their seconds and the longest pause."""
        self._open = False
        out = {}
        for kind, per_fun in self._durations.items():
            top = sorted(per_fun.items(), key=lambda kv: -kv[1][1])[:3]
            out[kind] = {
                "n": sum(v[0] for v in per_fun.values()),
                "s": sum(v[1] for v in per_fun.values()),
                "top": [[name, v[0], v[1]] for name, v in top],
            }
        out.update(self._events)
        out["gc"] = {g: {"n": v[0], "s": v[1], "max_s": v[2]} for g, v in sorted(self._gc.items())}
        return out
