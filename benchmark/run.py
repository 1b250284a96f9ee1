"""Run one benchmark cell: `python -m benchmark.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`.

One process, JAX imported once. Set-up builds the signed pool from the
seed, imports the validator table through the chain's
ValidatorPubkeyCache (key_validate on) and uploads it, then sends every
pool batch once through the cell's entry point, so that every program
the window runs is compiled or loaded before it opens. The window is a
closed loop: the `sync` entry is `api.verify_signature_sets`, one batch
in flight, as block import calls it; the `async` entry is
`api.verify_signature_sets_async`, kept `in_flight` deep in the default
VerifyPipeline. Each submit builds fresh Signature and SignatureSet
objects from the pool's points; the public keys are the cache's own.

After the window every verdict it was due is collected and compared
with the plain reference (reference.py). The last line of standard
output is the result; the numbers compared, each beside its limit, are
the last lines of standard error and the result's last key. Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints
no result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import generator, reference, spec  # noqa: E402

# answers still due this long after the window closes count as missing
DRAIN_S = 60.0
# the compared numbers and their limits: exact comparisons
LIMITS = {"verdict_mismatches": 0, "missing_verdicts": 0}
# the device trace covers the window up to the verdict that brings the
# sets answered to TRACE_SETS: whole batches, about 1.5 s in either
# cell. These looping programs write about two million device events a
# second, which the profiler takes some 20 us each to collect, and the
# chip's trace buffer overflows after about 3.5 s of them.
TRACE_SETS = 192


class BenchmarkFault(RuntimeError):
    pass


class WallClock:
    """The monotonic clock the harness injects into the process tracer,
    so the program's spans carry seconds."""

    @staticmethod
    def now() -> float:
        return time.perf_counter()


class Registry:
    """A state's validator list as ValidatorPubkeyCache reads it
    (`validators[i].pubkey`): `rows` entries cycling over the keys."""

    def __init__(self, pubkeys, rows: int):
        self._entries = [SimpleNamespace(pubkey=b) for b in pubkeys]
        self._rows = rows

    def __len__(self) -> int:
        return self._rows

    def __getitem__(self, i: int):
        return self._entries[i % len(self._entries)]


class Client:
    """Builds the program's objects for each submission of the pool."""

    def __init__(self, pool, cache, traffic: dict, seed: int):
        from lighthouse_tpu.crypto.bls import Signature, SignatureSet
        from lighthouse_tpu.crypto.bls.curve_ref import Point
        from lighthouse_tpu.crypto.bls.fields_ref import Fp2

        self._types = (Signature, SignatureSet, Point, Fp2)
        self.pool, self.traffic, self.seed = pool, traffic, seed
        self._keys = [
            [[cache.get(r) for r in s.rows] for s in b.sets] for b in pool.batches
        ]

    def sets(self, batch: int, twin):
        Signature, SignatureSet, Point, Fp2 = self._types
        keys = self._keys[batch]
        return [
            SignatureSet(
                Signature(Point(Fp2(*sig[0]), Fp2(*sig[1]), False)),
                list(keys[i]),
                message,
            )
            for i, (_, message, sig) in enumerate(
                generator.batch_sets(self.pool, batch, twin)
            )
        ]


@dataclass
class Submit:
    k: int
    batch: int
    twin: object
    n_sets: int
    t_submit: float
    t_verdict: float | None = None
    verdict: object = None
    error: str | None = None


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _entry(traffic: dict):
    from lighthouse_tpu.crypto.bls import api

    if traffic["entry"] == "sync":
        return api.verify_signature_sets, False
    if traffic["entry"] == "async":
        return api.verify_signature_sets_async, True
    raise BenchmarkFault(f"unknown entry {traffic['entry']!r}")


class Profile:
    """The profiler over the window's first TRACE_SETS answered sets,
    host spans from annotations only (no Python tracer), marked by
    `bench.trace`."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.on = False
        self._mark = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self.on = True

    def open_window(self) -> None:
        self._mark = _annotate("bench.trace")
        self._mark.__enter__()

    def stop(self) -> None:
        import jax

        if self.on:
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def warm_up(client: Client, traffic: dict, seed: int) -> None:
    """Every pool batch once, and one forged twin, through the entry."""
    call, is_async = _entry(traffic)
    todo = [(b, None) for b in range(len(client.pool.batches))] + [(0, 0)]
    futures = []
    for i, (b, twin) in enumerate(todo):
        out = call(client.sets(b, twin), seed=generator.rlc_seed(seed, i, stream=3))
        futures.append(out)
    if is_async:
        for f in futures:
            f.result()


def window(client: Client, traffic: dict, seed: int, seconds: float, profile=None):
    """The measured closed loop. Returns (submits, t_start, t_end, late)
    where `late` holds the futures still in flight at the close. With a
    `profile`, the trace stops once TRACE_SETS sets have their verdict."""
    call, is_async = _entry(traffic)
    depth = int(traffic["in_flight"])
    clock = time.perf_counter
    submits: list = []
    outstanding: deque = deque()
    t_start = clock()
    t_end = t_start + seconds
    answered = 0
    if profile is not None:
        profile.open_window()
    k = 0
    with _annotate("bench.window"):
        while clock() < t_end:
            if profile is not None and profile.on and answered >= TRACE_SETS:
                profile.stop()
            if len(outstanding) >= depth:
                rec, fut = outstanding.popleft()
                with _annotate("bench.wait"):
                    _resolve(rec, fut, clock)
                answered += rec.n_sets
                continue
            batch, twin = generator.submission(traffic, client.pool, k)
            with _annotate("bench.build"):
                sets = client.sets(batch, twin)
            rec = Submit(k, batch, twin, len(sets), clock())
            with _annotate("bench.submit"):
                try:
                    out = call(sets, seed=generator.rlc_seed(seed, k))
                except Exception as e:  # noqa: BLE001 -- a fault is a wrong answer
                    rec.error = repr(e)
                    out = None
            submits.append(rec)
            if rec.error is None:
                if is_async:
                    outstanding.append((rec, out))
                else:
                    rec.verdict, rec.t_verdict = out, clock()
                    answered += rec.n_sets
            k += 1
    if profile is not None:
        profile.stop()
    return submits, t_start, t_end, outstanding


def _resolve(rec: Submit, fut, clock) -> None:
    try:
        rec.verdict = fut.result()
    except Exception as e:  # noqa: BLE001 -- a fault is a wrong answer
        rec.error = repr(e)
    rec.t_verdict = clock()


def drain(outstanding, t_end: float) -> None:
    """Collect the answers still due: each one the device has finished,
    and any other up to DRAIN_S past the close. A late answer is late,
    not missing; its latency is already counted at its age at the close."""
    clock = time.perf_counter
    for rec, fut in outstanding:
        while not fut.done() and clock() < t_end + DRAIN_S:
            time.sleep(0.01)
        if fut.done():
            _resolve(rec, fut, clock)
        else:
            rec.error = "no verdict within the drain"


def end_to_end(submits, t_start: float, t_end: float) -> dict:
    """`sets_per_s`: the sets answered inside the window over the time
    from its start to the last of those answers, so that the rate moves
    with the work and not in steps of one batch; `verdict_p95_ms`: the
    95th percentile of every submitted set's wait, a set unanswered at
    the close counting at its age then."""
    done = [s for s in submits if s.t_verdict is not None and s.t_verdict <= t_end]
    if not done:
        raise BenchmarkFault("no verdict inside the window")
    last = max(s.t_verdict for s in done)
    sets_per_s = sum(s.n_sets for s in done) / (last - t_start)
    ages = np.repeat(
        [
            (min(s.t_verdict, t_end) if s.t_verdict is not None else t_end)
            - s.t_submit
            for s in submits
        ],
        [s.n_sets for s in submits],
    )
    return {
        "sets_per_s": sets_per_s,
        "verdict_p95_ms": float(np.percentile(ages, 95)) * 1e3,
    }


def stalls(submits, t_start: float, t_end: float) -> dict:
    """The longest wait between answers inside the window, where it
    began, the median wait, and how long the last answer lay before the
    close when a submit was still unanswered then: a pause of the host
    or the device shows as one long wait."""
    times = sorted(
        s.t_verdict for s in submits if s.t_verdict is not None and s.t_verdict <= t_end
    )
    edges = [t_start] + times
    gaps = [(b - a, a - t_start) for a, b in zip(edges, edges[1:])]
    if not gaps:
        return {}
    longest, at = max(gaps)
    return {
        "answers": len(times),
        "longest_gap_s": longest,
        "longest_gap_at_s": at,
        "median_gap_s": float(np.median([g for g, _ in gaps])),
        # a submit still unanswered at the close has waited at least this
        "open_at_close_s": t_end - edges[-1] if len(times) < len(submits) else 0.0,
    }


def compare(submits, pool) -> tuple:
    """(checks, failed sets): every answer of the window against the
    reference. A forged batch the reference accepts, or a valid one it
    rejects, is a fault of the benchmark, not of the program."""
    ref = reference.Reference(pool)
    reference.check_registry(pool)
    mismatches = missing = failed = 0
    for s in submits:
        want = ref.batch_valid(generator.batch_sets(pool, s.batch, s.twin))
        if want != (s.twin is None):
            raise reference.ReferenceFault(
                f"reference verdict {want} for batch {s.batch} twin {s.twin}"
            )
        if s.error is not None or not isinstance(s.verdict, bool):
            missing += 1
            failed += s.n_sets
        elif s.verdict != want:
            mismatches += 1
            failed += s.n_sets
    checks = {
        "verdict_mismatches": {"value": mismatches, "limit": LIMITS["verdict_mismatches"]},
        "missing_verdicts": {"value": missing, "limit": LIMITS["missing_verdicts"]},
    }
    return checks, failed


def counters() -> dict:
    """Every unlabelled counter and gauge of the program's registry."""
    from lighthouse_tpu.utils.metrics import REGISTRY

    out = {}
    for line in REGISTRY.expose().splitlines():
        if line.startswith("#") or "{" in line:
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    return out


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
    }


_WATCH = None


def _watch():
    """The process's one Watch (its listeners stay registered)."""
    global _WATCH
    if _WATCH is None:
        from benchmark.watch import Watch

        _WATCH = Watch()
    return _WATCH


def _cold_dispatches(records) -> int:
    """Launches whose shape missed the program's compile caches."""
    return sum(1 for r in records if r.kind == "dispatch" and r.cache_hit is False)


def _last_seq(led) -> int:
    recs = led.records()
    return recs[-1].seq if recs else -1


def _spans_in(tracer, t_start: float, t_end: float) -> list:
    return [
        s for s in tracer.finished_spans()
        if s.end is not None and s.start >= t_start and s.end <= t_end
    ]


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, trace_dir=None):
    """Set-up, window, comparison and metrics of one run: the result."""
    import jax

    from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
    from lighthouse_tpu.obs import ledger
    from lighthouse_tpu.utils import compile_cache, tracing

    from benchmark import trace_reduce

    compile_cache.arm()
    tracer = tracing.configure(clock=WallClock(), capacity=1 << 17) if trace else None
    phases = {"imports_s": time.monotonic() - _T0}
    t = time.monotonic()
    pool = generator.build_pool(cell.config, cell.traffic, seed)
    phases["pool_s"] = time.monotonic() - t
    t = time.monotonic()
    state = SimpleNamespace(validators=Registry(pool.pubkeys, pool.rows))
    cache = ValidatorPubkeyCache(state)
    phases["table_import_s"] = time.monotonic() - t
    t = time.monotonic()
    jax.block_until_ready(cache.device_table())
    phases["table_upload_s"] = time.monotonic() - t
    t = time.monotonic()
    client = Client(pool, cache, cell.traffic, seed)
    watch = _watch()
    c0 = counters()
    seq0 = _last_seq(ledger.default_ledger())
    watch.start()
    warm_up(client, cell.traffic, seed)
    work = watch.stop()
    phases["warm_up_s"] = time.monotonic() - t
    setup_s = time.monotonic() - _T0
    c1 = counters()
    # what the warm-up spent its time on: JAX's tracing, lowering and
    # compiling, the persistent cache, and the program's own count of
    # shapes that missed its caches
    work["tpu_compile_cache_misses_total"] = c1.get(
        "tpu_compile_cache_misses_total", 0.0
    ) - c0.get("tpu_compile_cache_misses_total", 0.0)
    work["cold_dispatches"] = _cold_dispatches(
        r for r in ledger.default_ledger().records() if r.seq > seq0
    )
    phases["warm_up"] = work
    print(f"setup {json.dumps(phases)}", file=sys.stderr, flush=True)

    c0 = counters()
    seq0 = _last_seq(ledger.default_ledger())
    profile = Profile(trace_dir) if trace else None
    if profile is not None:
        profile.start()
    watch.start()
    submits, t_start, t_end, late = window(
        client, cell.traffic, seed, seconds, profile
    )
    in_window = watch.stop()
    c1 = counters()
    launches = [
        r.to_dict() for r in ledger.default_ledger().records() if r.seq > seq0
    ]
    in_window.update(stalls(submits, t_start, t_end))
    print(f"window {json.dumps(in_window)}", file=sys.stderr, flush=True)
    spans = _spans_in(tracer, t_start, t_end) if trace else []
    drain(late, t_end)
    device = device_info(devices)
    del client, cache, state, late
    gc.collect()

    checks, failed = compare(submits, pool)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": sum(s.n_sets for s in submits),
        "failed": failed,
        "metrics": {},
        "device": device,
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = dict(end_to_end(submits, t_start, t_end), setup_s=setup_s)
        names = [m["name"] for m in cell.end_to_end]
    else:
        events = trace_reduce.events_from_profile(_load_profile(trace_dir))
        win = trace_reduce.window_of(events, "bench.trace")
        # the program's spans onto the trace's clock, through the trace
        # annotation entered at t_start on the host clock
        offset = win[0] - t_start * 1e9
        host_spans = [
            [s.name, s.start * 1e9 + offset, s.end * 1e9 + offset] for s in spans
        ]
        summary = trace_reduce.reduce(events, win, host_spans)
        staged = {k: v for k, v in summary.launches.items() if "_stage_" in k}
        print(f"launches {json.dumps(staged)}", file=sys.stderr, flush=True)
        ctx = SimpleNamespace(
            spans=spans,
            launches=launches,
            counters={k: c1.get(k, 0.0) - c0.get(k, 0.0) for k in c1},
            trace=summary,
        )
        names = [m["name"] for m in cell.per_layer]
        values = {n: spec.reader(n)(ctx) for n in names}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": summary.top_programs(10),
            "idle_gaps": summary.idle_by_host,
        }
    for n in names:
        if values.get(n) is not None:
            result["metrics"][n] = {"value": values[n], "unit": units[n]}
    result["checks"] = checks
    return result


def _load_profile(trace_dir):
    import jax

    paths = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise BenchmarkFault(f"no trace written under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(paths[-1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = spec.ROOT
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache" / "tpu")
    cell = spec.resolve(spec.load_spec(root), args.workload, root)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: needs {cell.chips} TPU chip(s); JAX has "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 2
    devices = devices[: cell.chips]
    hbm = spec.device_entry(devices[0].device_kind, root)

    trace_dir = root / ".bench_trace" / f"{args.workload}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        result = run_cell(
            cell, args.seed, args.seconds, bool(args.trace), devices, trace_dir
        )
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    peak = result["device"]["memory_peak_bytes"]
    print(json.dumps({
        "hbm": {
            "kind": devices[0].device_kind,
            "peak_bytes": peak,
            "hbm_bytes": hbm["hbm_bytes"],
            "in_use_pct": None if peak is None else 100.0 * peak / hbm["hbm_bytes"],
        }
    }), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
