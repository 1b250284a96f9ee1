"""The control and the planted faults: verdict paths that break a
guarantee, which the comparison has to refuse.

`python -m benchmark.control --workload <cell> --seeds 11,12,13
--seconds <s>` runs the cell on the chip once per seed in one process,
with the control in place of the timed path, and prints the numbers the
comparison reads for each. The benchmark's own runs never run it.

The control is `half`: each batch is verified on its first half only,
the work a verifier that samples its sets would skip. It breaks the
configurations' guarantee that a batch is accepted only if every one of
its sets verifies. The other faults are the failures a cell on one chip
can have: `stale` answers each batch with the previous batch's verdict
(a step that returns its state unchanged), and `flip` alters one answer
in four where it is produced.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from contextlib import contextmanager


class _Done:
    """A future that already holds its verdict."""

    def __init__(self, value):
        self._value = value

    def done(self):
        return True

    def result(self):
        return self._value


class _Mapped:
    def __init__(self, fut, fn):
        self._fut, self._fn = fut, fn

    def done(self):
        return self._fut.done()

    def result(self):
        return self._fn(self._fut.result())


def _map(out, fn):
    return fn(out) if isinstance(out, bool) else _Mapped(out, fn)


def half(call):
    def verify(sets, seed=None):
        return call(list(sets)[: len(sets) // 2], seed=seed)

    return verify


def stale(call):
    last = [None]

    def verify(sets, seed=None):
        out = call(sets, seed=seed)
        prev, last[0] = last[0], out
        if prev is None:
            return out if isinstance(out, bool) else _Done(True)
        return prev

    return verify


def flip(call):
    count = [0]

    def verify(sets, seed=None):
        count[0] += 1
        out = call(sets, seed=seed)
        return _map(out, lambda v: not v) if count[0] % 4 == 2 else out

    return verify


FAULTS = {"half": half, "stale": stale, "flip": flip}
CONTROL = "half"


@contextmanager
def planted(name: str):
    """Both BLS entry points of the program's api wrapped by fault `name`."""
    from lighthouse_tpu.crypto.bls import api

    saved = api.verify_signature_sets, api.verify_signature_sets_async
    api.verify_signature_sets = FAULTS[name](saved[0])
    api.verify_signature_sets_async = FAULTS[name](saved[1])
    try:
        yield
    finally:
        api.verify_signature_sets, api.verify_signature_sets_async = saved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=CONTROL)
    args = ap.parse_args(argv)

    from benchmark import run, spec

    root = spec.ROOT
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache" / "tpu")
    cell = spec.resolve(spec.load_spec(root), args.workload, root)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(args.fault):
            result = run.run_cell(
                cell, seed, args.seconds, False, devices[: cell.chips]
            )
        row = {"seed": seed, "fault": args.fault, "correct": result["correct"],
               "checks": result["checks"]}
        readings.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "fault": args.fault,
        "refused_on_every_seed": all(not r["correct"] for r in readings),
        "least_verdict_mismatches": min(
            r["checks"]["verdict_mismatches"]["value"] for r in readings
        ),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
