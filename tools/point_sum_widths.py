"""Device times behind `tpu/curve.py`'s FOLD_WIDTH: one complete G1 add at
widths 4,096 to 65,536 points, and the block's two point sums (512 keys x
256 sets, 256 rows x 128 messages) on the halving scan and folded at each
chunk size.

    python -m tools.point_sum_widths            # on a TPU
    python -m tools.point_sum_widths --small    # a tiny rehearsal on any device

Prints one JSON line: per variant the compile seconds and the median of
`--reps` warm runs in ms, on the host clock around `block_until_ready`.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls import curve_ref
from lighthouse_tpu.crypto.bls.tpu import curve as TC

FULL = {
    "widths": (4096, 8192, 16384, 32768, 65536),
    "sums": {(512, 256): (16, 32, 64, 128, 256), (256, 128): (32, 64, 128)},
}
SMALL = {"widths": (16, 32), "sums": {(16, 8): (2, 4)}}


def _points(shape):
    """`shape` rows cycling over 64 distinct multiples of the generator."""
    g = curve_ref.g1_generator()
    base = np.asarray(TC.g1_pack([g.mul(k + 1) for k in range(64)]))
    n = int(np.prod(shape))
    return jnp.asarray(np.resize(base, (n,) + base.shape[1:]).reshape(
        shape + base.shape[1:]))


def _time(fn, args, reps):
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    jax.block_until_ready(compiled(*args))
    runs = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        runs.append(1e3 * (time.perf_counter() - t))
    return {"compile_s": round(compile_s, 3), "ms": statistics.median(runs),
            "runs_ms": [round(r, 4) for r in runs]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args(argv)
    plan = SMALL if args.small else FULL
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "add": {}, "sum": {}}
    for w in plan["widths"]:
        p = _points((w,))
        out["add"][w] = _time(lambda a, b: TC.add(a, b, TC.FP), (p, p), args.reps)
    for (m, width), chunks in plan["sums"].items():
        p = _points((m, width))
        key = f"{m}x{width}"
        out["sum"][key] = {
            "halving": _time(lambda a: TC.fold_sum(a, m, TC.FP), (p,), args.reps)
        }
        for b in chunks:
            out["sum"][key][f"fold{b}"] = _time(
                lambda a, b=b: TC.fold_sum(a, b, TC.FP), (p,), args.reps
            )
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
