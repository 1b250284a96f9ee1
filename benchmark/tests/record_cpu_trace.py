"""Records `data/cpu_trace.xplane.pb`, the small CPU trace that
test_trace_reduce.py reads: `python benchmark/tests/record_cpu_trace.py`.

Inside a `bench.window` annotation, three rounds of 30 ms of host work
under `bench.build` with nothing on the device, then a jitted program
under `bench.submit`."""

import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

OUT = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


@jax.jit
def _stage_demo(x):
    for _ in range(4):
        x = jnp.tanh(x @ x.T)
    return x


def main() -> int:
    x = jnp.ones((384, 384), jnp.float32)
    _stage_demo(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.build"):
                    time.sleep(0.03)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    _stage_demo(x).block_until_ready()
        jax.profiler.stop_trace()
        path = sorted(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))[-1]
        shutil.copyfile(path, OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(OUT, OUT.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
