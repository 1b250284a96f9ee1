"""Per-stage steady-state timing of the staged TPU verifier (warm cache).

Prints one line per stage at the bench shape so optimization effort goes
where the time is. Run after `python -m lighthouse_tpu.cli warm`.
"""

import sys
import time

sys.path.insert(0, ".")

from __graft_entry__ import _example_batch
from lighthouse_tpu.utils.compile_cache import arm

arm()

import jax

print("devices:", jax.devices(), flush=True)

from lighthouse_tpu.crypto.bls.backends.jax_tpu import (
    _stage_final,
    _stage_hash,
    _stage_miller,
    _stage_prep,
)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
u, h_idx, pk, sig, scalars, real = _example_batch(
    N, 2, distinct=min(32, N), dedup=True
)

import jax.numpy as jnp

# chain once to get real intermediates (also warms executables)
t0 = time.perf_counter()
h_aff_u, h_inf_u = jax.block_until_ready(_stage_hash(u))
t_hash_cold = time.perf_counter() - t0
h_aff = jnp.take(h_aff_u, h_idx, axis=0)
h_inf = jnp.take(h_inf_u, h_idx, axis=0)
t0 = time.perf_counter()
prep = jax.block_until_ready(_stage_prep(pk, sig, scalars, real))
t_prep_cold = time.perf_counter() - t0
rpk_aff, rpk_inf, ssum_aff, ssum_inf, flags_ok = prep
t0 = time.perf_counter()
fprod = jax.block_until_ready(
    _stage_miller(rpk_aff, rpk_inf, h_aff, h_inf, ssum_aff, ssum_inf)
)
t_miller_cold = time.perf_counter() - t0
t0 = time.perf_counter()
jax.block_until_ready(_stage_final(fprod, flags_ok))
t_final_cold = time.perf_counter() - t0
print(
    f"cold/load: hash {t_hash_cold:.1f}s prep {t_prep_cold:.1f}s "
    f"miller {t_miller_cold:.1f}s final {t_final_cold:.1f}s",
    flush=True,
)

for name, fn, args in (
    ("hash  ", _stage_hash, (u,)),
    ("prep  ", _stage_prep, (pk, sig, scalars, real)),
    ("miller", _stage_miller, (rpk_aff, rpk_inf, h_aff, h_inf, ssum_aff, ssum_inf)),
    ("final ", _stage_final, (fprod, flags_ok)),
):
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    print(f"n={N} {name} steady {min(times) * 1e3:8.1f} ms", flush=True)
