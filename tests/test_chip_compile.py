"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a chip that is
described rather than attached (on-chip-measurement guide, section 2):
what it refuses here would be refused on the chip, at no chip time.
Interpret-mode parity tests cannot see such refusals -- the Pallas Fp
kernels passed every one of them while Mosaic refused both (scatter-add
column sums, an int32 matmul fold).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and the suite's workers all
import this file. Of the fused tower/Miller kernels only fp6_mul
(~4 s) fits this file's budget; the others take 7-82 s each to compile
for a v5e (ROADMAP, Speed item 5, records each).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.crypto.bls.tpu import limbs as L
from lighthouse_tpu.crypto.bls.tpu import pallas_kernels as PK

ROWS = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _limbs(sharding):
    return jax.ShapeDtypeStruct((ROWS, L.W), jnp.int32, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_fp_mul_kernel_compiles(one_chip):
    x = _limbs(one_chip)
    call = PK._mul_call(False, PK.BLOCK_ROWS)  # the compiled, not interpreted
    compiled = _compile(call, x, x)
    assert "tpu_custom_call" in compiled.as_text()


def test_fp_sq_kernel_compiles(one_chip):
    call = PK._sq_call(False, PK.BLOCK_ROWS)
    compiled = _compile(call, _limbs(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_fp6_mul_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((ROWS, 6, L.W), jnp.int32, sharding=one_chip)
    call = PK._fused_call("fp6_mul", False, PK.FUSED_BLOCK_ROWS)
    compiled = _compile(call, x, x)
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_limbs_mul_compiles(one_chip):
    x = _limbs(one_chip)
    compiled = _compile(L.mul, x, x)
    assert "tpu_custom_call" not in compiled.as_text()  # plain XLA path
