"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel logic on the CPU
but never meets the Mosaic compiler, so it cannot see a block spec that
violates the (8, 128) tiling, a reshape the vector layout pass refuses, or
a DMA slice narrower than a lane tile.  These tests lower the kernels at
the engine's real shapes for one chip of a `v5e:2x2` topology and compile
them with the TPU compiler that ships with jaxlib; nothing runs.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time, and collection must give every
test worker the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as pk
from repro.kernels.bramac_matmul import bramac_matmul

# granite-8b's attention at the engine's default paging: page 16, 32 query
# / 8 KV heads of 128, 8 slots x 2048 rows -> 128 table entries per slot
B, H, HKV, HD, PAGE, MAX_PAGES = 8, 32, 8, 128, 16, 128
NUM_PAGES = B * MAX_PAGES
# granite-8b's d_model x d_ff projection
K, N = 4096, 14336


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler plugin on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_compiles(one_chip, kv):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (NUM_PAGES, PAGE, HKV, HD)
    table_args = (s((B, MAX_PAGES), jnp.int32), s((B,), jnp.int32),
                  s((B,), jnp.int32))
    if kv == "bf16":
        _compile(lambda *a: pk._paged_decode(*a, interpret=False),
                 s((B, H, HD), jnp.bfloat16), s(pool, jnp.bfloat16),
                 s(pool, jnp.bfloat16), *table_args)
    else:
        scales = s(pool[:-1], jnp.float32)
        _compile(lambda *a: pk._paged_decode_q(*a, out_dtype="bfloat16",
                                               interpret=False),
                 s((B, H, HD), jnp.int8), s((B, H), jnp.float32),
                 s(pool, jnp.int8), scales, s(pool, jnp.int8), scales,
                 *table_args)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("m", [128, 8], ids=["M128", "M8"])
def test_bramac_matmul_compiles(one_chip, m, bits):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda x, w, xs, ws: bramac_matmul(
                 x, w, xs, ws, bits_a=bits, bits_w=bits,
                 block=(min(m, 128), 128, 128), interpret=False),
             s((m, K), jnp.int8), s((K, N), jnp.int8),
             s((m, 1), jnp.float32), s((1, N), jnp.float32))
