"""Ahead-of-time compiles of the serving kernels and the engine's programs
for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel logic on the CPU
but never meets the Mosaic compiler, so it cannot see a block spec that
violates the (8, 128) tiling, a reshape the vector layout pass refuses, or
a DMA slice narrower than a lane tile.  These tests lower the kernels at
the engine's real shapes for one chip of a `v5e:2x2` topology and compile
them with the TPU compiler that ships with jaxlib; nothing runs.  The
engine's `tick` and `admit` are compiled whole at a small paged size, to
hold the layer scan to updating the stacked KV pool in place.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time, and collection must give every
test worker the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_attention as pk
from repro.kernels.bramac_matmul import bramac_matmul
from repro.models import model as M
from repro.runtime import speculate as spc
from repro.runtime import workers
from repro.runtime.sampling import SamplingConfig

# granite-8b's attention at the engine's default paging: page 16, 32 query
# / 8 KV heads of 128, 8 slots x 2048 rows -> 128 table entries per slot,
# pools stacked over 16 layers
B, H, HKV, HD, PAGE, MAX_PAGES, LAYERS = 8, 32, 8, 128, 16, 128, 16
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

    pool = (LAYERS, NUM_PAGES, PAGE, HKV, HD)
    table_args = (s((B, MAX_PAGES), jnp.int32), s((B,), jnp.int32),
                  s((B,), jnp.int32), s((1,), jnp.int32))
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


# instruction line of optimized HLO: `%name = dtype[dims]{layout} opcode(`
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_copies(hlo: str, shapes) -> list[str]:
    """Instructions that materialize a whole stacked pool leaf or a whole
    layer of one: a copy, a dynamic-slice, or a fusion named after
    either (XLA names a fusion after its root)."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape not in shapes:
            continue
        if op.startswith("copy") or op == "dynamic-slice" or (
                op == "fusion" and ("copy" in name
                                    or "dynamic-slice" in name)):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["tick", "admit"])
def test_engine_programs_update_the_pool_in_place(one_chip, monkeypatch,
                                                  program):
    """The engine's tick and admit, compiled as on the chip (kernel not
    interpreted, state and caches donated), at granite-8b's attention
    widths with four layers and narrow weights: no instruction copies or
    slices out a whole stacked pool leaf or a whole layer of one, and
    the temporaries stay below one pool leaf.  A layer scan that takes
    the pool as its xs/ys fails both.  Each pool leaf (192 MiB) is too
    large for the compiler to place its copies in on-chip memory, where
    they would not count as temporaries."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(workers, "_donate", lambda: (1, 2))
    cfg = get_config("granite-8b", smoke=True).replace(
        num_layers=4, d_model=256, num_heads=H, num_kv_heads=HKV,
        head_dim=HD, d_ff=512, vocab_size=512, dtype="bfloat16")
    ns, ms, P, C, cap = 4, 256, 1536, 16, 4
    mp = ms // PAGE
    assert ns * mp != P      # a gathered view must not look like a layer
    flags = M.cache_pool_flags(cfg)
    greedy = SamplingConfig(method="greedy")

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state = sds(jax.eval_shape(lambda: workers.init_slot_state(
        ns, cap, mp, P, spc.empty_state(ns))))
    caches = sds(jax.eval_shape(lambda: M.init_cache(cfg, ns, ms,
                                                     num_pages=P)))
    if program == "tick":
        fn = workers.DecodeWorker(
            cfg=cfg, num_slots=ns, max_seq=ms, decode_steps=1,
            sampling=greedy, kv_layout="paged", decode_kernel=True,
            draft_len=0, drafter=None, pool_flags=flags,
            kv_flags=None).tick
        args = (params, state, caches)
    else:
        fn = workers.PrefillWorker(
            cfg=cfg, num_slots=ns, max_seq=ms, prefill_chunk=C,
            stop_cap=cap, sampling=greedy,
            base_key=jax.random.PRNGKey(0), kv_layout="paged",
            pool_flags=flags, draft_len=0, drafter=None)._admit_chunk
        i32, b = jnp.int32, jnp.bool_
        args = (params, state, caches, arr((ns, C), i32), arr((ns,), b),
                arr((ns,), b), arr((ns,), i32), arr((ns,), i32),
                arr((ns,), i32), arr((ns,), i32), arr((ns, cap), i32),
                arr((ns,), b), arr((ns, mp), i32), arr((ns,), i32),
                arr((ns,), i32), arr((ns,), i32), arr((P,), i32),
                arr((P,), i32))
    compiled = fn.lower(*args).compile()
    leaf = caches["pos0"]["k"]
    assert leaf.shape == (cfg.n_periods, P, PAGE, HKV, HD)
    shapes = {leaf.shape, leaf.shape[1:]}
    assert _pool_copies(compiled.as_text(), shapes) == []
    leaf_bytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf_bytes, (temp, leaf_bytes)
