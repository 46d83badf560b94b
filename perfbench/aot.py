"""Compile a configuration's admit and tick programs for a described
TPU v5e, without the chip, and print what `memory_analysis` says.

    JAX_PLATFORMS=cpu python3 perfbench/aot.py <config name> ...

This is how a cell's pool is sized before any chip time: the weights,
the page pool and each program's temporaries must fit 16 GB.  Nothing
runs; shapes only.  The decode kernel is compiled as on the chip (not in
interpret mode).
"""
from __future__ import annotations

import json
import pathlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from perfbench.run import find, load_benchmark, load_config  # noqa: E402

GIB = 2.0 ** 30


def analyse(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import system
    from repro.kernels import paged_attention as pk
    from repro.models import model as M
    from repro.runtime import speculate as spc
    from repro.runtime.sampling import SamplingConfig
    from repro.runtime import workers
    from repro.runtime.workers import (DecodeWorker, PrefillWorker,
                                       init_slot_state)

    # as on the chip: the kernel compiled (not interpreted), and state and
    # caches donated to the programs (the CPU backend turns both off)
    pk._interpret = lambda: False
    workers._donate = lambda: (1, 2)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    eng = cfg["engine"]
    ns, ms, P = eng["slots"], eng["max_seq"], eng["num_pages"]
    mc = system.model_config(cfg, system.launcher_args(cfg))
    mp, C, cap = -(-ms // mc.page_size), 16, 4
    flags = M.cache_pool_flags(mc)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    params = sds(jax.eval_shape(lambda: system.make_params(cfg, mc, 0)))
    state = sds(jax.eval_shape(lambda: init_slot_state(
        ns, cap, mp, P, spc.empty_state(ns))))
    caches = sds(jax.eval_shape(lambda: M.init_cache(mc, ns, ms,
                                                     num_pages=P)))
    greedy = SamplingConfig(method="greedy")
    dw = DecodeWorker(cfg=mc, num_slots=ns, max_seq=ms, decode_steps=1,
                      sampling=greedy, kv_layout="paged", decode_kernel=True,
                      draft_len=0, drafter=None, pool_flags=flags,
                      kv_flags=None)
    pw = PrefillWorker(cfg=mc, num_slots=ns, max_seq=ms, prefill_chunk=C,
                       stop_cap=cap, sampling=greedy,
                       base_key=jax.random.PRNGKey(0), kv_layout="paged",
                       pool_flags=flags, draft_len=0, drafter=None)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    i32, b = jnp.int32, jnp.bool_
    admit_args = (params, state, caches, arr((ns, C), i32), arr((ns,), b),
                  arr((ns,), b), arr((ns,), i32), arr((ns,), i32),
                  arr((ns,), i32), arr((ns,), i32), arr((ns, cap), i32),
                  arr((ns,), b), arr((ns, mp), i32), arr((ns,), i32),
                  arr((ns,), i32), arr((ns,), i32), arr((P,), i32),
                  arr((P,), i32))
    out = {}
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree_util.tree_leaves(t))
    out["weights_gib"] = nbytes(params) / GIB
    out["kv_pool_gib"] = nbytes(caches) / GIB
    for name, fn, args in (("tick", dw.tick, (params, state, caches)),
                           ("admit", pw._admit_chunk, admit_args)):
        ma = fn.lower(*args).compile().memory_analysis()
        out[name] = {k: getattr(ma, k) / GIB for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
    return out


def main() -> None:
    bench = load_benchmark()
    for name in sys.argv[1:]:
        cfg = load_config(find(bench["configs"], name, "config"))
        print(json.dumps({"config": name, **analyse(cfg)}), flush=True)


if __name__ == "__main__":
    main()
