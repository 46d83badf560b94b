"""Layer stack: pattern-driven blocks, scan over periods, caches, remat.

A block is `<mixer>+<ff>` (configs/base.py).  Parameters of position i in
the repeating pattern are stacked over the `n_periods` scan axis, so a
72-layer model lowers as one scanned period — compact HLO, fast dry-run
compiles, and the FSDP all-gather of each period's params happens inside
the scan (overlappable by the XLA latency-hiding scheduler).

`cfg.first_dense_layers` leading blocks (`cfg.dense_spec`: the pattern's
first mixer with a dense FFN) run before the scan, unrolled: their
params sit under `dense_layers.pos{i}` (one unstacked block each) and
their cache under the cache tree's `dense_layers` key, stacked over
them like a scanned position's, so pool code treats it as one more
stacked pool.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.layers import init_mlp, init_rmsnorm, mlp, rmsnorm
from repro.parallel.sharding import constrain

MIXERS = ("attn", "mla", "xattn", "mamba", "mlstm", "slstm")
FFS = ("dense", "moe", "none")


def parse_spec(spec: str) -> tuple[str, str]:
    mixer, ff = spec.split("+")
    assert mixer in MIXERS and ff in FFS, spec
    return mixer, ff


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def init_block(key, cfg, spec: str):
    mixer, ff = parse_spec(spec)
    k1, k2 = jax.random.split(key)
    dt = cfg.compute_dtype
    p: dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dt)}
    if mixer == "attn":
        p["mixer"] = attn.init_gqa(k1, cfg)
    elif mixer == "mla":
        p["mixer"] = attn.init_mla(k1, cfg)
    elif mixer == "xattn":
        p["mixer"] = attn.init_xattn(k1, cfg)
    elif mixer == "mamba":
        p["mixer"] = mb.init_mamba(k1, cfg)
    elif mixer == "mlstm":
        p["mixer"] = xl.init_mlstm(k1, cfg)
    elif mixer == "slstm":
        p["mixer"] = xl.init_slstm(k1, cfg)
    if ff != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dt)
        p["moe" if ff == "moe" else "mlp"] = (
            moe_mod.init_moe(k2, cfg) if ff == "moe"
            else init_mlp(k2, cfg.d_model, cfg.d_ff, dt))
    return p


def init_block_cache(cfg, spec: str, batch: int, max_seq: int, dtype,
                     num_pages=None):
    """Decode-time state for one block (None for stateless).

    num_pages switches attention KV to the paged pool layout; recurrent
    state and the cross-attention cache are per-slot fixed-size arrays
    either way (they are the "registers" of a slot, not token storage)."""
    mixer, _ = parse_spec(spec)
    if mixer == "attn":
        return attn.init_gqa_cache(cfg, batch, max_seq, dtype, num_pages)
    if mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_seq, dtype, num_pages)
    if mixer == "xattn":
        return attn.init_xattn_cache(cfg, batch, dtype)
    if mixer == "mamba":
        return mb.init_mamba_state(cfg, batch, dtype)
    if mixer == "mlstm":
        return xl.init_mlstm_state(cfg, batch)
    if mixer == "slstm":
        return xl.init_slstm_state(cfg, batch)
    raise ValueError(mixer)


# ---------------------------------------------------------------------------
# per-block apply
# ---------------------------------------------------------------------------

# recurrent mixer -> (one decode step, whole-sequence prefill)
_RECURRENT = {"mamba": (mb.mamba_step, mb.mamba_sequence),
              "mlstm": (xl.mlstm_step, xl.mlstm_sequence),
              "slstm": (xl.slstm_step, xl.slstm_sequence)}


def block_apply(p, x, cfg, spec, *, positions, vision_embeds=None,
                cache=None, cache_pos=None, paged=None, layer=None):
    """Returns (x, aux_loss, new_cache).

    `cache` is this pattern position's cache stacked over the scanned
    layers (axis 0); the block reads and writes it at `layer` only (the
    `paged` bundle carries the same index) and returns the whole stacked
    tree (None when `cache` is None: training).

    `paged` (an attention.PagedKV bundle, threaded untouched from the
    engine) selects the paged KV discipline inside gqa/mla — including,
    when its decode_kernel flag is set, the pallas block-table decode
    kernel for Sq=1 gqa reads (mla always takes the gather oracle)."""
    mixer, ff = parse_spec(spec)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        y, new_cache = attn.gqa(p["mixer"], h, cfg, positions, cache,
                                cache_pos, paged, layer)
    elif mixer == "mla":
        y, new_cache = attn.mla(p["mixer"], h, cfg, positions, cache,
                                cache_pos, paged, layer)
    elif mixer == "xattn":
        y, new_cache = attn.xattn(p["mixer"], h, cfg, vision_embeds,
                                  cache, cache_pos, layer)
    else:
        step, sequence = _RECURRENT[mixer]
        if cache is not None and x.shape[1] == 1:      # decode
            y, state = step(p["mixer"], h, cfg, jax.tree_util.tree_map(
                lambda a: a[layer], cache))
        else:
            y, state = sequence(p["mixer"], h, cfg)
        new_cache = None if cache is None else jax.tree_util.tree_map(
            lambda a, s: a.at[layer].set(s.astype(a.dtype)), cache, state)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if ff == "dense":
        x = x + mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg.norm_eps), cfg.quant)
    elif ff == "moe":
        # serving (a cache is passed) routes dropless: a token's output
        # must not depend on its batch companions
        y, aux = moe_mod.moe(p["moe"], rmsnorm(p["norm2"], x, cfg.norm_eps),
                             cfg, dropless=cache is not None)
        x = x + y
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def init_stack(key, cfg):
    """{"pos{i}": stacked-over-periods block params}"""
    params = {}
    for i, spec in enumerate(cfg.layer_pattern):
        keys = jax.random.split(jax.random.fold_in(key, i), cfg.n_periods)
        params[f"pos{i}"] = jax.vmap(
            lambda k: init_block(k, cfg, spec))(keys)
    return params


def init_leading(key, cfg):
    """{"pos{i}": block params} of the leading dense blocks."""
    return {f"pos{i}": init_block(jax.random.fold_in(key, i), cfg,
                                  cfg.dense_spec)
            for i in range(cfg.first_dense_layers)}


def _stacked_specs(cfg):
    """(cache key, block spec, rows) of every stacked cache entry."""
    out = [(f"pos{i}", spec, cfg.n_periods)
           for i, spec in enumerate(cfg.layer_pattern)]
    if cfg.first_dense_layers:
        out.append(("dense_layers", cfg.dense_spec, cfg.first_dense_layers))
    return out


def init_stack_cache(cfg, batch, max_seq, dtype, num_pages=None):
    caches = {}
    for key, spec, rows in _stacked_specs(cfg):
        one = init_block_cache(cfg, spec, batch, max_seq, dtype, num_pages)
        caches[key] = jax.tree_util.tree_map(
            lambda a, rows=rows: jnp.broadcast_to(
                a, (rows,) + a.shape).copy(), one)
    return caches


def stack_cache_pool_flags(cfg):
    """A pytree matching init_stack_cache's paged structure with True at
    shared page-pool leaves and False at per-slot leaves — engines use it
    to reset/merge only slot-private state (pools are co-owned and must
    never be blanket-reset or slot-masked), and `runtime.pages.cow_copy`
    uses it to route copy-on-write page splits to every pool leaf (each
    stacked leaf is (n_periods, num_pages, page_size, ...) — the page
    axis is axis 1) while leaving per-slot leaves untouched."""
    flags = {}
    for key, spec, _ in _stacked_specs(cfg):
        mixer, _ = parse_spec(spec)
        is_pool = mixer in ("attn", "mla")
        shapes = jax.eval_shape(
            lambda s=spec: init_block_cache(cfg, s, 1, cfg.page_size,
                                            cfg.compute_dtype, num_pages=1))
        flags[key] = jax.tree_util.tree_map(lambda _: is_pool, shapes)
    return flags


def leading_apply(params, x, cfg, *, positions, vision_embeds=None,
                  caches=None, cache_pos=None, paged=None):
    """The leading dense blocks, unrolled, each reading and writing row i
    of the `dense_layers` cache.  Returns (x, aux_total, caches)."""
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(cfg.first_dense_layers):
        bundle = None if paged is None else dataclasses.replace(paged,
                                                                layer=i)

        def block(p, x, cache, bundle=bundle, i=i):
            return block_apply(p, x, cfg, cfg.dense_spec,
                               positions=positions,
                               vision_embeds=vision_embeds, cache=cache,
                               cache_pos=cache_pos, paged=bundle, layer=i)
        if cfg.remat:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.nothing_saveable)
        x, aux, nc = block(params[f"pos{i}"], x,
                           None if caches is None else caches["dense_layers"])
        aux_total += aux
        if caches is not None:
            caches = {**caches, "dense_layers": nc}
        x = constrain(x, "batch", "act_seq", None)
    return x, aux_total, caches


def stack_apply(params, x, cfg, *, positions, vision_embeds=None,
                caches=None, cache_pos=None, paged=None):
    """Scan over periods. Returns (x, aux_total, new_caches).

    The stacked caches ride in the scan's carry, not its xs/ys: every
    block reads and writes them at its layer index, so the tree stays
    one set of buffers that XLA updates in place.  As the scan's xs/ys,
    XLA would slice every layer out, write it into a fresh output and
    copy the whole tree back on every call (XLA cannot alias a scan's
    input and output)."""

    def period(x, caches, p_slice, layer):
        aux_total = jnp.zeros((), jnp.float32)
        bundle = None if paged is None else dataclasses.replace(paged,
                                                                layer=layer)
        for i, spec in enumerate(cfg.layer_pattern):
            key = f"pos{i}"
            x, aux, nc = block_apply(
                p_slice[key], x, cfg, spec, positions=positions,
                vision_embeds=vision_embeds,
                cache=None if caches is None else caches[key],
                cache_pos=cache_pos, paged=bundle, layer=layer)
            aux_total += aux
            if caches is not None:
                caches = {**caches, key: nc}
        x = constrain(x, "batch", "act_seq", None)
        return x, aux_total, caches

    body = period
    if cfg.remat:
        body = jax.checkpoint(period,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, layer_in):
        x, aux_acc, caches = carry
        x, aux, caches = body(x, caches, *layer_in)
        return (x, aux_acc + aux, caches), None

    carry = (x, jnp.zeros((), jnp.float32), caches)
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(
            scan_body, carry,
            (params, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
        return carry

    # unrolled (python-loop) stack: identical computation, every period in
    # the HLO — used by the dry-run's cost probe (scan bodies are counted
    # once by XLA cost analysis) and available as a runtime choice.
    for i in range(cfg.n_periods):
        carry, _ = scan_body(
            carry, (jax.tree_util.tree_map(lambda a: a[i], params), i))
    return carry
