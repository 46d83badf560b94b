"""Pallas paged-attention decode kernel: block-table walks, not gathers.

The serving oracle (`attention.paged_view` + `chunk_attention`) materializes
a dense ``(B, max_seq, ...)`` copy of every sequence's pages before
attending, so per-decode-step memory traffic is proportional to ``max_seq``
even for ten-token sequences.  This kernel applies the paper's
small-fixed-array discipline to the decode hot loop: each grid step owns one
sequence, walks that sequence's block table directly, and DMAs one
``(page_size, Hkv, hd)`` K/V tile at a time from the pool into a fixed VMEM
scratch buffer, combining pages with an online softmax.  The pool is the
layer scan's stacked ``(n_periods, P, page_size, Hkv, hd)`` buffer, read at
``[layer, page]`` with ``layer`` a scalar-prefetch operand, so no layer of
it is ever sliced out (copied) before the kernel runs.

Kernel invariants (the contract the parity suite pins):

* **Page-bounded gathers** — the page loop runs ``min(n_pages[b],
  ceil(length[b] / page_size))`` iterations, never ``max_seq / page_size``:
  per-step HBM traffic is proportional to the sequence's *live* tokens.
  Table entries at or beyond ``n_pages`` are never read.
* **Online-softmax exactness contract** — scores are computed in fp32 with
  the oracle's exact masking rule (rows at or past ``length`` replaced by
  -1e30 before the running max), and pages are combined with a running
  max + rescaled accumulator.  Outputs match the gather oracle to float
  reassociation error (the sum is associated per-page instead of once over
  ``max_seq``); greedy token streams are asserted bit-identical in
  tests/test_paged_attention_kernel.py.
* **Masks honored** — the kernel is read-only: ownership (`owned`), write
  (`write_mask`) and speculative (`bound`) masks are write-side concerns
  enforced by `attention.paged_update` before the kernel ever runs, so a
  tile read through the table sees exactly the rows those masks admitted.
  A sequence with ``n_pages == 0`` (free slot) reads nothing and returns
  zeros.
* **int8 KV stays int8** — the quantized variant loads int8 K/V tiles plus
  their per-row scales and dequantizes *in-kernel* on the one resident
  tile; no fp copy of the cache is ever materialized (the oracle's
  `decode_attention_q` contract, minus its probability requantization —
  see `paged_decode_q`).

Dispatch follows `kernels/ops.py`: interpret mode is resolved per call via
`_interpret()` and enters the jit cache as a static argument, so the suite
runs the same kernel code on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _interpret


def _row(b, *_scalars):
    """Block index of sequence b's (1, ...) row; the scalar-prefetch refs
    (tables, n_pages, lengths, layer) that Pallas also passes are unused
    here."""
    return b, 0, 0


def _online_update(carry, s, v_tile):
    """One page's online-softmax step: fold fp32 scores ``s`` (Hkv, g, ps)
    and the fp32 value tile ``v_tile`` (Hkv, ps, hd) into the running
    (max, normalizer, accumulator) carry."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p, v_tile, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    return m_new, l, acc * corr[..., None] + pv


def _finish(out_ref, carry, H, hd):
    m, l, acc = carry
    l = jnp.where(l > 0, l, 1.0)        # free slot (no pages): emit zeros
    out_ref[0] = (acc / l[..., None]).reshape(H, hd).astype(out_ref.dtype)


def _fp_kernel(tables_ref, n_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
               out_ref, k_scr, v_scr, sems, *, page_size, hkv):
    H, hd = q_ref.shape[1], q_ref.shape[2]
    g, ps = H // hkv, page_size
    b = pl.program_id(0)
    layer = layer_ref[0]
    q = q_ref[0].astype(jnp.float32).reshape(hkv, g, hd)
    L = len_ref[b]
    n_eff = jnp.minimum(n_ref[b], (L + ps - 1) // ps)

    def body(j, carry):
        pid = tables_ref[b, j]
        ck = pltpu.make_async_copy(k_hbm.at[layer, pid], k_scr, sems.at[0])
        cv = pltpu.make_async_copy(v_hbm.at[layer, pid], v_scr, sems.at[1])
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        kt = k_scr[...].astype(jnp.float32).transpose(1, 0, 2)  # (Hkv,ps,hd)
        vt = v_scr[...].astype(jnp.float32).transpose(1, 0, 2)
        s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd)
        rows = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
        s = jnp.where(rows < L, s, -1e30)       # the oracle's masking rule
        return _online_update(carry, s, vt)

    carry = (jnp.full((hkv, g), -jnp.inf, jnp.float32),
             jnp.zeros((hkv, g), jnp.float32),
             jnp.zeros((hkv, g, hd), jnp.float32))
    carry = jax.lax.fori_loop(0, n_eff, body, carry)
    _finish(out_ref, carry, H, hd)


def _q_kernel(tables_ref, n_ref, len_ref, layer_ref, q_ref, qs_ref, k_hbm,
              ks_hbm, v_hbm, vs_hbm, out_ref, k_scr, ks_scr, v_scr, vs_scr,
              sems, *, page_size, hkv):
    """int8 variant: reproduces `decode_attention_q`'s arithmetic — int8
    score dot with the K row scales factored out, fp32 softmax, V row
    scales folded into the probabilities, probabilities *requantized* to
    int8 for an integer PV dot — with three page walks instead of one
    gather (max, then normalizer + probability row scale at the exact
    final max, then the quantized accumulation).  The extra walks keep
    every partial bit-comparable to the oracle: only the normalizer's
    float association order differs.  Traffic stays proportional to live
    tokens; no fp copy of the cache is ever materialized.

    Layout: a page's K/V tile is viewed as ``w = page_size * Hkv``
    (row, head) pairs, matching the lane-dense ``(1, w)`` scale rows, and
    every query head scores all ``w`` of them in one 2-D dot; pairs of
    another KV head are masked to -1e30 like rows past the length, so
    they add exact zeros.  Integer-valued f32 dots are exact here: a
    page's partial sums stay below 2**24 and accumulate in int32."""
    H, hd = q_ref.shape[1], q_ref.shape[2]
    g, ps = H // hkv, page_size
    w = ps * hkv
    b = pl.program_id(0)
    layer = layer_ref[0]
    q = q_ref[0].astype(jnp.float32)                       # (H, hd) ints
    qs = qs_ref[0]                                         # (H, 1)
    L = len_ref[b]
    n_eff = jnp.minimum(n_ref[b], (L + ps - 1) // ps)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, w), 1)
    head_ok = col % hkv == jax.lax.broadcasted_iota(jnp.int32, (H, w), 0) // g
    row = col // hkv                                       # row within page

    def load_scores(j):
        """DMA page j's tiles; masked fp32 scores (H, w) exactly as the
        oracle computes them, plus the V tile and its row scales."""
        pid = tables_ref[b, j]
        cps = [pltpu.make_async_copy(src, dst, sems.at[i])
               for i, (src, dst) in enumerate(
                   ((k_hbm.at[layer, pid], k_scr), (ks_hbm.at[pid], ks_scr),
                    (v_hbm.at[layer, pid], v_scr), (vs_hbm.at[pid], vs_scr)))]
        for c in cps:
            c.start()
        for c in cps:
            c.wait()
        k = k_scr[...].astype(jnp.float32).reshape(w, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * qs * ks_scr[...] / math.sqrt(hd)
        s = jnp.where(head_ok & (j * ps + row < L), s, -1e30)
        return s, v_scr[...].astype(jnp.float32).reshape(w, hd), vs_scr[...]

    def max_body(j, m):
        s, _, _ = load_scores(j)
        return jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))

    m = jax.lax.fori_loop(0, n_eff, max_body,
                          jnp.full((H, 1), -jnp.inf, jnp.float32))

    def norm_body(j, carry):
        l, u = carry
        s, _, vs = load_scores(j)
        p = jnp.exp(s - m)
        return l + jnp.sum(p, axis=-1, keepdims=True), \
            jnp.maximum(u, jnp.max(p * vs, axis=-1, keepdims=True))

    l, u = jax.lax.fori_loop(0, n_eff, norm_body,
                             (jnp.zeros((H, 1), jnp.float32),
                              jnp.zeros((H, 1), jnp.float32)))
    l = jnp.where(l > 0, l, 1.0)        # free slot (no pages): emit zeros
    # _quant_rows' scale over the probability row (probs * V row scales)
    pscale = jnp.maximum(u / l, 1e-6) / 127.0

    def acc_body(j, acc):
        s, v, vs = load_scores(j)
        p = jnp.exp(s - m) / l * vs
        pq = jnp.clip(jnp.round(p / pscale), -127, 127)
        part = jax.lax.dot_general(pq, v, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return acc + part.astype(jnp.int32)

    acc = jax.lax.fori_loop(0, n_eff, acc_body,
                            jnp.zeros((H, hd), jnp.int32))
    out_ref[0] = (acc.astype(jnp.float32) * pscale).astype(out_ref.dtype)


def _layer_arg(layer):
    """The layer index as the (1,) i32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def paged_decode(q, k_pool, v_pool, tables, n_pages, lengths, layer):
    """Decode attention straight off layer `layer` of the stacked paged
    pool (fp K/V).

    q: (B, H, hd) roped queries; pools: (n_periods, P, page_size, Hkv,
    hd); tables: (B, max_pages) i32; n_pages: (B,) i32; lengths: (B,)
    i32 rows each query attends (``position + 1``); layer: scalar i32.
    Returns (B, H, hd) in q.dtype.
    """
    return _paged_decode(q, k_pool, v_pool, tables, n_pages, lengths,
                         _layer_arg(layer), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode(q, k_pool, v_pool, tables, n_pages, lengths, layer, *,
                  interpret):
    B, H, hd = q.shape
    ps, Hkv = k_pool.shape[2], k_pool.shape[3]
    kern = functools.partial(_fp_kernel, page_size=ps, hkv=Hkv)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, hd), _row),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, H, hd), _row),
            scratch_shapes=[pltpu.VMEM((ps, Hkv, hd), k_pool.dtype),
                            pltpu.VMEM((ps, Hkv, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, n_pages, lengths, layer, q, k_pool, v_pool)


def paged_decode_q(q_int8, q_scale, k_pool, k_scales, v_pool, v_scales,
                   tables, n_pages, lengths, layer, out_dtype):
    """int8-KV decode attention off layer `layer` of the stacked
    quantized pool.

    q_int8/q_scale: (B, H, hd) int8 + (B, H) f32 row-quantized queries
    (callers quantize with `attention._quant_rows`, exactly as the oracle
    does); k/v pools: (n_periods, P, page_size, Hkv, hd) int8 with
    (n_periods, P, page_size, Hkv) f32 row scales.  Tolerance note vs
    `decode_attention_q`: the kernel replays the oracle's arithmetic step
    for step, including the int8 probability requantization before the PV
    dot (see `_q_kernel`); the only divergence left is the softmax
    normalizer's float association order (summed per page here, once
    over max_seq there), so outputs agree to reassociation error and
    greedy token streams stay identical (asserted in the parity suite)."""
    return _paged_decode_q(q_int8, q_scale, k_pool, k_scales, v_pool,
                           v_scales, tables, n_pages, lengths,
                           _layer_arg(layer),
                           out_dtype=jnp.dtype(out_dtype).name,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _paged_decode_q(q_int8, q_scale, k_pool, k_scales, v_pool, v_scales,
                    tables, n_pages, lengths, layer, *, out_dtype,
                    interpret):
    B, H, hd = q_int8.shape
    ps, Hkv = k_pool.shape[2], k_pool.shape[3]
    kern = functools.partial(_q_kernel, page_size=ps, hkv=Hkv)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, hd), _row),
                      pl.BlockSpec((1, H, 1), _row),
                      hbm, hbm, hbm, hbm],
            out_specs=pl.BlockSpec((1, H, hd), _row),
            scratch_shapes=[pltpu.VMEM((ps, Hkv, hd), jnp.int8),
                            pltpu.VMEM((1, ps * Hkv), jnp.float32),
                            pltpu.VMEM((ps, Hkv, hd), jnp.int8),
                            pltpu.VMEM((1, ps * Hkv), jnp.float32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, n_pages, lengths, layer, q_int8, q_scale[..., None],
      k_pool, _lane_rows(k_scales, layer), v_pool,
      _lane_rows(v_scales, layer))


def _lane_rows(scales, layer):
    """Layer `layer` of the (n_periods, P, page_size, Hkv) row scales ->
    (P, 1, page_size * Hkv): one lane-dense row per page, which the kernel
    can DMA (a (page_size, Hkv) tile is narrower than the 128-lane tiling
    a DMA slice must respect).  The reshape is a relayout copy of one
    layer's scale pool on every call; taking the layer first keeps it to
    that layer instead of the whole stack."""
    _, P, ps, hkv = scales.shape
    return jax.lax.dynamic_index_in_dim(scales, layer[0], keepdims=False) \
        .reshape(P, 1, ps * hkv)


# ---------------------------------------------------------------------------
# KV bytes-read accounting (the maxtext decode-microbenchmark currency)
# ---------------------------------------------------------------------------

def kv_row_bytes(cfg) -> int:
    """Bytes one decode step reads per cached KV row, summed over every
    layer that owns a paged pool (attn: K+V heads, int8 rows carry their
    f32 scales; mla: the latent c_kv + k_rope row; xattn/recurrent layers
    hold no paged pool and contribute nothing)."""
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    total = 0
    for spec in cfg.layer_pattern:
        if "mla" in spec:
            total += (cfg.kv_lora_rank + cfg.qk_rope_dim) * itemsize
        elif "attn" in spec and "xattn" not in spec:
            if getattr(cfg, "quant_kv", False):
                total += 2 * cfg.num_kv_heads * (cfg.hd + 4)  # int8 + f32
            else:
                total += 2 * cfg.num_kv_heads * cfg.hd * itemsize
    return total * cfg.n_periods


def decode_read_rows(lengths, page_size: int) -> int:
    """Pool rows ONE decode step touches under the kernel: each live
    sequence reads its allocated pages up to the page holding its last row
    (``ceil(length / page_size)`` tiles of ``page_size`` rows) — the
    page-bounded invariant this module exists for.  `lengths` are the live
    row counts (position + 1) of occupied slots; free slots read nothing."""
    return sum(-(-int(n) // page_size) * page_size for n in lengths if n > 0)


def oracle_read_rows(num_slots: int, max_seq: int) -> int:
    """Pool rows ONE decode step touches under the gather oracle:
    `paged_view` materializes all ``num_slots`` tables to ``max_seq`` rows
    each, live or not — the traffic floor the kernel removes."""
    return num_slots * max_seq
