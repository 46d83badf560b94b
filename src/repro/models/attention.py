"""Attention mixers: GQA (+RoPE), MLA (latent attention), cross-attention.

All support three modes driven by the call:
  * train/prefill: full causal attention, query-chunked (online softmax per
    chunk is unnecessary — chunking the query axis alone bounds the score
    matrix at (B, H, chunk, S), which is what fits VMEM/HBM at 32k).
  * decode: single-token query against a KV cache updated in place.

Caches are plain dicts of arrays so they shard/checkpoint like params.

KV layouts (the paper's small-fixed-array memory discipline applied to
serving).  A cache leaf holds every scanned layer: its axis 0 is the
layer (period) index, and the mixers read and write it at the layer
they are given (`layer`, which the layer scan also stamps into the
`PagedKV`/`DenseKV` bundle), so the scan carries one set of buffers and
never slices a layer out or restacks one:
  * dense — per-sequence (B, max_seq, …) reservations, written at
    cache_pos (the start clamped as dynamic_update_slice clamps it).
  * paged — one shared pool of (page_size,)-row pages per layer plus
    per-sequence int32 block tables (a `PagedKV` bundle threaded through
    the forward call).  Writes scatter rows through the table (masked
    rows drop out of bounds), reads gather the table back into a
    (B, max_seq, …) view whose masked rows make exactly-zero softmax
    contributions — so the arithmetic is bit-identical to the dense
    layout while pool capacity is bounded by live tokens, not
    num_slots × max_seq.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as pk
from repro.models.layers import (apply_rope, apply_rope_pairs, dense,
                                 init_dense, init_rmsnorm, rmsnorm,
                                 yarn_freqs, yarn_mscale)

Q_CHUNK = 1024


# ---------------------------------------------------------------------------
# paged KV layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PagedKV:
    """Block-table view of a shared page pool, built *inside* traced code
    (plain dataclass, not a pytree: `max_seq`/`page_size` stay static).

    tables     (B, max_pages) i32 — page id of each sequence's page j;
               unallocated entries may hold any in-range id (their rows are
               only ever read masked).
    n_pages    (B,) i32 — pages actually allocated per sequence; writes to
               positions at or past `n_pages * page_size` are dropped.
    write_mask (B,) bool — sequences allowed to write this call (admitting
               slots during prefill, active slots during decode); a masked
               sequence's rows never reach the pool, so co-resident
               sequences sharing it stay untouched.
    owned      (B, max_pages) bool or None — per-table-entry write
               permission from the refcounted allocator: entries mapped
               read-only (prefix-cache shares) are False and their writes
               are dropped, so a slot can never corrupt a page other
               consumers read.  None (dense-era callers) means every
               allocated entry is writable.
    bound      (B,) i32 or None — per-sequence accepted-length bound for
               speculative decoding: writes at positions >= bound are
               dropped.  The engine sets bound = pos + budget so a draft
               window can never write rows a non-speculative run could
               not reach (and `pages.rollback` honours the same bound).
               None means no extra bound (the non-speculative paths).
    decode_kernel — static bool: route Sq=1 gqa reads through the pallas
               paged-attention kernel (`kernels/paged_attention.py`), which
               walks the block table page by page instead of gathering a
               dense (B, max_seq, …) view.  Writes, mla and Sq>1 chunks
               (prefill, the speculative verify window) always use the
               gather oracle, which stays the parity reference.
    layer      i32 scalar (or int) — the layer of the stacked pool
               (n_periods, P, page_size, …) this call writes and reads;
               the layer scan sets it per layer (`dataclasses.replace`).
    """
    tables: jax.Array
    n_pages: jax.Array
    write_mask: jax.Array
    max_seq: int
    page_size: int
    owned: jax.Array | None = None
    bound: jax.Array | None = None
    decode_kernel: bool = False
    layer: jax.Array | int = 0


@dataclasses.dataclass
class DenseKV:
    """Write discipline for the dense layout when rows are scattered at
    arbitrary per-row positions (the speculative verify chunk) instead of
    one contiguous dynamic_update_slice.  dynamic_update_slice CLAMPS a
    start index that would overflow — a draft window near max_seq would
    silently slide back and scramble earlier valid rows — so speculative
    dense writes go through a per-position scatter that *drops*
    out-of-range rows instead, mirroring `paged_update`'s masking
    (write_mask gates whole sequences; `bound` is the same per-sequence
    accepted-length bound PagedKV carries; `layer` is the layer of the
    stacked cache written, as in PagedKV)."""
    write_mask: jax.Array                       # (B,) bool
    max_seq: int
    bound: jax.Array | None = None              # (B,) i32
    layer: jax.Array | int = 0


def dense_update(cache, new, positions, dv: DenseKV):
    """Scatter `new` (B, S, …) rows into layer `dv.layer` of the stacked
    dense cache (n_periods, B, max_seq, …) at absolute `positions`
    (B, S); masked / out-of-range rows drop.

    Both bounds matter: a negative position would wrap (`.at[]` follows
    NumPy indexing) and silently alias the tail of a live sequence."""
    ok = dv.write_mask[:, None] & (positions < dv.max_seq) & (positions >= 0)
    if dv.bound is not None:
        ok &= positions < dv.bound[:, None]
    pos = jnp.where(ok, positions, dv.max_seq)  # max_seq is OOB -> dropped
    rows = jnp.arange(positions.shape[0])[:, None]
    return cache.at[dv.layer, rows, pos].set(new.astype(cache.dtype),
                                             mode="drop")


def paged_update(pool, new, positions, pv: PagedKV):
    """Scatter `new` (B, S, …) rows at absolute `positions` (B, S) through
    the block table into layer `pv.layer` of the stacked pool
    ((n_periods, P, page_size, …)), in place.  Masked / out-of-range
    rows — and rows aimed at a shared (un-owned) page or past the
    speculative bound — are routed to page id P and dropped.

    The lower bound is load-bearing: a negative position floor-divides to a
    negative pg_idx (which passes `< n_pages`), clips to table entry 0, and
    `% page_size` wraps its row positive — without `positions >= 0` a stray
    padding row would land inside a live page."""
    P, ps = pool.shape[1], pv.page_size
    pg_idx = positions // ps
    ok = pv.write_mask[:, None] & (pg_idx < pv.n_pages[:, None]) \
        & (positions < pv.max_seq) & (positions >= 0)
    if pv.owned is not None:
        ok &= jnp.take_along_axis(
            pv.owned, jnp.clip(pg_idx, 0, pv.tables.shape[1] - 1), axis=1)
    if pv.bound is not None:
        ok &= positions < pv.bound[:, None]
    pg = jnp.take_along_axis(
        pv.tables, jnp.clip(pg_idx, 0, pv.tables.shape[1] - 1), axis=1)
    pg = jnp.where(ok, pg, P)                       # OOB page id -> dropped
    return pool.at[pv.layer, pg, positions % ps].set(
        new.astype(pool.dtype), mode="drop")


def paged_view(pool, pv: PagedKV):
    """Gather each sequence's pages of layer `pv.layer` of the stacked pool
    into a dense (B, max_seq, …) view, in one gather (no layer of the
    pool is sliced out first).

    Unallocated table entries gather garbage rows, but every such row sits
    at a position the causal mask excludes, where `_attend` replaces its
    score with exactly -1e30 — identical to the dense layout's untouched
    rows, so downstream softmax arithmetic is bit-identical."""
    view = pool[pv.layer, jnp.clip(pv.tables, 0, pool.shape[1] - 1)]
    B = pv.tables.shape[0]
    view = view.reshape((B, -1) + pool.shape[3:])
    return view[:, :pv.max_seq]


# ---------------------------------------------------------------------------
# core softmax attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, mask, scale=None):
    """q: (B,Sq,H,hd) k/v: (B,Sk,Hkv,hd); mask: (Sq,Sk) or (B,1,Sq,Sk);
    scores are scaled by `scale`, by default 1/sqrt(hd)."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Sq, Hkv, group, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, :, None]                      # (B,1,1,Sq,Sk)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])   # v head dim may differ (MLA)


def causal_attention(q, k, v, q_offset=0, scale=None):
    """Query-chunked causal attention (training / prefill)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if Sq <= Q_CHUNK:
        mask = (jnp.arange(Sk)[None, :] <=
                (jnp.arange(Sq)[:, None] + q_offset))
        return _attend(q, k, v, mask, scale)
    # ragged sequences run the full chunks through the scanned body and the
    # leftover rows (< Q_CHUNK of them) through one extra trailing _attend
    n_chunks = Sq // Q_CHUNK
    Sq_full = n_chunks * Q_CHUNK
    qc = q[:, :Sq_full].reshape(B, n_chunks, Q_CHUNK, H, hd).swapaxes(0, 1)

    def body(i, qi):
        offs = q_offset + i * Q_CHUNK
        mask = (jnp.arange(Sk)[None, :] <=
                (jnp.arange(Q_CHUNK)[:, None] + offs))
        return _attend(qi, k, v, mask, scale)

    out = jax.lax.map(lambda args: body(*args),
                      (jnp.arange(n_chunks), qc))
    out = out.swapaxes(0, 1).reshape(B, Sq_full, H, v.shape[-1])
    if Sq_full < Sq:
        tail = Sq - Sq_full
        mask = (jnp.arange(Sk)[None, :] <=
                (jnp.arange(tail)[:, None] + q_offset + Sq_full))
        out = jnp.concatenate(
            [out, _attend(q[:, Sq_full:], k, v, mask, scale)], axis=1)
    return out


def decode_attention(q, k_cache, v_cache, pos):
    """Single-token decode: the pos-masked cache attention of
    chunk_attention at Sq=1.  q: (B,1,H,hd); pos: (B,) current lengths."""
    return chunk_attention(q, k_cache, v_cache, pos[:, None])


def chunk_attention(q, k_cache, v_cache, positions, scale=None):
    """Causal attention of a prefill chunk at an arbitrary offset.

    q: (B,Sq,H,hd) chunk queries; caches: (B,S_max,Hkv,hd) already updated
    with this chunk's K/V; positions: (B,Sq) absolute query positions.
    Each query row attends every cache row at or before its own position —
    at offset 0 this reduces to plain causal prefill (rows past the chunk
    are masked to exact zeros), and at offset>0 it sees all earlier chunks."""
    Sk = k_cache.shape[1]
    mask = jnp.arange(Sk)[None, None, :] <= positions[:, :, None]  # (B,Sq,Sk)
    return _attend(q, k_cache, v_cache, mask[:, None], scale)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg):
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.compute_dtype
    return {"wq": init_dense(ks[0], d, H * hd, dt),
            "wk": init_dense(ks[1], d, Hkv * hd, dt),
            "wv": init_dense(ks[2], d, Hkv * hd, dt),
            "wo": init_dense(ks[3], H * hd, d, dt)}


def gqa(p, x, cfg, positions, cache=None, cache_pos=None, paged=None,
        layer=None):
    """cache: {"k","v"} stacked over the scanned layers, (n_periods, B,
    S_max, Hkv, hd), or (n_periods, P, page_size, Hkv, hd) pools when a
    `PagedKV` bundle is passed, or None (train/prefill).  Only layer
    `layer` is written and read; the whole stacked cache comes back.

    The cache write is scoped `kv_write` beside, not inside, the
    `attention` scope of the projections and the read, so a trace
    groups every device op under one of the two.

    Returns (out, new_cache)."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attention"):
        q = dense(x, p["wq"], cfg.quant).reshape(B, S, H, hd)
        k = dense(x, p["wk"], cfg.quant).reshape(B, S, Hkv, hd)
        v = dense(x, p["wv"], cfg.quant).reshape(B, S, Hkv, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cache is None:
        new_cache = None
    elif "ks" in cache:                          # int8 KV cache (quant_kv)
        new_cache = _update_cache_q(cache, k, v, cache_pos, paged, positions,
                                    layer)
    else:
        with jax.named_scope("kv_write"):
            write = _row_writer(cache_pos, paged, positions, layer)
            new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
    with jax.named_scope("attention"):
        kernel = isinstance(paged, PagedKV) and paged.decode_kernel \
            and S == 1
        if cache is None:
            out = causal_attention(q, k, v)
        elif "ks" in cache:
            # decode and prefill chunks both attend the stored int8 rows
            # (earlier chunks only exist quantized) via the same masked
            # path
            if kernel:
                # page-bounded pallas decode: the pool is read as stored
                # int8, one (page_size, Hkv, hd) tile at a time (q
                # row-quantized exactly as decode_attention_q would)
                qq, qs = _quant_rows(q)
                out = pk.paged_decode_q(
                    qq[:, 0], qs[:, 0], new_cache["k"], new_cache["ks"],
                    new_cache["v"], new_cache["vs"], paged.tables,
                    paged.n_pages, positions[:, 0] + 1, paged.layer,
                    q.dtype)[:, None]
            else:
                out = decode_attention_q(
                    q, {key: _layer_view(new_cache[key], paged, layer)
                        for key in new_cache}, positions)
        elif kernel:
            # page-bounded pallas decode kernel; the gather below stays
            # the parity oracle (and the Sq>1 prefill/verify path)
            out = pk.paged_decode(q[:, 0], new_cache["k"], new_cache["v"],
                                  paged.tables, paged.n_pages,
                                  positions[:, 0] + 1,
                                  paged.layer)[:, None]
        else:
            # paged and dense decode (S=1, positions == cache_pos),
            # prefill chunks and the speculative dense window share the
            # same masked path over the layer's (B, max_seq, …) rows
            out = chunk_attention(q, _layer_view(new_cache["k"], paged, layer),
                                  _layer_view(new_cache["v"], paged, layer),
                                  positions)
        out = dense(out.reshape(B, S, H * hd), p["wo"], cfg.quant)
    return out, new_cache


def _row_writer(pos, paged, positions, layer):
    """The cache write of one layout into one layer of a stacked leaf: a
    per-position scatter with drop for the speculative dense window
    (`DenseKV`) and the paged pool, at the bundle's layer; a per-sequence
    contiguous write at `pos` and `layer` for the dense cache."""
    if isinstance(paged, DenseKV):
        return lambda c, n: dense_update(c, n, positions, paged)
    if paged is not None:
        return lambda c, n: paged_update(c, n, positions, paged)
    return lambda c, n: _update_cache(c, n, pos, layer)


def _layer_view(cache, paged, layer):
    """One layer's (B, max_seq, …) rows of a stacked cache leaf: the
    paged pool's gather through the block table (at the bundle's layer),
    or the dense slice at `layer`."""
    if isinstance(paged, PagedKV):
        return paged_view(cache, paged)
    return cache[layer]


def _update_cache(cache, new, pos, layer):
    """Insert `new` (B,S,…) into layer `layer` of the stacked cache
    (n_periods, B, max_seq, …) at per-batch position `pos` (B,).  The
    start is clamped to [0, max_seq - S], exactly as a per-sequence
    dynamic_update_slice clamps it (S == max_seq overwrites every row)."""
    B, S = new.shape[:2]
    start = jnp.clip(pos, 0, cache.shape[2] - S)
    rows = start[:, None] + jnp.arange(S, dtype=start.dtype)[None]
    return cache.at[layer, jnp.arange(B)[:, None], rows].set(
        new.astype(cache.dtype))


def init_gqa_cache(cfg, batch, max_seq, dtype, num_pages=None):
    """num_pages=None: dense (batch, max_seq, …) reservations; otherwise a
    shared paged pool of (num_pages, page_size, …) — no batch axis, the
    engine's block tables carry the sequence↔page mapping."""
    hd = cfg.hd
    if num_pages is None:
        shape = (batch, max_seq, cfg.num_kv_heads, hd)
        sshape = (batch, max_seq, cfg.num_kv_heads)
    else:
        shape = (num_pages, cfg.page_size, cfg.num_kv_heads, hd)
        sshape = (num_pages, cfg.page_size, cfg.num_kv_heads)
    if getattr(cfg, "quant_kv", False):
        # int8 KV cache (beyond-paper: the paper's integer-MAC dataflow
        # applied to the cache, which dominates decode HBM bytes)
        return {"k": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(sshape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "vs": jnp.ones(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# int8 KV cache (quant_kv) — BRAMAC integer arithmetic inside attention
# ---------------------------------------------------------------------------

def _quant_rows(x):
    """Per-(…, head) row int8 quantization over the feature dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _update_cache_q(cache, k, v, pos, paged, positions, layer):
    with jax.named_scope("kv_write"):
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        write = _row_writer(pos, paged, positions, layer)
        return {"k": write(cache["k"], kq), "ks": write(cache["ks"], ks),
                "v": write(cache["v"], vq), "vs": write(cache["vs"], vs)}


def decode_attention_q(q, cache, positions):
    """Attention over the int8 cache — decode (Sq=1) and offset prefill
    chunks (Sq=C) alike; positions: (B, Sq) absolute query positions.

    Both dots run int8×int8→int32 on the MXU (the nd=1 endpoint of the
    BRAMAC digit loop): Q is row-quantized on the fly; K's scales factor
    out of the score dot; V's *per-position* scales fold into the
    probabilities elementwise before the PV dot, so V is consumed as
    stored int8 — no dequantized cache copy is ever materialized."""
    B, Sq, H, hd = q.shape
    kc, ks, vc, vs = cache["k"], cache["ks"], cache["v"], cache["vs"]
    Sk, Hkv = kc.shape[1], kc.shape[2]
    group = H // Hkv
    qq, qs = _quant_rows(q)                              # (B,Sq,H,hd),(B,Sq,H)
    qg = qq.reshape(B, Sq, Hkv, group, hd)
    scores_i = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,      # int8 MXU dot
                          preferred_element_type=jnp.int32)
    qs_g = qs.reshape(B, Sq, Hkv, group).transpose(0, 2, 3, 1)  # (B,Hkv,g,Sq)
    scores = scores_i.astype(jnp.float32) \
        * qs_g[..., None] * ks.transpose(0, 2, 1)[:, :, None, None, :]
    scores = scores / math.sqrt(hd)
    mask = (jnp.arange(Sk)[None, None, :]
            <= positions[:, :, None])[:, None, None]     # (B,1,1,Sq,Sk)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    # fold per-position V scales into the probabilities, requantize rows
    pv = probs * vs.transpose(0, 2, 1)[:, :, None, None, :]  # (B,Hkv,g,Sq,Sk)
    pq, pscale = _quant_rows(pv)
    out_i = jnp.einsum("bhgqk,bkhd->bqhgd", pq, vc,
                       preferred_element_type=jnp.int32)
    out = out_i.astype(jnp.float32) \
        * pscale.transpose(0, 3, 1, 2)[..., None]            # (B,Sq,Hkv,g,1)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3/DeepSeek style)
# ---------------------------------------------------------------------------

def init_mla(key, cfg):
    """With `q_lora_rank` 0 the query is one projection `w_q` (DeepSeek-V2-
    Lite); otherwise a LoRA `w_dq`, `q_norm`, `w_uq`."""
    d = cfg.d_model
    H = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    dt = cfg.compute_dtype
    q = {"w_dq": init_dense(ks[0], d, qr, dt),
         "q_norm": init_rmsnorm(qr, dt),
         "w_uq": init_dense(ks[1], qr, H * (nope + rope), dt)} if qr \
        else {"w_q": init_dense(ks[0], d, H * (nope + rope), dt)}
    return q | {
        "w_dkv": init_dense(ks[2], d, kvr, dt),
        "kv_norm": init_rmsnorm(kvr, dt),
        "w_kr": init_dense(ks[3], d, rope, dt),
        "w_uk": init_dense(ks[4], kvr, H * nope, dt),
        "w_uv": init_dense(ks[5], kvr, H * vd, dt),
        "wo": init_dense(ks[6], H * vd, d, dt),
    }


def _mla_rope(cfg):
    """(rope(x, positions), softmax scale or None for 1/sqrt(hd)) of MLA's
    rope dims: `apply_rope` over the two halves, or, with
    `rope_interleaved`, DeepSeek-V2's adjacent pairs at YaRN frequencies
    (`yarn_freqs`; cos/sin scaled by mscale / mscale_all_dim, scores by
    mscale_all_dim^2 / sqrt(nope + rope))."""
    if not cfg.rope_interleaved:
        return (lambda t, pos: apply_rope(t, pos, cfg.rope_theta)), None
    f = cfg.rope_scaling_factor
    freqs = yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, f,
                       cfg.rope_original_max_position, cfg.rope_beta_fast,
                       cfg.rope_beta_slow)
    m_all = yarn_mscale(f, cfg.rope_mscale_all_dim) \
        if cfg.rope_mscale_all_dim else 1.0
    cs = yarn_mscale(f, cfg.rope_mscale) / m_all
    scale = m_all * m_all / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    return (lambda t, pos: apply_rope_pairs(t, pos, freqs, cs)), scale


def mla(p, x, cfg, positions, cache=None, cache_pos=None, paged=None,
        layer=None):
    """Latent attention; the cache stores only (c_kv, k_rope) — the paper's
    BRAMAC quantization applies to every projection here as well.  The
    stacked cache is written and read at `layer`; the write is scoped
    `kv_write`, the rest `attention`, as in gqa.  The cached rows are
    up-projected by `w_uk`/`w_uv` at every read (the un-absorbed form)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    rotate, scale = _mla_rope(cfg)
    with jax.named_scope("attention"):
        if cfg.q_lora_rank:
            q = dense(rmsnorm(p["q_norm"], dense(x, p["w_dq"], cfg.quant),
                              cfg.norm_eps), p["w_uq"], cfg.quant)
        else:
            q = dense(x, p["w_q"], cfg.quant)
        q = q.reshape(B, S, H, nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = rotate(q_rope, positions)

        c_kv = rmsnorm(p["kv_norm"], dense(x, p["w_dkv"], cfg.quant),
                       cfg.norm_eps)
        k_rope = rotate(dense(x, p["w_kr"], cfg.quant)[:, :, None, :],
                        positions)[:, :, 0]                # (B,S,rope)

    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            write = _row_writer(cache_pos, paged, positions, layer)
            new_cache = {"c_kv": write(cache["c_kv"], c_kv),
                         "k_rope": write(cache["k_rope"], k_rope)}
    with jax.named_scope("attention"):
        if cache is not None:
            # up-projections run over the layer's (B, max_seq, …) rows:
            # the gathered view (paged) or the dense cache
            c_kv = _layer_view(new_cache["c_kv"], paged, layer)
            k_rope = _layer_view(new_cache["k_rope"], paged, layer)
        Sk = c_kv.shape[1]

        k_nope = dense(c_kv, p["w_uk"], cfg.quant).reshape(B, Sk, H, nope)
        v = dense(c_kv, p["w_uv"], cfg.quant).reshape(B, Sk, H, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, Sk, H, rope))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

        if cache is not None:                    # decode or prefill chunk
            out = chunk_attention(q_full, k, v, positions, scale)
        else:
            out = causal_attention(q_full, k[:, :S], v[:, :S], scale=scale)
        out = dense(out.reshape(B, S, H * vd), p["wo"], cfg.quant)
    return out, new_cache


def init_mla_cache(cfg, batch, max_seq, dtype, num_pages=None):
    lead = (batch, max_seq) if num_pages is None \
        else (num_pages, cfg.page_size)
    return {"c_kv": jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
            "k_rope": jnp.zeros(lead + (cfg.qk_rope_dim,), dtype)}


# ---------------------------------------------------------------------------
# Cross-attention (VLM: text queries attend to image patch embeddings)
# ---------------------------------------------------------------------------

def init_xattn(key, cfg):
    return init_gqa(key, cfg) | {
        "kv_norm": init_rmsnorm(cfg.d_model, cfg.compute_dtype)}


def xattn(p, x, cfg, vision_embeds, cache=None, cache_pos=None, layer=None):
    """vision_embeds: (B, T_v, D) precomputed patch embeddings (stub
    frontend per the assignment).  K/V are position-free; for decode the
    projected K/V are cached once, at `layer` of the stacked cache
    (n_periods, B, T_v, Hkv, hd), which decode reads and returns
    unchanged."""
    with jax.named_scope("attention"):
        B, S, _ = x.shape
        H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        q = dense(x, p["wq"], cfg.quant).reshape(B, S, H, hd)
        decoding = cache is not None and S == 1    # static condition
        if decoding:
            # projected during prefill
            k, v = cache["k"][layer], cache["v"][layer]
        else:
            ve = rmsnorm(p["kv_norm"], vision_embeds, cfg.norm_eps)
            Tv = ve.shape[1]
            k = dense(ve, p["wk"], cfg.quant).reshape(B, Tv, Hkv, hd)
            v = dense(ve, p["wv"], cfg.quant).reshape(B, Tv, Hkv, hd)
        mask = jnp.ones((S, k.shape[1]), bool)
        out = _attend(q, k, v, mask)
        if cache is None or decoding:
            new_cache = cache
        else:
            new_cache = {"k": cache["k"].at[layer].set(
                             k.astype(cache["k"].dtype)),
                         "v": cache["v"].at[layer].set(
                             v.astype(cache["v"].dtype))}
        out = dense(out.reshape(B, S, H * hd), p["wo"], cfg.quant)
    return out, new_cache


def init_xattn_cache(cfg, batch, dtype):
    shape = (batch, cfg.vision_tokens, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
