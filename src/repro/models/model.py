"""Top-level model API: init / train forward / loss / prefill / decode.

Batch dict conventions (ShapeDtypeStruct stand-ins come from
launch.input_specs with identical structure):

  LM / code / dense / moe : {"tokens": (B,S) i32, "labels": (B,S) i32}
  vlm   : + {"vision_embeds": (B, T_v, D)}   (stub frontend)
  audio : {"frame_embeds": (B,S,D), "labels": (B,S)}  (stub EnCodec)

Serving:
  prefill(params, batch, cache)   — writes the cache, returns last logits
  decode_step(params, tokens, cache, pos) — one token for every sequence
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.layers import cross_entropy, embed, init_embed, init_rmsnorm, \
    rmsnorm, unembed
from repro.parallel.sharding import constrain


def init_params(cfg: ModelConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    params = {"embed": init_embed(k1, cfg.vocab_size, cfg.d_model,
                                  cfg.compute_dtype),
              "final_norm": init_rmsnorm(cfg.d_model, cfg.compute_dtype),
              "layers": tf.init_stack(k2, cfg)}
    if cfg.first_dense_layers:
        params["dense_layers"] = tf.init_leading(jax.random.fold_in(key, 2),
                                                 cfg)
    return params


def _inputs_to_hidden(params, batch, cfg):
    if "frame_embeds" in batch:                      # audio stub frontend
        x = batch["frame_embeds"].astype(cfg.compute_dtype)
    else:
        x = embed(params["embed"], batch["tokens"])
    return constrain(x, "batch", None, None)


def forward(params, batch, cfg: ModelConfig, caches=None, cache_pos=None,
            last_only: bool = False, gather_pos=None, paged=None):
    """Returns (logits, aux_loss, new_caches).

    last_only: unembed only the final position — prefill at 32k would
    otherwise materialize a (B, 32768, vocab) logits tensor.
    gather_pos: (B,) per-sequence position to unembed instead (chunked
    prefill: each slot's true last prompt token sits at a different row);
    returns (B, 1, vocab) logits like last_only.
    paged: an attention.PagedKV bundle — caches hold shared page pools
    instead of dense per-sequence reservations, and attention
    gathers/scatters KV rows through its block tables.  The bundle's
    block tables / refcounts / ownership bits come from the engine's
    `runtime.pages.PagePool` allocator state: entries mapped read-only
    (prefix-cache shares) carry owned=False, and the paged scatter drops
    their writes so shared pages are never corrupted.  A bundle with
    decode_kernel=True additionally routes S=1 gqa reads through the
    pallas paged-decode kernel (kernels/paged_attention.py) — per-step
    traffic bounded by each sequence's live pages, never max_seq; mla
    and S>1 chunks keep the gather oracle."""
    x = _inputs_to_hidden(params, batch, cfg)
    B, S = x.shape[:2]
    if cache_pos is not None:
        # serving: absolute positions start at each sequence's cache_pos —
        # S == 1 is a decode step, S > 1 a (possibly offset) prefill chunk
        positions = cache_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    ve = batch.get("vision_embeds")
    if ve is not None:
        ve = ve.astype(cfg.compute_dtype)
    if cfg.first_dense_layers:
        x, aux0, caches = tf.leading_apply(
            params["dense_layers"], x, cfg, positions=positions,
            vision_embeds=ve, caches=caches, cache_pos=cache_pos, paged=paged)
    x, aux, new_caches = tf.stack_apply(
        params["layers"], x, cfg, positions=positions, vision_embeds=ve,
        caches=caches, cache_pos=cache_pos, paged=paged)
    if cfg.first_dense_layers:
        aux = aux + aux0
    if last_only:
        x = x[:, -1:]
    elif gather_pos is not None:
        x = jnp.take_along_axis(x, gather_pos[:, None, None], axis=1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.quant)
    return constrain(logits, "batch", None, "tp"), aux, new_caches


def loss_fn(params, batch, cfg: ModelConfig, aux_weight: float = 0.01):
    logits, aux, _ = forward(params, batch, cfg)
    loss = cross_entropy(logits, batch["labels"])
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, num_pages=None):
    """num_pages=None: dense [batch, max_seq] KV reservations.  Otherwise
    attention KV lives in a shared pool of `num_pages` pages of
    `cfg.page_size` rows each (block tables, refcounts and page ownership
    are engine state — a `runtime.pages.PagePool` — passed to
    forward/decode_step as an attention.PagedKV bundle)."""
    return tf.init_stack_cache(cfg, batch, max_seq, cfg.compute_dtype,
                               num_pages)


def cache_pool_flags(cfg: ModelConfig):
    """Pytree matching init_cache(num_pages=...) with True at shared-pool
    leaves, False at per-slot leaves (recurrent state, xattn KV)."""
    return tf.stack_cache_pool_flags(cfg)


def prefill(params, batch, cfg: ModelConfig, caches):
    """Run the prompt through the model, filling the cache.

    Returns (last_token_logits (B,V), new_caches)."""
    B, S = _batch_bs(batch, cfg)
    cache_pos = jnp.zeros((B,), jnp.int32)      # prefill writes from 0
    logits, _, new_caches = forward(params, batch, cfg, caches, cache_pos,
                                    last_only=True)
    return logits[:, -1], new_caches


def decode_step(params, tokens, cfg: ModelConfig, caches, pos, paged=None):
    """tokens: (B,1) i32; pos: (B,) current position (index being written).

    Returns (logits (B,V), new_caches)."""
    batch = {"tokens": tokens}
    logits, _, new_caches = forward(params, batch, cfg, caches, cache_pos=pos,
                                    paged=paged)
    return logits[:, 0], new_caches


def _batch_bs(batch, cfg):
    if "frame_embeds" in batch:
        return batch["frame_embeds"].shape[:2]
    return batch["tokens"].shape[:2]
