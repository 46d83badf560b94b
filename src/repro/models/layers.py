"""Shared neural layers: norms, dense (BRAMAC-aware), SwiGLU MLP, RoPE, embed.

Pure-functional: `init_*` returns a param pytree, `*_apply` consumes it.
Every matmul flows through `dense()` so the BRAMAC quantized path is a
single-switch feature across the whole model zoo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bramac_linear as bl


def dense(x: jax.Array, w: jax.Array, quant: bl.QuantConfig | None) -> jax.Array:
    """All model matmuls route here → BRAMAC integration point."""
    return bl.dense(x, w, quant)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def he_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def init_dense(key, d_in, d_out, dtype):
    return he_init(key, (d_in, d_out), dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": init_dense(k1, d_model, d_ff, dtype),
            "w_up": init_dense(k2, d_model, d_ff, dtype),
            "w_down": init_dense(k3, d_ff, d_model, dtype)}


@jax.named_scope("mlp")
def mlp(p, x, quant=None):
    g = dense(x, p["w_gate"], quant)
    u = dense(x, p["w_up"], quant)
    return dense(jax.nn.silu(g) * u, p["w_down"], quant)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                    # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, vocab, d_model, dtype):
    k1, k2 = jax.random.split(key)
    return {"embedding": (jax.random.normal(k1, (vocab, d_model)) * 0.02
                          ).astype(dtype),
            "unembed": init_dense(k2, d_model, vocab, dtype)}


def embed(p, tokens):
    return jnp.take(p["embedding"], tokens, axis=0)


@jax.named_scope("unembed")
def unembed(p, x, quant=None):
    return dense(x, p["unembed"], quant)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy in f32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
