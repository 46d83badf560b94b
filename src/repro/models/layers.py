"""Shared neural layers: norms, dense (BRAMAC-aware), SwiGLU MLP, RoPE, embed.

Pure-functional: `init_*` returns a param pytree, `*_apply` consumes it.
Every matmul flows through `dense()` so the BRAMAC quantized path is a
single-switch feature across the whole model zoo.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

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


def swiglu(p, x, quant=None):
    g = dense(x, p["w_gate"], quant)
    u = dense(x, p["w_up"], quant)
    return dense(jax.nn.silu(g) * u, p["w_down"], quant)


@jax.named_scope("mlp")
def mlp(p, x, quant=None):
    return swiglu(p, x, quant)


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


# YaRN (arXiv:2309.00071) as DeepSeek-V2 applies it to MLA's rope dims
# (`DeepseekV2YarnRotaryEmbedding`): dims rotating fewer than `beta_slow`
# times over the original context are interpolated by `factor`, those
# rotating more than `beta_fast` times are kept, with a linear ramp
# between; attention logits are scaled by mscale(factor, mscale_all_dim)^2.

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(dim: int, theta: float, beta_fast: float, beta_slow: float,
               original: int) -> tuple[int, int]:
    """(low, high): the frequency indices where the ramp starts and ends."""
    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    return max(math.floor(corr(beta_fast)), 0), \
        min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_freqs(dim: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """(dim/2,) f32 inverse frequencies; a factor of 1 gives plain RoPE's."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra = (1.0 / base).astype(np.float32)
    if factor <= 1:
        return extra
    inter = (1.0 / (factor * base)).astype(np.float32)
    low, high = yarn_range(dim, theta, beta_fast, beta_slow, original)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / ((high - low) or 0.001), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope_pairs(x: jax.Array, positions: jax.Array, freqs,
                     scale: float = 1.0) -> jax.Array:
    """Rotate adjacent pairs (2i, 2i+1) of x (..., S, H, hd) by
    positions (..., S) x freqs[i]; cos and sin are scaled by `scale`."""
    angles = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(freqs, jnp.float32)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


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
