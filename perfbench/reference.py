"""Plain float32 reference of the dense llama-style decoder the configs
describe (granite-8b: arXiv:2405.04324), independent of the program.

It imports nothing of the program and takes nothing the program made:
its weights come from `weights.draw` with the seed, leaf by leaf.  The
math, per layer: x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x));
x += Wd·(silu(Wg·n2(x)) * Wu·n2(x)); then the final RMSNorm and an untied
unembedding.  RoPE rotates the two halves of each head (HF llama
`rotate_half`), query head h reads key/value head h // (H / Hkv), and
attention is scaled by 1/sqrt(head_dim) with the softmax in float32.

A configuration with `"weights": "int8"` states w8a8 serving: each linear
is an exact int8 x int8 -> int32 dot of per-output-channel int8 weights
and per-row symmetric int8 activations (scale = max|row| / 127, values
round(x / scale) clipped to [-128, 127]), dequantized by both scales.

`quant=(bits_w, bits_a)` computes the same model one precision lower
(the control of `correct.py`): every linear's weights are quantized per
output channel to bits_w and its activations per row to bits_a.

Work is done layer by layer over groups of sequences, so the weights of
one layer and the activations of a few sequences are all that is held.

This is one reference module; a configuration file names its own under
`"reference"` (a path from the checkout's root, this one by default),
and `correct.py` loads it by path.  A reference module provides:

  logits_at(cfg, seed, tokens, rows, quant=None, group=4)  required: as
      below, the whole configuration file as `cfg`;
  Q_CHUNK  required: the sequence length `tokens` is padded to a
      multiple of;
  KINDS    optional: {kind: fn(key, shape, dtype)} for weights that
      `weights.draw`'s kinds do not cover (a zero bias, a state-space
      constant); `system.make_params` draws such a kind with
      `fn(weights.leaf_key(root, name, layer), shape, dtype)`, and the
      module draws it the same way.

Layers are absolute: stacked leaf row j under the program's
`layers.pos{k}` is reference layer j * len(layer_pattern) + k, drawn
with that index.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights as W

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 128
LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(cfg: dict) -> dict:
    d, H, Hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    return {"d": d, "H": H, "Hkv": Hkv, "hd": hd,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def linear_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, H, Hkv, hd, ff = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"]
    return {"wq": (d, H * hd), "wk": (d, Hkv * hd), "wv": (d, Hkv * hd),
            "wo": (H * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}


def _quant_rows(x, bits):
    hi = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / hi
    q = jnp.clip(jnp.round(x / s), -hi - 1, hi).astype(jnp.int8)
    return q, s


def _quant_cols(w, bits):
    hi = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-8) / hi
    q = jnp.clip(jnp.round(w / s), -hi - 1, hi).astype(jnp.int8)
    return q, s


def _int_dot(xq, wq):
    return jax.lax.dot_general(xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _mm(x, w, bits_a):
    """x @ w for w = float array, or (int8 values, f32 scale) with
    per-row activation quantization to bits_a."""
    if bits_a is None:
        return jnp.dot(x, w.astype(jnp.float32), precision=HI)
    wq, ws = w
    xq, xs = _quant_rows(x, bits_a)
    return _int_dot(xq, wq).astype(jnp.float32) * xs * ws


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (G, S, heads, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA over query chunks.  q (G,S,H,hd), k/v (G,S,Hkv,hd)."""
    G, S, H, hd = q.shape
    Hkv = k.shape[2]
    n = S // Q_CHUNK
    qc = q.reshape(G, n, Q_CHUNK, Hkv, H // Hkv, hd).transpose(
        1, 0, 2, 3, 4, 5)

    def chunk(args):
        i, qi = args
        s = jnp.einsum("gqhjd,gkhd->ghjqk", qi, k, precision=HI)
        s = s / math.sqrt(hd)
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        mask = jnp.arange(S)[None, :] <= rows[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ghjqk,gkhd->gqhjd", p, v, precision=HI)

    out = jax.lax.map(chunk, (jnp.arange(n), qc))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(G, S, H * hd)


def _layer_weights(cfg, quant, root, i):
    """Layer i's weights as the reference computes with them."""
    kind = "int8" if cfg["weights"] == "int8" else "matrix"
    out = {"n1": W.draw(root, "attn_norm", i, (dims(cfg)["d"],), "norm"),
           "n2": W.draw(root, "mlp_norm", i, (dims(cfg)["d"],), "norm")}
    for name, shape in linear_shapes(cfg).items():
        out[name] = _prepare(W.draw(root, name, i, shape, kind), quant)
    return out


def _prepare(w, quant):
    """A drawn linear weight in the form `_mm` takes."""
    if quant is None:
        if isinstance(w, tuple):
            return w
        return w.astype(jnp.float32)
    dense = w[0].astype(jnp.float32) * w[1] if isinstance(w, tuple) \
        else w.astype(jnp.float32)
    return _quant_cols(dense, quant[0])


def _bits_a(cfg, quant):
    if quant is not None:
        return quant[1]
    return cfg.get("activation_bits") if cfg["weights"] == "int8" else None


def _layer(cfg, quant, x, w):
    m = dims(cfg)
    G, S, _ = x.shape
    ba, eps = _bits_a(cfg, quant), cfg["rms_norm_eps"]
    h = _rms(x, w["n1"], eps)
    q = _mm(h, w["wq"], ba).reshape(G, S, m["H"], m["hd"])
    k = _mm(h, w["wk"], ba).reshape(G, S, m["Hkv"], m["hd"])
    v = _mm(h, w["wv"], ba).reshape(G, S, m["Hkv"], m["hd"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    x = x + _mm(_attention(q, k, v), w["wo"], ba)
    h = _rms(x, w["n2"], eps)
    g = _mm(h, w["w_gate"], ba)
    u = _mm(h, w["w_up"], ba)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], ba)


def _head_weights(cfg, quant, root):
    m = dims(cfg)
    kind = "int8" if cfg["weights"] == "int8" else "matrix"
    return {"norm": W.draw(root, "final_norm", 0, (m["d"],), "norm"),
            "unembed": _prepare(W.draw(root, "unembed", 0, (m["d"], m["V"]),
                                       kind), quant)}


def _head(cfg, quant, x, rows, w):
    """Logits (G, R, V) at hidden rows `rows` (G, R)."""
    h = jnp.take_along_axis(x, rows[..., None], axis=1)
    h = _rms(h, w["norm"], cfg["rms_norm_eps"])
    return _mm(h, w["unembed"], _bits_a(cfg, quant))


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, quant):
    cfg = dict(cfg_items)
    m = dims(cfg)
    embed = jax.jit(lambda root, toks: jnp.take(
        W.draw(root, "embedding", 0, (m["V"], m["d"]), "embed"), toks,
        axis=0).astype(jnp.float32))
    return (embed,
            jax.jit(functools.partial(_layer_weights, cfg, quant)),
            jax.jit(functools.partial(_layer, cfg, quant)),
            jax.jit(functools.partial(_head_weights, cfg, quant)),
            jax.jit(functools.partial(_head, cfg, quant)))


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              quant: tuple | None = None, group: int = 4) -> list:
    """Reference logits at chosen positions.

    tokens (N, S) int32, right-padded, S a multiple of `Q_CHUNK`; rows
    (N, R) int32 positions whose next-token logits are wanted (padding
    rows may repeat a valid one).  Returns one (g, R, V) f32 device array
    per group of `group` sequences."""
    cfg = {k: v for k, v in cfg.items() if not isinstance(v, (dict, list))}
    N, S = tokens.shape
    if S % Q_CHUNK:
        raise ValueError(f"sequence length {S} is not a multiple of "
                         f"{Q_CHUNK}")
    embed, lw, layer, hw, head = _programs(tuple(sorted(cfg.items())), quant)
    root = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        xs = [embed(root, jnp.asarray(tokens[i:i + group]))
              for i in range(0, N, group)]
        for i in range(cfg["num_hidden_layers"]):
            w = lw(root, i)
            xs = [layer(x, w) for x in xs]
            del w
        w = hw(root)
        return [head(x, jnp.asarray(rows[j * group:(j + 1) * group]), w)
                for j, x in enumerate(xs)]
