"""Plain float32 reference of DeepSeek-V2(-Lite) (arXiv:2405.04434; HF
`modeling_deepseek.py`), independent of the program.

It imports nothing of the program and takes nothing the program made:
its weights come from `weights.draw` with the seed, leaf by leaf, under
the names the configuration file's `"leaves"` give the program's leaves.
Per layer i, with n1/n2 RMSNorms:

  attention (MLA, no query LoRA): q = Wq·n1(x) split per head into
    q_nope (128) and q_pe (64); c = kv_norm(Wdkv·n1(x)) (512),
    k_pe = Wkr·n1(x) (64, one for all heads); k_nope = Wuk·c and
    v = Wuv·c per head (the un-absorbed form); q_pe and k_pe rotated as
    `apply_rotary_pos_emb` does: each head's 64 dims regrouped from
    pairs (2j, 2j+1) into halves, then `rotate_half` at YaRN
    frequencies (`rope_scaling`); scores (q_nope·k_nope + q_pe·k_pe) x
    (nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2, causal
    softmax in float32; x += Wo·(p·v).
  FFN: layers below `first_k_dense_replace` a SwiGLU of
    `intermediate_size`; the others a softmax router over every routed
    expert (its width is the published count), top `num_experts_per_tok`
    by probability, weights renormalised only with `norm_topk_prob`,
    times `routed_scaling_factor`, plus the shared experts, one SwiGLU
    of `n_shared_experts * moe_intermediate_size`.
  head: the final RMSNorm and an untied unembedding.

The expert share.  The file's `n_routed_experts` is the number of
experts held here, `first_expert` the first of them, and the router
scores `published.n_routed_experts`; the routed sum runs over the held
experts only, as the program's does on one chip of the deployment.
With the whole count held this is the model's own layer.

Departures from HF: the weights are random; every position is scored in
float32 at `HIGHEST` (HF keeps cos/sin in the model's dtype); the
training-time auxiliary losses and the grouped top-k (`n_group` 1,
`topk_group` 1, `topk_method` "greedy" are plain top-k) are not
modelled.  Layer indices: the leading dense layer i draws its own names
(`dense{i}_...`) at layer 0; MoE layer first_k_dense_replace + j draws
at layer j, as the program's stacked row j under `layers.pos0`.

`quant=(bits_w, bits_a)` computes the same model one precision lower
(the control of `correct.py`): every linear but the router is quantized
per output channel to bits_w and its activations per row to bits_a.
Work is done layer by layer over groups of sequences; the contract is
in `reference.py`'s docstring.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights as W
from perfbench.reference import HI, _mm, _prepare, _rms

Q_CHUNK = 128
SWIGLU = ("w_gate", "w_up", "w_down")


def dims(cfg: dict) -> dict:
    """The scalars the reference computes with, from the file."""
    if cfg.get("q_lora_rank"):
        raise ValueError("this reference states MLA without query LoRA")
    if cfg.get("weights", "bf16") != "bf16":
        raise ValueError("this reference states weights of the model's "
                         "dtype only")
    rs = cfg["rope_scaling"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "L0": cfg["first_k_dense_replace"],
            "ff": cfg["intermediate_size"],
            "eff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "k": cfg["num_experts_per_tok"],
            "held": cfg["n_routed_experts"],
            "first": cfg.get("first_expert", 0),
            "router": cfg.get("published", {}).get("n_routed_experts",
                                                   cfg["n_routed_experts"]),
            "renorm": bool(cfg["norm_topk_prob"]),
            "rsf": float(cfg["routed_scaling_factor"]),
            "kvr": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "factor": float(rs["factor"]),
            "original": rs["original_max_position_embeddings"],
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "mscale": float(rs["mscale"]),
            "mscale_all_dim": float(rs["mscale_all_dim"]),
            "dtype": cfg["dtype"]}


def mla_shapes(m: dict) -> dict:
    d, H = m["d"], m["H"]
    return {"mla_q": (d, H * (m["nope"] + m["rope"])),
            "mla_kv_down": (d, m["kvr"]), "mla_k_rope": (d, m["rope"]),
            "mla_k_up": (m["kvr"], H * m["nope"]),
            "mla_v_up": (m["kvr"], H * m["vd"]),
            "mla_out": (H * m["vd"], d)}


def swiglu_shapes(d: int, ff: int) -> dict:
    return {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


# ---- YaRN, as DeepseekV2YarnRotaryEmbedding computes it ----

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_correction_range(m: dict) -> tuple[int, int]:
    dim = m["rope"]

    def find_dim(rotations):
        return dim * math.log(m["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(m["theta"]))
    return max(math.floor(find_dim(m["beta_fast"])), 0), \
        min(math.ceil(find_dim(m["beta_slow"])), dim - 1)


def yarn_inv_freq(m: dict) -> np.ndarray:
    dim = m["rope"]
    pos = m["theta"] ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    freq_extra = (1.0 / pos).astype(np.float32)
    freq_inter = (1.0 / (m["factor"] * pos)).astype(np.float32)
    low, high = yarn_correction_range(m)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def softmax_scale(m: dict) -> float:
    s = (m["nope"] + m["rope"]) ** -0.5
    if m["mscale_all_dim"]:
        ms = yarn_get_mscale(m["factor"], m["mscale_all_dim"])
        s = s * ms * ms
    return s


def _rope(x, m):
    """x (G, S, heads, rope) at positions 0..S-1: HF's pair regrouping,
    then rotate_half."""
    G, S, h, dd = x.shape
    x = x.reshape(G, S, h, dd // 2, 2).swapaxes(-1, -2).reshape(G, S, h, dd)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(m))[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)            # (S, dd)
    cs = yarn_get_mscale(m["factor"], m["mscale"]) \
        / yarn_get_mscale(m["factor"], m["mscale_all_dim"])
    cos = (jnp.cos(emb) * cs)[None, :, None]
    sin = (jnp.sin(emb) * cs)[None, :, None]
    rot = jnp.concatenate([-x[..., dd // 2:], x[..., :dd // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(q_nope, q_pe, k_nope, k_pe, v, scale):
    """Causal attention over query chunks.  q/k_nope (G,S,H,nope), q_pe
    (G,S,H,rope), k_pe (G,S,1,rope), v (G,S,H,vd) -> (G,S,H*vd)."""
    G, S, H, _ = q_nope.shape
    n = S // Q_CHUNK

    def chunk(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * Q_CHUNK, Q_CHUNK, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * Q_CHUNK, Q_CHUNK, 1)
        s = jnp.einsum("gqhd,gkhd->ghqk", qn, k_nope, precision=HI) \
            + jnp.einsum("gqhd,gkd->ghqk", qp, k_pe[:, :, 0], precision=HI)
        s = s * scale
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ghqk,gkhd->gqhd", p, v, precision=HI)

    out = jax.lax.map(chunk, jnp.arange(n))                   # (n,G,C,H,vd)
    return out.transpose(1, 0, 2, 3, 4).reshape(G, S, -1)


# ---- weights ----

def _draw(root, name, layer, shape, kind, m, dtype=None):
    return W.draw(root, name, layer, shape, kind,
                  dtype or jnp.dtype(m["dtype"]))


def _mla_weights(root, prefix, layer, m, quant):
    out = {"n1": _draw(root, prefix + "attn_norm", layer, (m["d"],),
                       "norm", m),
           "n2": _draw(root, prefix + "mlp_norm", layer, (m["d"],), "norm",
                       m),
           "kv_norm": _draw(root, prefix + "mla_kv_norm", layer,
                            (m["kvr"],), "norm", m)}
    for name, shape in mla_shapes(m).items():
        out[name] = _prepare(_draw(root, prefix + name, layer, shape,
                                   "matrix", m), quant)
    return out


def _dense_weights(m, quant, root, i):
    """Leading dense layer i: its own names, drawn at layer 0."""
    p = f"dense{i}_"
    out = _mla_weights(root, p, 0, m, quant)
    for name, shape in swiglu_shapes(m["d"], m["ff"]).items():
        out[name] = _prepare(_draw(root, p + name, 0, shape, "matrix", m),
                             quant)
    return out


def _moe_weights(m, quant, root, j):
    """MoE layer L0 + j, drawn at layer j."""
    out = _mla_weights(root, "", j, m, quant)
    out["router"] = _draw(root, "router", j, (m["d"], m["router"]),
                          "matrix", m, jnp.float32)
    for name, shape in swiglu_shapes(m["d"], m["eff"]).items():
        out["expert_" + name] = _prepare(_draw(
            root, "expert_" + name, j, (m["held"],) + shape, "matrix", m),
            quant)
    for name, shape in swiglu_shapes(m["d"],
                                     m["shared"] * m["eff"]).items():
        out["shared_" + name] = _prepare(_draw(
            root, "shared_" + name, j, shape, "matrix", m), quant)
    return out


# ---- layers ----

def _swiglu(h, wg, wu, wd, ba):
    return _mm(jax.nn.silu(_mm(h, wg, ba)) * _mm(h, wu, ba), wd, ba)


def _mla(m, ba, x, w):
    G, S, _ = x.shape
    H, nope, rope = m["H"], m["nope"], m["rope"]
    h = _rms(x, w["n1"], m["eps"])
    q = _mm(h, w["mla_q"], ba).reshape(G, S, H, nope + rope)
    c = _rms(_mm(h, w["mla_kv_down"], ba), w["kv_norm"], m["eps"])
    k_pe = _mm(h, w["mla_k_rope"], ba)[:, :, None, :]
    k_nope = _mm(c, w["mla_k_up"], ba).reshape(G, S, H, nope)
    v = _mm(c, w["mla_v_up"], ba).reshape(G, S, H, m["vd"])
    out = _attention(q[..., :nope], _rope(q[..., nope:], m), k_nope,
                     _rope(k_pe, m), v, softmax_scale(m))
    return x + _mm(out, w["mla_out"], ba)


def _dense_layer(m, quant, x, w):
    ba = None if quant is None else quant[1]
    x = _mla(m, ba, x, w)
    h = _rms(x, w["n2"], m["eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], ba)


def routed(m, h, router, experts, ba=None):
    """The held experts' part of the routed sum at h (..., d); `experts`
    maps w_gate/w_up/w_down to (held, ...) weights (or int8 pairs)."""
    logits = jnp.dot(h, router.astype(jnp.float32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m["k"])
    if m["renorm"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * m["rsf"]
    weight = jnp.einsum("...k,...ke->e...", top_p, jax.nn.one_hot(
        top_i - m["first"], m["held"], dtype=jnp.float32))

    def one(acc, e):
        def pick(w):
            return jax.tree_util.tree_map(lambda a: a[e], w)
        y = _swiglu(h, pick(experts["w_gate"]), pick(experts["w_up"]),
                    pick(experts["w_down"]), ba)
        return acc + weight[e][..., None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(m["held"]))
    return acc


def _moe_layer(m, quant, x, w):
    ba = None if quant is None else quant[1]
    x = _mla(m, ba, x, w)
    h = _rms(x, w["n2"], m["eps"])
    experts = {n: w["expert_" + n] for n in SWIGLU}
    shared = _swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                     w["shared_w_down"], ba)
    return x + routed(m, h, w["router"], experts, ba) + shared


def _head_weights(m, quant, root):
    return {"norm": _draw(root, "final_norm", 0, (m["d"],), "norm", m),
            "unembed": _prepare(_draw(root, "unembed", 0, (m["d"], m["V"]),
                                      "matrix", m), quant)}


def _head(m, quant, x, rows, w):
    """Logits (G, R, V) at hidden rows `rows` (G, R)."""
    h = jnp.take_along_axis(x, rows[..., None], axis=1)
    h = _rms(h, w["norm"], m["eps"])
    return _mm(h, w["unembed"], None if quant is None else quant[1])


@functools.lru_cache(maxsize=None)
def _programs(items: tuple, quant):
    m = dict(items)
    embed = jax.jit(lambda root, toks: jnp.take(
        _draw(root, "embedding", 0, (m["V"], m["d"]), "embed", m), toks,
        axis=0).astype(jnp.float32))
    part = functools.partial
    return (embed,
            jax.jit(part(_dense_weights, m, quant), static_argnums=1),
            jax.jit(part(_dense_layer, m, quant)),
            jax.jit(part(_moe_weights, m, quant)),
            jax.jit(part(_moe_layer, m, quant)),
            jax.jit(part(_head_weights, m, quant)),
            jax.jit(part(_head, m, quant)))


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              quant: tuple | None = None, group: int = 4) -> list:
    """Reference logits at chosen positions, as `reference.logits_at`:
    tokens (N, S) int32 right-padded, S a multiple of `Q_CHUNK`; rows
    (N, R).  Returns one (g, R, V) f32 device array per group."""
    m = dims(cfg)
    N, S = tokens.shape
    if S % Q_CHUNK:
        raise ValueError(f"sequence length {S} is not a multiple of "
                         f"{Q_CHUNK}")
    embed, dw, dense, mw, moe, hw, head = _programs(
        tuple(sorted(m.items())), quant)
    root = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        xs = [embed(root, jnp.asarray(tokens[i:i + group]))
              for i in range(0, N, group)]
        for i in range(m["L"]):
            if i < m["L0"]:
                w = dw(root, i)
                xs = [dense(x, w) for x in xs]
            else:
                w = mw(root, i - m["L0"])
                xs = [moe(x, w) for x in xs]
            del w
        w = hw(root)
        return [head(x, jnp.asarray(rows[j * group:(j + 1) * group]), w)
                for j, x in enumerate(xs)]
