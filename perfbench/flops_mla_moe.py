"""Model FLOPs of a DeepSeek-V2-style step (MLA, a leading dense layer,
routed and shared experts), from the configuration file's own keys.

A token costs 2 FLOPs per weight it touches: every layer's MLA
projections (query, latent down, rope key, the key and value
up-projections of its own latent, output), the dense layers' SwiGLU, and
in each MoE layer the router, the shared experts and the expected share
of its routed experts held here (`num_experts_per_tok` x held /
published experts, each a SwiGLU of `moe_intermediate_size`); attention
over its context counts once per attended position and head, at the
query-key width (nope + rope) and the value width; the unembedding
counts where the token's logits are used.  These are the model's FLOPs,
not the program's: the un-absorbed form's up-projection of the whole
cache view at every step is not in them.
"""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kvr = cfg["kv_lora_rank"]
    held = cfg["n_routed_experts"]
    router = cfg.get("published", {}).get("n_routed_experts", held)
    eff = cfg["moe_intermediate_size"]
    mla = d * H * (nope + rope) + d * (kvr + rope) \
        + kvr * H * (nope + vd) + H * vd * d
    return {"L": cfg["num_hidden_layers"], "L0": cfg["first_k_dense_replace"],
            "d": d, "H": H, "qk": nope + rope, "vd": vd, "V": cfg["vocab_size"],
            "mla": mla, "dense_ff": 3 * d * cfg["intermediate_size"],
            "moe": d * router + 3 * d * eff * cfg["n_shared_experts"]
            + 3 * d * eff * cfg["num_experts_per_tok"] * held / router}


def linear_weights(cfg: dict) -> float:
    """Weights one token touches in all layers (expected, for the routed
    experts), without the unembedding."""
    m = _dims(cfg)
    return m["L"] * m["mla"] + m["L0"] * m["dense_ff"] \
        + (m["L"] - m["L0"]) * m["moe"]


def token_flops(cfg: dict, context: int, logits: bool) -> float:
    """One token that attends `context` positions (itself included)."""
    m = _dims(cfg)
    attn = 2 * m["H"] * (m["qk"] + m["vd"]) * context
    f = 2 * linear_weights(cfg) + m["L"] * attn
    return f + (2 * m["d"] * m["V"] if logits else 0)


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of n tokens, prefilled from position 0; logits only for
    the last row."""
    m = _dims(cfg)
    attn = 2 * m["H"] * (m["qk"] + m["vd"]) * n * (n + 1) // 2
    return 2 * linear_weights(cfg) * n + m["L"] * attn \
        + 2 * m["d"] * m["V"]
