"""Mixture-of-Experts FFN: top-k routing, capacity-based dispatch, EP.

Serving (a forward with a cache) is dropless: an expert receives at most
one assignment per token, so a buffer of T rows per expert, token t in
row t, holds every assignment, and a token's output never depends on
its batch companions.  Every expert runs over all T rows, and the
combine weight is zero where a token did not choose it.  Only an
explicit `per_source` dispatch on a mesh still hands serving to
`ep.ep_moe`'s lossy path.

Training keeps capacity drops: tokens are scattered into a per-expert
capacity buffer (E, C, d) — the Switch-Transformer dispatch — so expert
compute is E·C·(3·d·ff) ≈ the *active* FLOPs (k/E of dense-all-experts),
which keeps the roofline's MODEL_FLOPS/HLO_FLOPs ratio honest.  Tokens
overflowing an expert's capacity are dropped (standard; tests use a
no-drop capacity to check exactness against the dense reference).
Experts are sharded over the `model` mesh axis (expert parallelism); the
scatter/gather lowers to all-to-all-style collectives under pjit.

A serving layer may hold a share of the experts (`cfg.router_experts` >
`cfg.num_experts`): the router scores all `router_experts`, and the layer
computes the part of the result its experts [`first_expert`,
`first_expert + num_experts`) give; assignments to the others add
nothing here (on one chip of an expert-parallel deployment, without the
exchange).  `n_shared_experts` adds an always-on SwiGLU of width
`n_shared_experts * expert_d_ff`; `norm_topk_prob=False` weights the
chosen experts by their raw probabilities.

Router math is f32; a Switch-style load-balancing aux loss is returned.
Named scopes inside `mlp`: `route` (router, top-k, combine), `experts`
(the routed expert matmuls), `shared` (the shared experts).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bramac_linear as bl
from repro.core.quant import QuantizedTensor
from repro.models.layers import he_init, init_mlp, swiglu
from repro.parallel import ep, sharding


def _expert_matmul(x, w):
    """(E,C,a)·(E,a,b)→(E,C,b); takes float or serving-quantized weights.

    Quantized weights route through the expert-parallel shard_map einsum
    whenever a sharding ctx is active and its `expert` axis divides E —
    bit-exact vs the single-device path, so activation is a pure placement
    decision.  Float (training) weights keep the plain einsum: pjit +
    `constrain` already shard it without an explicit collective."""
    if isinstance(w, QuantizedTensor):
        ctx = sharding.active()
        if ctx is not None and ep.shardable(x, ctx):
            return ep.ep_quant_einsum_edf(x, w, mesh=ctx.mesh)
        return bl.serve_einsum_edf(x, w, transpose_out=False)
    return jnp.einsum("ecd,edf->ecf", x, w)


def init_moe(key, cfg):
    d, ff, E = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    dt = cfg.compute_dtype
    p = {
        "router": he_init(ks[0], (d, cfg.n_router_experts), jnp.float32),
        "w_gate": he_init(ks[1], (E, d, ff), dt, fan_in=d),
        "w_up": he_init(ks[2], (E, d, ff), dt, fan_in=d),
        "w_down": he_init(ks[3], (E, ff, d), dt, fan_in=ff),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d,
                               cfg.n_shared_experts * ff, dt)
    return p


def moe_capacity(T: int, E: int, k: int, capacity_factor: float) -> int:
    """Per-expert buffer rows C — the ONE formula every dispatch path
    (dense, EP-global, EP-per-source and its reference) derives from, so
    their drop semantics can only diverge by documented capacity math
    (per-source uses C_src = ceil(C / ep_size) of this same C)."""
    return int(max(1, round(T * k / E * capacity_factor)))


@jax.named_scope("mlp")
def moe(p, x, cfg, capacity_factor: float | None = None,
        dispatch: str | None = None, dropless: bool = False):
    """x: (B, S, d) → (out, aux_loss).

    `dropless` (serving) takes the dropless dispatch of the module
    docstring; otherwise tokens past an expert's capacity are dropped.

    `capacity_factor` / `dispatch` default to `cfg.moe_capacity_factor` /
    `cfg.ep_dispatch` (the knobs Engine and launch/serve.py plumb down).
    dispatch="per_source" hands the WHOLE layer to `ep.ep_moe`'s lossy
    GShard-style path when a sharding ctx is active and can token+expert-
    shard it — forwarding the caller's `capacity_factor`, never ep_moe's
    default (a silent mismatch between the sharded and dense paths,
    regression-tested in tests/test_parallel_ep.py).  Without a ctx (or a
    non-dividing mesh) it falls through to the dense path below, which is
    exactly per-source semantics at ep_size=1 (C_src = C).
    """
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    if dispatch is None:
        dispatch = cfg.ep_dispatch
    if dispatch not in ("global", "per_source"):
        raise ValueError(f"ep_dispatch must be 'global' or 'per_source', "
                         f"got {dispatch!r}")
    if dispatch == "per_source":
        ctx = sharding.active()
        if ctx is not None and ep.layer_shardable(x, cfg, ctx):
            return ep.ep_moe(p, x, cfg, mesh=ctx.mesh,
                             capacity_factor=capacity_factor,
                             dispatch="per_source")

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, d)

    with jax.named_scope("route"):
        logits = xf.astype(jnp.float32) @ p["router"]       # (T,E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)              # (T,k)
        if cfg.norm_topk_prob:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if dropless:
        out = _dropless(p, xf, top_p, top_i, cfg).reshape(B, S, d)
    else:
        if cfg.n_router_experts != E:
            raise ValueError("an expert layer holding a share of the "
                             "experts serves dropless only")
        out = _capacity(p, xf, top_p, top_i, E, k, capacity_factor,
                        cfg).reshape(B, S, d)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            out = out + swiglu(p["shared"], x, cfg.quant)

    # ---- Switch load-balance loss ----
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_i, cfg.n_router_experts, dtype=jnp.float32),
        axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.n_router_experts * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def _dropless(p, xf, top_p, top_i, cfg):
    """(T, d) routed output of the held experts: each runs over all T
    rows (capacity T, token t in row t), combined by the chosen experts'
    weights (zero for a token that did not choose an expert, and for
    experts held elsewhere)."""
    E, T = cfg.num_experts, xf.shape[0]
    with jax.named_scope("route"):
        held = jax.nn.one_hot(top_i - cfg.first_expert, E,
                              dtype=jnp.float32)             # (T,k,E)
        combine = jnp.einsum("tk,tke->et", top_p, held).astype(xf.dtype)
    with jax.named_scope("experts"):
        buf = jnp.broadcast_to(xf[None], (E, T, xf.shape[1]))
        g = _expert_matmul(buf, p["w_gate"])
        u = _expert_matmul(buf, p["w_up"])
        ye = _expert_matmul(jax.nn.silu(g) * u, p["w_down"])
    with jax.named_scope("route"):
        return jnp.einsum("etd,et->td", ye, combine)


def _capacity(p, xf, top_p, top_i, E, k, capacity_factor, cfg):
    """(T, d) output of the capacity dispatch (training): assignments
    past an expert's capacity C are dropped."""
    T, d = xf.shape
    # ---- capacity dispatch ----
    C = moe_capacity(T, E, k, capacity_factor)
    a = top_i.reshape(T * k)                                # assignments
    if cfg.moe_dispatch == "sort":
        pos = _rank_in_expert_sort(a, E)
    else:
        pos = _rank_in_expert_cumsum(a, E)
    keep = pos < C
    pos_c = jnp.where(keep, pos, C - 1)

    xk = jnp.repeat(xf, k, axis=0)                          # (T*k, d)
    buf = jnp.zeros((E, C, d), xf.dtype)
    buf = buf.at[a, pos_c].add(jnp.where(keep[:, None], xk, 0))

    # ---- expert compute (EP: E sharded over `model`) ----
    g = _expert_matmul(buf, p["w_gate"])
    u = _expert_matmul(buf, p["w_up"])
    ye = _expert_matmul(jax.nn.silu(g) * u, p["w_down"])

    # ---- combine ----
    yk = ye[a, pos_c]                                       # (T*k, d)
    w = (top_p.reshape(T * k).astype(xf.dtype)
         * keep.astype(xf.dtype))[:, None]
    return jnp.sum((yk * w).reshape(T, k, d), axis=1)


def _rank_in_expert_cumsum(a: jax.Array, E: int) -> jax.Array:
    """The original one-hot running-count rank (moe_dispatch="cumsum") —
    O(T·k, E) memory; kept as the §Perf baseline and property-tested
    against the sort path in tests/test_moe_routing_properties.py."""
    onehot = jax.nn.one_hot(a, E, dtype=jnp.int32)          # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot          # rank in expert
    return jnp.take_along_axis(pos_in_e, a[:, None], axis=1)[:, 0]


def _rank_in_expert_sort(a: jax.Array, E: int) -> jax.Array:
    """pos[i] = #{j : a[j] == a[i], j before i in expert order}.

    argsort-based: stable-sort assignments, rank within the sorted run of
    each expert (index − expert start offset), scatter ranks back.
    O(n log n) time, O(n) memory — replaces the (T·k, E) one-hot cumsum
    whose reduce-window lowering is quadratic at 32k-token scale (§Perf).
    """
    n = a.shape[0]
    order = jnp.argsort(a, stable=True)                     # expert-major
    sorted_a = a[order]
    counts = jnp.bincount(a, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_a]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    return pos


def moe_reference(p, x, cfg):
    """Dense all-experts reference (exact, no drops) for tests."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    combine = jnp.einsum("bske,bsk->bse",
                         jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                         top_p).astype(x.dtype)
    g = jnp.einsum("bsd,edf->ebsf", x, p["w_gate"])
    u = jnp.einsum("bsd,edf->ebsf", x, p["w_up"])
    ye = jnp.einsum("ebsf,efd->ebsd", jax.nn.silu(g) * u, p["w_down"])
    return jnp.einsum("ebsd,bse->bsd", ye, combine)
