"""Expert-parallel quantized einsum — §VI many-tile scale-out for MoE.

The MoE expert matmul is a batched (E, C, d)·(E, d, f) einsum.  Two ways to
cut it across a mesh, both bit-exact against the single-device serving path
(`bl.serve_einsum_edf`) because every cross-shard reduction happens on the
int32 accumulator before the one dequant epilogue:

  * **partition="e" (expert-parallel)** — each shard owns E/n experts (the
    weight slices `param_specs` already places on the `model` axis) and the
    matching capacity-buffer slices.  Expert compute is embarrassingly
    parallel: no reduction at all, so exactness is structural.
  * **partition="d" (contraction-parallel)** — the TP analogue: activations
    are quantized *globally* (per (e,c) row over the full d), each shard
    multiplies its d-slice with `bl.edf_accumulate` (unit-scale int32 mode),
    and an integer `psum` joins the partials — the dummy-array Accumulator
    row across devices.

Both compose with a `dp_axis` that additionally shards the capacity axis C
(rows are independent), giving DP×EP / DP×TP meshes.

`ep_moe` is the full expert-parallel MoE layer (tokens AND experts sharded
over the EP axis) with two token-dispatch modes:

  * **dispatch="global"** — exact: the *global* rank-in-expert is recovered
    from an all-gathered per-shard count scan, every source scatters into
    full (E, C, d) capacity buffers that an `all_to_all` delivers and the
    owner sums, and the combine `all_gather`s the expert outputs (every
    source token may need any owner's rows at global capacity).  Bit-exact
    vs the single-device `moe()` — drops included.
  * **dispatch="per_source"** — the GShard-style lossy fast path: each
    source packs at most `C_src = ceil(C / n)` tokens per destination
    expert into fixed-size buffers (token values + an int32 sidecar of
    expert ids / source ranks / validity), one `all_to_all` delivers them,
    experts compute on the concatenated per-source rows, and a *mirrored*
    per-source-capacity `all_to_all` scatters results straight back to
    their sources.  No count scan and no all-gather, so per-device dispatch
    volume drops from O(E·C) to O(E·C/n) — at the cost of over-capacity
    drops decided purely shard-locally.

    Tie-break semantics (load-bearing for the property tests): within a
    shard, capacity is granted in (token, k-slot) order via the stable
    argsort rank — earlier assignments win; a token is dropped iff its
    *shard-local* rank-in-expert ≥ C_src.  Global occupancy never causes
    drops, so the drop mask of shard s depends only on shard s's tokens.
    `per_source_reference` replays exactly this rule on one device, which
    makes the lossy path testable bit-exactly (values AND drop mask).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bramac_linear as bl
from repro.core import quant
from repro.parallel import sharding


def _ep_axis(mesh: Mesh, axis: str | None) -> str:
    """Resolve the physical EP axis: explicit arg > active `expert` rule >
    "model"."""
    if axis is not None:
        return axis
    ctx = sharding.active()
    if ctx is not None:
        phys = ctx.phys_axis("expert")
        if phys is not None:
            return phys
    return "model"


def shardable(x: jax.Array, ctx=None) -> bool:
    """True when the active (or given) sharding ctx can expert-shard an
    (E, C, d) buffer: a string `expert` rule whose mesh axis divides E."""
    ctx = ctx or sharding.active()
    if ctx is None:
        return False
    phys = ctx.phys_axis("expert")
    if phys is None:
        return False
    return x.shape[0] % ctx.mesh.shape[phys] == 0


def layer_shardable(x: jax.Array, cfg, ctx=None) -> bool:
    """True when the full `ep_moe` layer can run under the active (or
    given) ctx for a (B, S, d) input: the `expert` rule resolves to one
    mesh axis whose size divides both E and T = B·S (tokens and experts
    are both sharded over it)."""
    ctx = ctx or sharding.active()
    if ctx is None:
        return False
    phys = ctx.phys_axis("expert")
    if phys is None:
        return False
    n = ctx.mesh.shape[phys]
    B, S = x.shape[0], x.shape[1]
    return cfg.num_experts % n == 0 and (B * S) % n == 0


def _dequant(acc, x_scale, w_scale, dtype):
    """The single dequant epilogue all partitionings funnel into."""
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(dtype)


def ep_quant_einsum_edf(x: jax.Array, qw: quant.QuantizedTensor, *,
                        mesh: Mesh, axis: str | None = None,
                        partition: str = "e", bits_a: int = 8,
                        dp_axis: str | None = None) -> jax.Array:
    """Sharded quantized expert einsum "ecd,edf->ecf" on `mesh`.

    partition="e": experts sharded (EP), no reduction.
    partition="d": contraction sharded (TP) with int32 partial-sum psum.
    dp_axis: optionally also shard the capacity axis C (DP composition).
    Same logical operands as `bl.serve_einsum_edf`; sharding is applied via
    shard_map in_specs, so callers pass full (or pre-placed) arrays.
    """
    E, C, d = x.shape
    ax = _ep_axis(mesh, axis)
    n = mesh.shape[ax]
    wv = qw.unpacked_values()                               # (E, d|f, f|d)
    ws = jnp.broadcast_to(qw.scale, (E, 1, wv.shape[-1]))
    if dp_axis is not None and C % mesh.shape[dp_axis]:
        raise ValueError(f"C={C} not divisible by {mesh.shape[dp_axis]}-way "
                         f"'{dp_axis}' axis")

    if partition == "e":
        if E % n:
            raise ValueError(f"E={E} not divisible by {n}-way '{ax}' axis")

        def expert_parallel(xb, wvb, wsb):
            # local experts only: per-row activation quantization and the
            # int32 accumulator are untouched by the split — structural
            # bit-exactness.
            qx = quant.quantize(xb, bits_a, axis=-1)
            return _dequant(bl.edf_accumulate(qx.values, wvb),
                            qx.scale, wsb, x.dtype)

        return jax.shard_map(expert_parallel, mesh=mesh,
                             in_specs=(P(ax, dp_axis, None),
                                       P(ax, None, None), P(ax, None, None)),
                             out_specs=P(ax, dp_axis, None),
                             check_vma=False)(x, wv, ws)

    if partition == "d":
        if wv.shape[1] % n:
            raise ValueError(f"d={wv.shape[1]} not divisible by {n}-way "
                             f"'{ax}' axis")
        # quantize with full-row scales BEFORE sharding the contraction, so
        # shard partials are raw int32 (unit-scale mode) and psum is exact.
        qx = quant.quantize(x, bits_a, axis=-1)

        def contraction_parallel(xv, wvb):
            return jax.lax.psum(bl.edf_accumulate(xv, wvb), ax)

        acc = jax.shard_map(contraction_parallel, mesh=mesh,
                            in_specs=(P(None, dp_axis, ax), P(None, ax, None)),
                            out_specs=P(None, dp_axis, None),
                            check_vma=False)(qx.values, wv)
        return _dequant(acc, qx.scale, ws, x.dtype)

    raise ValueError(f"partition must be 'e' or 'd', got {partition!r}")


# ---------------------------------------------------------------------------
# Full expert-parallel MoE layer
# ---------------------------------------------------------------------------

def _moe_weights(p, E):
    """(quantized, flat weight list) shared by `ep_moe` and the reference.

    Quantized leaves are unpacked once outside the shard_map so in_specs
    can slice them; scales are broadcast to (E, 1, f) for the same reason.
    """
    quantized = isinstance(p["w_gate"], quant.QuantizedTensor)
    if quantized:
        def unpack(qw):
            wv = qw.unpacked_values()
            return wv, jnp.broadcast_to(qw.scale, (E, 1, wv.shape[-1]))
        weights = [a for name in ("w_gate", "w_up", "w_down")
                   for a in unpack(p[name])]
    else:
        weights = [p["w_gate"], p["w_up"], p["w_down"]]
    return quantized, weights


def _expert_ffn(buf, weights, quantized, bits_a):
    """gate/up/silu/down on an (E', C', d) buffer — the one expert-compute
    body every dispatch mode and the reference funnel through, so their
    bit-exactness is structural (activation quantization is per row)."""
    if quantized:
        gv, gs, uv, us, dv, ds = weights

        def mm(xb, wv, ws):
            qx = quant.quantize(xb, bits_a, axis=-1)
            return _dequant(bl.edf_accumulate(qx.values, wv),
                            qx.scale, ws, xb.dtype)

        g, u = mm(buf, gv, gs), mm(buf, uv, us)
        return mm(jax.nn.silu(g) * u, dv, ds)
    gv, uv, dv = weights
    g = jnp.einsum("ecd,edf->ecf", buf, gv)
    u = jnp.einsum("ecd,edf->ecf", buf, uv)
    return jnp.einsum("ecd,edf->ecf", jax.nn.silu(g) * u, dv)


def ep_moe(p, x, cfg, *, mesh: Mesh, axis: str | None = None,
           capacity_factor: float | None = None, bits_a: int = 8,
           dispatch: str = "global", return_drops: bool = False):
    """Expert-parallel `models.moe.moe`: x (B, S, d) → (out, aux_loss).

    Tokens AND experts are sharded over the EP axis; `dispatch` selects the
    token movement (see the module docstring):

      * "global"     — exact global-capacity buffers: all-gathered count
        scan for the global rank-in-expert, all_to_all dispatch summed at
        the owner, all_gather combine.  Bit-exact vs single-device `moe()`.
      * "per_source" — GShard-style per-source capacity C_src = ceil(C/n):
        purely local ranks, one all_to_all out and a mirrored all_to_all
        back, no gather.  Lossy (shard-local over-capacity drops);
        bit-exact vs `per_source_reference` — drop mask included.

    Weights may be float or serving-quantized (`QuantizedTensor`) — the
    quantized path is bit-exact vs single-device `moe()` for 2/4/8-bit.
    With `return_drops=True` a third output gives the (T, k) keep mask
    (shard-major token order), for drop accounting and the parity tests.
    `capacity_factor=None` resolves to `cfg.moe_capacity_factor`, so a
    direct ep_moe call can never silently disagree with the dense path.
    """
    from repro.models.moe import _rank_in_expert_sort, moe_capacity

    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    ax = _ep_axis(mesh, axis)
    n = mesh.shape[ax]
    if E % n or T % n:
        raise ValueError(f"E={E} and T={T} must divide the {n}-way "
                         f"'{ax}' axis")
    if dispatch not in ("global", "per_source"):
        raise ValueError(f"dispatch must be 'global' or 'per_source', "
                         f"got {dispatch!r}")
    C = moe_capacity(T, E, k, capacity_factor)
    Cs = -(-C // n)                                         # ceil(C / n)
    El = E // n
    xf = x.reshape(T, d)

    quantized, weights = _moe_weights(p, E)
    w_specs = (P(ax, None, None),) * len(weights)

    def shard_fn(xl, router, *w):
        Tl = xl.shape[0]
        logits = xl.astype(jnp.float32) @ router            # (Tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        a = top_i.reshape(Tl * k)
        xk = jnp.repeat(xl, k, axis=0)                      # (Tl*k, d)

        if dispatch == "global":
            # ---- global capacity dispatch from local routing ----
            counts = jnp.bincount(a, length=E)
            all_counts = jax.lax.all_gather(counts, ax)     # (n, E)
            me = jax.lax.axis_index(ax)
            before = jnp.sum(jnp.where(jnp.arange(n)[:, None] < me,
                                       all_counts, 0), axis=0)  # (E,)
            pos = _rank_in_expert_sort(a, E) + before[a]    # global rank
            keep = pos < C
            pos_c = jnp.where(keep, pos, C - 1)

            buf = jnp.zeros((E, C, d), x.dtype)
            buf = buf.at[a, pos_c].add(jnp.where(keep[:, None], xk, 0))
            # dispatch: chunk e' of `buf` is this shard's contribution to
            # the experts shard e' owns — all_to_all delivers, owner sums
            # sources (dropped tokens were zeroed above, so the sum is
            # drop-exact).
            buf = jax.lax.all_to_all(buf.reshape(n, El, C, d), ax,
                                     split_axis=0, concat_axis=0)
            buf = jnp.sum(buf, axis=0)                      # (El, C, d)

            ye = _expert_ffn(buf, w, quantized, bits_a)     # (El, C, d)

            # combine: the gather half of the scatter-gather — every source
            # needs every owner's rows (owner order == axis order, matching
            # the single-device buffer layout).
            ye = jax.lax.all_gather(ye, ax, axis=0, tiled=True)  # (E, C, d)
            yk = ye[a, pos_c]                               # (Tl*k, d)
        else:
            # ---- per-source capacity dispatch (GShard lossy path) ----
            # ranks are purely LOCAL: no count scan, no gather.  Capacity
            # is granted in (token, k-slot) order (stable argsort), and a
            # token is dropped iff its shard-local rank ≥ C_src.
            pos = _rank_in_expert_sort(a, E)
            keep = pos < Cs
            pos_c = jnp.where(keep, pos, Cs - 1)

            buf = jnp.zeros((E, Cs, d), x.dtype)
            buf = buf.at[a, pos_c].add(jnp.where(keep[:, None], xk, 0))
            # int32 sidecar rides the same scatter: (expert id, source
            # rank, valid) — the GShard packed-buffer format, where routing
            # metadata travels WITH the values so the owner never has to
            # reconstruct it from global state.  Kept (a, pos_c) pairs are
            # unique, so add==set; dropped assignments add zeros.
            meta = jnp.zeros((E, Cs, 3), jnp.int32)
            meta = meta.at[a, pos_c].add(
                jnp.where(keep[:, None],
                          jnp.stack([a, pos, jnp.ones_like(a)], axis=-1),
                          0))
            # one all_to_all each way: chunk e' of `buf` goes to the shard
            # owning experts e' — received rows stay source-major, so the
            # owner concatenates instead of summing.
            buf = jax.lax.all_to_all(buf.reshape(n, El, Cs, d), ax,
                                     split_axis=0, concat_axis=0)
            meta = jax.lax.all_to_all(meta.reshape(n, El, Cs, 3), ax,
                                      split_axis=0, concat_axis=0)
            # validity mask enforces the "only packed rows contribute"
            # contract (unwritten rows are already zero, so this is a
            # bit-exact no-op — kept as the invariant, not for values).
            buf = jnp.where(meta[..., 2:3] > 0, buf, 0)     # (n, El, Cs, d)
            buf = buf.transpose(1, 0, 2, 3).reshape(El, n * Cs, d)

            ye = _expert_ffn(buf, w, quantized, bits_a)     # (El, n*Cs, d)

            # mirrored combine: owner o's rows for source s go straight
            # back to shard s; received chunks are owner-major, which IS
            # the global expert order.
            ye = ye.reshape(El, n, Cs, d).transpose(1, 0, 2, 3)
            ye = jax.lax.all_to_all(ye, ax, split_axis=0, concat_axis=0)
            yk = ye.reshape(E, Cs, d)[a, pos_c]             # (Tl*k, d)

        w_tok = (top_p.reshape(Tl * k).astype(x.dtype)
                 * keep.astype(x.dtype))[:, None]
        out = jnp.sum((yk * w_tok).reshape(Tl, k, d), axis=1)

        # ---- Switch load-balance loss (psum'd partial sums) ----
        frac_tokens = jax.lax.psum(
            jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                    axis=(0, 1)), ax) / (T * k)
        frac_probs = jax.lax.psum(jnp.sum(probs, axis=0), ax) / T
        aux = E * jnp.sum(frac_tokens * frac_probs)
        if return_drops:
            return out, aux, keep.reshape(Tl, k)
        return out, aux

    out_specs = (P(ax, None), P())
    if return_drops:
        out_specs += (P(ax, None),)
    res = jax.shard_map(shard_fn, mesh=mesh,
                        in_specs=(P(ax, None), P(None, None), *w_specs),
                        out_specs=out_specs,
                        check_vma=False)(xf, p["router"], *weights)
    if return_drops:
        out, aux, keep = res
        return out.reshape(B, S, d), aux, keep
    out, aux = res
    return out.reshape(B, S, d), aux


def per_source_reference(p, x, cfg, *, ep_size: int,
                         capacity_factor: float | None = None,
                         bits_a: int = 8):
    """Single-device pure-JAX simulator of `ep_moe(dispatch="per_source")`.

    Replays the exact shard decomposition an `ep_size`-way EP axis would
    induce — tokens in shard-major blocks, shard-local stable-argsort
    ranks, C_src = ceil(C / ep_size) drops — and runs the identical
    `_expert_ffn` body on identically-ordered buffers, so outputs AND the
    drop mask match the distributed path bit for bit.  This is what makes
    the lossy path testable without a mesh.

    Returns (out (B,S,d), aux_loss, keep (T,k) bool in shard-major order).
    """
    from repro.models.moe import _rank_in_expert_sort, moe_capacity

    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    n = ep_size
    if E % n or T % n:
        raise ValueError(f"E={E} and T={T} must divide ep_size={n}")
    C = moe_capacity(T, E, k, capacity_factor)
    Cs = -(-C // n)
    Tl = T // n
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ p["router"]           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # shard-local ranks: each shard-major block of Tl*k assignments is
    # ranked independently — exactly shard_fn's local argsort.
    a = top_i.reshape(n, Tl * k)
    pos = jax.vmap(lambda v: _rank_in_expert_sort(v, E))(a)
    keep = pos < Cs
    pos_c = jnp.where(keep, pos, Cs - 1)

    xk = jnp.repeat(xf, k, axis=0).reshape(n, Tl * k, d)
    buf = jax.vmap(lambda ai, pi, xi, ki:
                   jnp.zeros((E, Cs, d), x.dtype).at[ai, pi].add(
                       jnp.where(ki[:, None], xi, 0)))(a, pos_c, xk, keep)
    # (n, E, Cs, d) → source-major rows per expert, the owners' concat order
    buf = buf.transpose(1, 0, 2, 3).reshape(E, n * Cs, d)

    quantized, weights = _moe_weights(p, E)
    ye = _expert_ffn(buf, weights, quantized, bits_a)       # (E, n*Cs, d)

    ybuf = ye.reshape(E, n, Cs, d).transpose(1, 0, 2, 3)    # (n, E, Cs, d)
    yk = jax.vmap(lambda yb, ai, pi: yb[ai, pi])(ybuf, a, pos_c)
    w_tok = (top_p.reshape(n, Tl * k).astype(x.dtype)
             * keep.astype(x.dtype))[..., None]
    out = jnp.sum((yk * w_tok).reshape(n, Tl, k, d), axis=2)

    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out.reshape(B, S, d), aux, keep.reshape(T, k)
