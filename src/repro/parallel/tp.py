"""Tensor-parallel quantized matmul — explicit collectives over the `model`
axis (§VI's many-tile scale-out, across devices instead of BRAMs).

Two partitionings of `quant_matmul`, both bit-exact against the
single-device kernel:

  * **K-sharded (row-parallel)** — each model shard holds a (M, K/n)
    activation slice and the matching (K/n, N) weight rows, runs the BRAMAC
    kernel with *unit scales* so the shard result is the raw int32
    accumulator, and an integer `psum` reduces partial sums across shards
    before a single dequant epilogue.  The cross-device psum plays exactly
    the role of the dummy-array Accumulator row: partials meet in integer
    domain, so blocking/sharding cannot perturb the result.
  * **N-sharded (column-parallel)** — each shard owns full-K columns of the
    weight (and their per-column scales); no reduction is needed and the
    global out_specs concatenation assembles the output.

Exactness caveat (inherent to the kernel's float32 epilogue): integer
accumulators are exact up to 2**24; per-shard partials are smaller than the
single-device accumulator, so any (bits, K) that is exact on one device is
exact sharded.

The physical mesh axis defaults to the active logical-axis rule set in
`parallel.sharding` (`tp` → "model"), so callers that already `activate()`d
a mesh get consistent placement for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops
from repro.parallel import sharding


def _tp_axis(mesh: Mesh, axis: str | None) -> str:
    """Resolve the physical TP axis: explicit arg > active `tp` rule >
    "model"."""
    if axis is not None:
        return axis
    ctx = sharding.active()
    if ctx is not None:
        phys = ctx.rules.get("tp")
        if isinstance(phys, str):
            return phys
    return "model"


def tp_quant_matmul(x_q, w_q, x_scale, w_scale, *, mesh: Mesh,
                    bits_a: int, bits_w: int, axis: str | None = None,
                    partition: str = "k", signed: bool = True,
                    out_dtype=jnp.float32, use_kernel: bool = True):
    """Tensor-parallel (M,K)x(K,N) quantized matmul on `mesh`.

    partition="k": row-parallel with int32 partial-sum psum.
    partition="n": column-parallel, output assembled across shards.
    Inputs are the same logical operands as `ops.quant_matmul`; sharding is
    applied internally via shard_map in_specs, so callers pass full arrays
    (or arrays already placed to match the specs).
    """
    M, K = x_q.shape
    N = w_q.shape[-1]
    ax = _tp_axis(mesh, axis)
    n_shards = mesh.shape[ax]
    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32), (M, 1))
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (1, N))

    if partition == "k":
        if K % n_shards:
            raise ValueError(f"K={K} not divisible by {n_shards}-way "
                             f"'{ax}' axis")
        one = jnp.ones((1, 1), jnp.float32)

        def row_parallel(xq, wq):
            acc = ops.quant_matmul(xq, wq, one, one, bits_a=bits_a,
                                   bits_w=bits_w, signed=signed,
                                   out_dtype=jnp.int32,
                                   use_kernel=use_kernel)
            return jax.lax.psum(acc, ax)

        acc = jax.shard_map(row_parallel, mesh=mesh,
                            in_specs=(P(None, ax), P(ax, None)),
                            out_specs=P(None, None),
                            check_vma=False)(x_q, w_q)
        return (acc.astype(jnp.float32) * xs * ws).astype(out_dtype)

    if partition == "n":
        if N % n_shards:
            raise ValueError(f"N={N} not divisible by {n_shards}-way "
                             f"'{ax}' axis")

        def col_parallel(xq, wq, xsl, wsl):
            return ops.quant_matmul(xq, wq, xsl, wsl, bits_a=bits_a,
                                    bits_w=bits_w, signed=signed,
                                    out_dtype=out_dtype,
                                    use_kernel=use_kernel)

        return jax.shard_map(col_parallel, mesh=mesh,
                             in_specs=(P(None, None), P(None, ax),
                                       P(None, None), P(None, ax)),
                             out_specs=P(None, ax),
                             check_vma=False)(x_q, w_q, xs, ws)

    raise ValueError(f"partition must be 'k' or 'n', got {partition!r}")
