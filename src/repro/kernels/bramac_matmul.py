"""BRAMAC production kernel: radix-4 bit-plane quantized matmul (Pallas/TPU).

TPU-native adaptation of BRAMAC's hybrid bit-serial & bit-parallel dataflow
(DESIGN.md §2):

  * the quantized weight tile (bk × bn, int8) is DMA'd HBM→VMEM and stays
    *resident* while activation digits stream through it — the "dummy array";
  * activations are consumed two bits per pass (radix-4 digits — the MAC2
    bit-pair {I2[i], I1[i]}), so 2/4/8-bit activations need 1/2/4 MXU passes;
  * the int32 VMEM accumulator plays the role of the dummy array's
    P/Accumulator rows: digit passes shift-accumulate in place;
  * the top digit of signed activations is accumulated with negative weight —
    Algorithm 1 line 5's inverter-row subtraction;
  * the Pallas grid pipeline double-buffers the next weight tile copy behind
    the current tile's compute — the eFSM overlap of Fig 5 that frees the
    "main BRAM" (HBM) for the rest of the system.

Weights enter as int8 holding n-bit values; scales are applied in a fused
epilogue on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import num_digits

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bk, bn) — MXU-aligned


def _digits(u: jax.Array, j: int, nd: int, signed: bool) -> jax.Array:
    d = (u >> (2 * j)) & 0x3
    if signed and j == nd - 1:
        d = jnp.where(d >= 2, d - 4, d)
    return d.astype(jnp.int8)


def _kernel(x_ref, w_ref, xs_ref, ws_ref, out_ref, acc_ref, *,
            bits_a: int, signed: bool, n_k: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, bk) int8 n-bit vals
    u = x.astype(jnp.int32) & ((1 << bits_a) - 1)   # unsigned bit view
    w = w_ref[...]

    nd = num_digits(bits_a)
    acc = acc_ref[...]
    for j in range(nd):                              # bit-serial digit passes
        d = _digits(u, j, nd, signed)
        part = jax.lax.dot_general(                  # bit-parallel MXU pass
            d, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + part * (4 ** j)                  # shift-accumulate (P<<2)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _epilogue():                                 # fused dequant epilogue
        r = acc_ref[...].astype(jnp.float32)
        out_ref[...] = (r * xs_ref[...] * ws_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits_a", "bits_w", "signed", "block", "out_dtype",
                     "interpret"))
def bramac_matmul(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                  w_scale: jax.Array, *, bits_a: int, bits_w: int,
                  signed: bool = True, block=DEFAULT_BLOCK,
                  out_dtype=jnp.float32,
                  interpret: bool = False) -> jax.Array:
    """Quantized matmul  (M,K)·(K,N) → (M,N) via the BRAMAC dataflow.

    x_q:     (M, K) int8 holding bits_a-bit values.
    w_q:     (K, N) int8 holding bits_w-bit values.
    x_scale: (M, 1) or (1, 1) f32 per-row activation scales.
    w_scale: (1, N) or (1, 1) f32 per-column weight scales.
    """
    bm, bk, bn = block
    K = x_q.shape[1]
    M = x_q.shape[0]
    N = w_q.shape[-1]
    if M % bm or K % bk or N % bn:
        raise ValueError(f"shape ({M},{K},{N}) not divisible by block {block}")
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    xs = jnp.broadcast_to(x_scale.astype(jnp.float32), (M, 1))
    ws = jnp.broadcast_to(w_scale.astype(jnp.float32), (1, N))

    kernel = functools.partial(
        _kernel, bits_a=bits_a, signed=signed, n_k=n_k, out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # activations
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # weights
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),     # x scales
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),     # w scales
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],      # the dummy array
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, xs, ws)
