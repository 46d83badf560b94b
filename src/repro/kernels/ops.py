"""Public jit'd entry points for the BRAMAC kernels.

`quant_matmul` handles block padding and CPU-interpret dispatch so callers
never touch pallas directly.  `bramac_dense` is the training-friendly
fake-quant (QAT) matmul with a straight-through-estimator VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import ref
from repro.kernels.bramac_matmul import bramac_matmul


def _interpret() -> bool:
    """Pallas interpret mode on the CPU backend, compiled kernels on TPU;
    any other backend is refused rather than silently interpreted.
    Resolved per call — not frozen at import, so
    `jax.config.update("jax_platform_name", ...)` after importing this
    module still selects the right dispatch."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels target TPU (compiled) or CPU "
                           f"(interpret mode), not {backend!r}")
    return backend == "cpu"


def _pad_to(x, m, axis, value=0):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


VMEM_BUDGET = 16 * 2**20          # v5e VMEM per core (bytes)


def kernel_vmem_bytes(block: tuple[int, int, int], out_bytes: int = 4) -> int:
    """VMEM working set of one bramac_matmul grid point: activation tile
    (int8) + resident weight tile (int8 — the dummy-array footprint) +
    int32 accumulator + output tile.  Block shapes
    must keep this under VMEM_BUDGET with headroom for double-buffering
    (×2 on the streamed operands)."""
    bm, bk, bn = block
    x = bm * bk                   # int8
    w = bk * bn
    acc = bm * bn * 4
    out = bm * bn * out_bytes
    return 2 * x + 2 * w + acc + out   # ×2: grid-pipeline double buffers


def pick_block(M: int, K: int, N: int) -> tuple[int, int, int]:
    """Largest MXU-friendly blocks that don't over-pad small operands."""
    def pick(d, cap=128, floor=8):
        b = min(cap, max(floor, d))
        while d % b and b > floor:  # prefer a divisor to avoid padding
            b //= 2
        return b
    return pick(M), pick(K), pick(N)


def quant_matmul(x_q, w_q, x_scale, w_scale, *, bits_a: int, bits_w: int,
                 signed: bool = True, out_dtype=jnp.float32,
                 use_kernel: bool = True):
    """Quantized (M,K)x(K,N) matmul via the BRAMAC Pallas kernel.

    Pads to block multiples, runs the kernel (interpret mode on CPU), and
    slices back. When use_kernel=False runs the pure-jnp digit reference
    (useful under jit-of-vmap where pallas interpret mode is slow).
    """
    # interpret is resolved here (call/trace time) and enters the jit cache
    # as a static arg, so flipping the backend after import retraces.
    return _quant_matmul(x_q, w_q, x_scale, w_scale, bits_a=bits_a,
                         bits_w=bits_w, signed=signed, out_dtype=out_dtype,
                         use_kernel=use_kernel, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bits_a", "bits_w", "signed",
                                             "out_dtype", "use_kernel",
                                             "interpret"))
def _quant_matmul(x_q, w_q, x_scale, w_scale, *, bits_a: int, bits_w: int,
                  signed: bool, out_dtype, use_kernel: bool, interpret: bool):
    M, K = x_q.shape
    N = w_q.shape[-1]
    if not use_kernel:
        return ref.quant_matmul_digit_ref(
            x_q, w_q, x_scale, w_scale, bits_a=bits_a, signed=signed,
            out_dtype=out_dtype)

    bm, bk, bn = pick_block(M, K, N)
    xp = _pad_to(_pad_to(x_q, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    xs = _pad_to(jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32), (M, 1)),
                 bm, 0, value=1.0)
    ws = _pad_to(jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (1, N)),
                 bn, 1, value=1.0)
    out = bramac_matmul(xp, wp, xs, ws, bits_a=bits_a, bits_w=bits_w,
                        signed=signed, block=(bm, bk, bn),
                        out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Training-facing fake-quant dense with straight-through estimator.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def bramac_dense(x, w, bits_w: int, bits_a: int, use_kernel: bool = False):
    """y = dequant(Q(x) · Q(w)) with STE gradients.

    Forward runs the integer BRAMAC dataflow (per-row activation scales,
    per-column weight scales).  Backward treats quantization as identity.
    """
    y, _ = _bramac_dense_fwd(x, w, bits_w, bits_a, use_kernel)
    return y


def _bramac_dense_fwd(x, w, bits_w, bits_a, use_kernel):
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    qx = quant.quantize(x2, bits_a, axis=-1)           # per-row
    qw = quant.quantize(w, bits_w, axis=0)             # per-column
    y = quant_matmul(qx.values, qw.values, qx.scale, qw.scale,
                     bits_a=bits_a, bits_w=bits_w,
                     out_dtype=x.dtype, use_kernel=use_kernel)
    return y.reshape(*orig_shape[:-1], w.shape[-1]), (x, w)


def _bramac_dense_bwd(bits_w, bits_a, use_kernel, res, g):
    x, w = res
    g2 = g.reshape(-1, g.shape[-1]).astype(w.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    dx = (g2 @ w.T).reshape(x.shape).astype(x.dtype)
    dw = (x2.T @ g2).astype(w.dtype)
    return dx, dw


bramac_dense.defvjp(_bramac_dense_fwd, _bramac_dense_bwd)
