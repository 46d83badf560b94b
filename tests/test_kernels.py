"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.quant import qrange
from repro.kernels import ops, ref
from repro.kernels.bramac_matmul import bramac_matmul
from repro.kernels.mac2_kernel import mac2_mvm_kernel

jax.config.update("jax_platform_name", "cpu")

BITS = [2, 4, 8]


def rand_q(rng, bits, shape, signed=True):
    lo, hi = qrange(bits) if signed else (0, (1 << bits) - 1)
    return rng.integers(lo, hi + 1, size=shape, dtype=np.int8)


# ---------------------------------------------------------------------------
# Production radix-4 kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits_a", BITS)
@pytest.mark.parametrize("bits_w", BITS)
@pytest.mark.parametrize("shape", [(8, 16, 8), (16, 32, 24), (128, 128, 128)])
def test_bramac_matmul_shapes(bits_a, bits_w, shape):
    if shape == (128, 128, 128) and (bits_a, bits_w) != (4, 4):
        pytest.skip("full-block case covered once (interpret mode is slow)")
    M, K, N = shape
    rng = np.random.default_rng(hash((bits_a, bits_w, shape)) % 2**31)
    xq = jnp.asarray(rand_q(rng, bits_a, (M, K)))
    wq = jnp.asarray(rand_q(rng, bits_w, (K, N)))
    xs = jnp.asarray(rng.uniform(0.5, 2.0, (M, 1)).astype(np.float32))
    ws = jnp.asarray(rng.uniform(0.5, 2.0, (1, N)).astype(np.float32))
    got = ops.quant_matmul(xq, wq, xs, ws, bits_a=bits_a, bits_w=bits_w)
    want = ref.quant_matmul_exact(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_bramac_matmul_dtypes(out_dtype):
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rand_q(rng, 4, (16, 32)))
    wq = jnp.asarray(rand_q(rng, 4, (32, 16)))
    xs = jnp.ones((16, 1), jnp.float32)
    ws = jnp.ones((1, 16), jnp.float32)
    got = ops.quant_matmul(xq, wq, xs, ws, bits_a=4, bits_w=4,
                           out_dtype=out_dtype)
    assert got.dtype == out_dtype
    want = ref.quant_matmul_exact(xq, wq, xs, ws, out_dtype=out_dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


def test_bramac_matmul_unsigned_inputs():
    rng = np.random.default_rng(3)
    xq = jnp.asarray(rand_q(rng, 4, (8, 16), signed=False))
    wq = jnp.asarray(rand_q(rng, 4, (16, 8)))
    one = jnp.ones((1, 1), jnp.float32)
    got = ops.quant_matmul(xq, wq, one, one, bits_a=4, bits_w=4, signed=False)
    want = ref.quant_matmul_exact(xq, wq, one, one)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_kernels_refuse_backends_without_a_pallas_path(monkeypatch):
    """Interpret mode is for the CPU only: on any backend that is neither
    CPU nor TPU the kernels raise instead of silently interpreting."""
    assert ops._interpret() is True                 # this suite: CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from(BITS), seed=st.integers(0, 2**31 - 1))
def test_digit_ref_matches_exact(bits, seed):
    """The digit-dataflow reference is exact for any quantized input."""
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(rand_q(rng, bits, (8, 24)))
    wq = jnp.asarray(rand_q(rng, bits, (24, 8)))
    xs = jnp.asarray(rng.uniform(0.1, 2, (8, 1)).astype(np.float32))
    ws = jnp.asarray(rng.uniform(0.1, 2, (1, 8)).astype(np.float32))
    a = ref.quant_matmul_digit_ref(xq, wq, xs, ws, bits_a=bits)
    b = ref.quant_matmul_exact(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# Faithful dummy-array kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("shape", [(8, 6), (16, 10), (40, 8)])
def test_mac2_mvm_kernel(bits, shape):
    R, C = shape
    rng = np.random.default_rng(hash((bits, shape)) % 2**31)
    w = jnp.asarray(rand_q(rng, bits, (R, C)))
    x = jnp.asarray(rand_q(rng, bits, (C,)))
    got = mac2_mvm_kernel(w, x, bits=bits, block=8, interpret=True)
    want = ref.mac2_mvm_ref(w, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mac2_mvm_kernel_unsigned():
    rng = np.random.default_rng(9)
    w = jnp.asarray(rand_q(rng, 4, (8, 6)))
    x = jnp.asarray(rand_q(rng, 4, (6,), signed=False))
    got = mac2_mvm_kernel(w, x, bits=4, signed=False, block=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.mac2_mvm_ref(w, x)))


def test_kernel_blocks_fit_vmem():
    """Default and scaled-up block shapes stay inside the v5e VMEM budget
    (with double-buffering headroom), and MXU dims stay 128-aligned."""
    for block in [(128, 128, 128), (256, 128, 512), (512, 512, 512)]:
        assert ops.kernel_vmem_bytes(block) < ops.VMEM_BUDGET, block
        assert all(b % 128 == 0 for b in block)
    # something must NOT fit, or the budget check is vacuous
    assert ops.kernel_vmem_bytes((1024, 1024, 2048)) > ops.VMEM_BUDGET


@pytest.mark.parametrize("block", [(16, 16, 16), (8, 32, 16)])
def test_bramac_matmul_block_sweep(block):
    """Kernel correctness is block-shape independent."""
    rng = np.random.default_rng(7)
    M, K, N = 32, 64, 32
    xq = jnp.asarray(rand_q(rng, 4, (M, K)))
    wq = jnp.asarray(rand_q(rng, 4, (K, N)))
    xs = jnp.ones((M, 1), jnp.float32)
    ws = jnp.ones((1, N), jnp.float32)
    got = bramac_matmul(xq, wq, xs, ws, bits_a=4, bits_w=4, block=block,
                        interpret=True)
    want = ref.quant_matmul_exact(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# STE dense
# ---------------------------------------------------------------------------

def test_bramac_dense_forward_and_grad():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))

    y = ops.bramac_dense(x, w, 8, 8)
    # 8-bit fake-quant ≈ float matmul within a few percent
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=0.15, atol=0.1)

    def loss(x, w):
        return jnp.sum(ops.bramac_dense(x, w, 8, 8) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(gw)).all()
