"""Tensor-parallel quantized execution on 8 virtual CPU devices (the
`multidevice` marker — see tests/conftest.py)."""
import pytest
from conftest import run_multidevice as run_sub


@pytest.mark.multidevice
def test_tp_quant_matmul_bit_exact_all_bits():
    """K-sharded (int32 partial psum) and N-sharded (column-parallel) TP
    matmul == single-device quant_matmul, bit for bit, for 2/4/8-bit."""
    out = run_sub("""
from repro.core.quant import qrange
from repro.kernels import ops
from repro.parallel import tp

mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(0)
M, K, N = 16, 64, 24
for bits_a in (2, 4, 8):
    for bits_w in (2, 4, 8):
        la, ha = qrange(bits_a)
        lw, hw = qrange(bits_w)
        xq = jnp.asarray(rng.integers(la, ha + 1, (M, K), dtype=np.int8))
        wq = jnp.asarray(rng.integers(lw, hw + 1, (K, N), dtype=np.int8))
        xs = jnp.asarray(rng.uniform(0.5, 2.0, (M, 1)).astype(np.float32))
        ws = jnp.asarray(rng.uniform(0.5, 2.0, (1, N)).astype(np.float32))
        ref = ops.quant_matmul(xq, wq, xs, ws, bits_a=bits_a, bits_w=bits_w)
        for part in ("k", "n"):
            got = tp.tp_quant_matmul(xq, wq, xs, ws, mesh=mesh,
                                     bits_a=bits_a, bits_w=bits_w,
                                     partition=part)
            assert got.dtype == ref.dtype
            assert bool(jnp.all(got == ref)), (bits_a, bits_w, part)
print("TP_EXACT_OK")
""")
    assert "TP_EXACT_OK" in out


@pytest.mark.multidevice
def test_tp_quant_matmul_respects_active_tp_rule():
    """With a sharding ctx active, tp resolves the physical axis from the
    logical `tp` rule instead of assuming an axis name."""
    out = run_sub("""
from repro.core.quant import qrange
from repro.kernels import ops
from repro.parallel import sharding as shd, tp

mesh = jax.make_mesh((2, 4), ("data", "model"))
shd.activate(mesh)                       # rules: tp -> "model"
rng = np.random.default_rng(1)
lo, hi = qrange(8)
xq = jnp.asarray(rng.integers(lo, hi + 1, (8, 32), dtype=np.int8))
wq = jnp.asarray(rng.integers(lo, hi + 1, (32, 16), dtype=np.int8))
one = jnp.ones((1, 1), jnp.float32)
ref = ops.quant_matmul(xq, wq, one, one, bits_a=8, bits_w=8)
got = tp.tp_quant_matmul(xq, wq, one, one, mesh=mesh, bits_a=8, bits_w=8)
assert bool(jnp.all(got == ref))
print("TP_RULE_OK")
""")
    assert "TP_RULE_OK" in out


@pytest.mark.multidevice
def test_tp_quant_matmul_divisibility_error():
    out = run_sub("""
from repro.parallel import tp

mesh = jax.make_mesh((8,), ("model",))
x = jnp.zeros((4, 12), jnp.int8)         # K=12 not divisible by 8
w = jnp.zeros((12, 8), jnp.int8)
one = jnp.ones((1, 1), jnp.float32)
try:
    tp.tp_quant_matmul(x, w, one, one, mesh=mesh, bits_a=8, bits_w=8)
except ValueError as e:
    assert "not divisible" in str(e)
    print("TP_DIV_OK")
""")
    assert "TP_DIV_OK" in out


@pytest.mark.multidevice
def test_sharded_quantized_engine_decode():
    """Engine(mesh=...) with a pre-quantized parameter tree: the full
    continuous-batching loop (prefill + decode) completes tensor-parallel."""
    out = run_sub("""
from repro.configs import get_config
from repro.core import bramac_linear as bl
from repro.models import model as M
from repro.runtime.serve import Engine

cfg = get_config("granite-8b", smoke=True)
params = M.init_params(cfg, jax.random.PRNGKey(0))
qparams = bl.tree_prepare_serving(
    params, bl.QuantConfig(enabled=True, bits_w=8, bits_a=8))
mesh = jax.make_mesh((2, 4), ("data", "model"))
eng = Engine(cfg, qparams, num_slots=2, max_seq=32, mesh=mesh)
reqs = [eng.submit([1, 2, 3], max_new_tokens=3),
        eng.submit([4, 5], max_new_tokens=3)]
eng.run()
assert all(r.done for r in reqs)
assert all(len(r.out_tokens) == 3 for r in reqs)
assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out_tokens)
print("ENGINE_TP_OK")
""")
    assert "ENGINE_TP_OK" in out


@pytest.mark.multidevice
def test_sharded_engine_builds_kv_cache_split_over_kv_heads():
    """Engine(mesh=...) creates its paged KV pool in place on the mesh,
    KV heads over `model` (where wk/wv put them), instead of staging the
    whole cache on the first device; a head count the axis does not
    divide stays replicated."""
    out = run_sub("""
from repro.configs import get_config
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.runtime.serve import Engine

cfg = get_config("granite-8b", smoke=True)          # 2 KV heads
params = M.init_params(cfg, jax.random.PRNGKey(0))
for spec, want in (("data=4,model=2", "model"), ("data=2,model=4", None)):
    with Engine(cfg, params, num_slots=2, max_seq=32,
                mesh=shd.build_mesh(spec)) as eng:
        k = eng.caches["pos0"]["k"]                 # (periods, P, ps, Hkv, hd)
        assert len(k.sharding.device_set) == 8, k.sharding
        got = tuple(k.sharding.spec) + (None,) * k.ndim
        assert got[3] == want and got[:3] == (None,) * 3, (spec, got)
        req = eng.submit([1, 2, 3], max_new_tokens=3)
        eng.run()
        assert req.done and len(req.out_tokens) == 3
print("KV_SPLIT_OK")
""")
    assert "KV_SPLIT_OK" in out
