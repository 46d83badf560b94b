"""Distributed-execution tests on 8 virtual CPU devices (the `multidevice`
marker: standalone runs spawn one subprocess per test so the XLA
device-count flag never leaks; ci.sh batches them in one pass)."""
import pytest
from conftest import run_multidevice as run_sub

pytestmark = pytest.mark.multidevice


def test_sharded_train_step_matches_single_device():
    """pjit'd (2 data × 4 model) train step == unsharded step numerically."""
    out = run_sub("""
from repro.configs import get_config
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.optim import adamw

cfg = get_config("granite-8b", smoke=True).replace(num_layers=2)
params = M.init_params(cfg, jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                      cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                      cfg.vocab_size)}

def loss(p, b):
    return M.loss_fn(p, b, cfg)[0]

ref_loss = loss(params, batch)
ref_grad = jax.grad(lambda p: loss(p, batch))(params)

mesh = shd.build_mesh("data=2,model=4")
ctx = shd.activate(mesh)
p_sh = shd.param_shardings(params, ctx)
params_s = jax.device_put(params, p_sh)
b_sh = jax.tree_util.tree_map(
    lambda a: NamedSharding(mesh, P("data", *([None]*(a.ndim-1)))), batch)
batch_s = jax.device_put(batch, b_sh)
got_loss, got_grad = jax.jit(jax.value_and_grad(loss),
                             in_shardings=(p_sh, b_sh))(params_s, batch_s)
np.testing.assert_allclose(float(got_loss), float(ref_loss), rtol=2e-5)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32))))
          for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                          jax.tree_util.tree_leaves(ref_grad)))
print("MAXERR", err)
assert err < 5e-4, err
print("SHARDED_OK")
""")
    assert "SHARDED_OK" in out


def test_dp_trainer_compression_and_convergence():
    """shard_map DP trainer: int8+error-feedback grads still converge, and
    one-step compressed grads are close to exact mean grads."""
    out = run_sub("""
from repro.runtime import dp_trainer as dp

mesh = jax.make_mesh((8,), ("data",))
params = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 4))}
target = jax.random.normal(jax.random.PRNGKey(1), (16, 4))

def loss_fn(p, batch):
    pred = batch @ p["w"]
    want = batch @ target
    return jnp.mean((pred - want) ** 2)

step = dp.make_dp_train_step(loss_fn, mesh, compress=True)
step_exact = dp.make_dp_train_step(loss_fn, mesh, compress=False)
err = dp.init_error_feedback(params, mesh)
batch = jax.random.normal(jax.random.PRNGKey(2), (64, 16))

g1, err1, l1 = step(params, err, batch)
g0, _, _ = step_exact(params, err, batch)
rel = float(jnp.linalg.norm(g1["w"] - g0["w"]) / jnp.linalg.norm(g0["w"]))
print("REL", rel)
assert rel < 0.05, rel

# convergence with compressed grads matches exact-gradient convergence
import copy
finals = []
for st in (step, step_exact):
    p = copy.deepcopy(params)
    e = dp.init_error_feedback(params, mesh)
    for i in range(200):
        g, e, l = st(p, e, batch)
        p = jax.tree_util.tree_map(lambda a, gg: a - 0.05 * gg, p, g)
    finals.append(float(l))
print("FINAL_LOSSES", finals)
assert finals[0] < 0.01 * 37.6           # descended >100x
assert abs(finals[0] - finals[1]) < 0.05 * finals[1] + 1e-6
print("DP_OK")
""")
    assert "DP_OK" in out


def test_multihost_batch_sharding_and_elastic_mesh():
    """Same checkpoint usable across 8-device and 2-device meshes
    (elastic scale-down) with identical loss."""
    out = run_sub("""
from repro.configs import get_config
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.checkpoint import ckpt
import tempfile, os

cfg = get_config("musicgen-large", smoke=True).replace(num_layers=2)
params = M.init_params(cfg, jax.random.PRNGKey(0))
d = tempfile.mkdtemp()
ckpt.save(d, 0, params)

losses = []
for shape, axes in (((8, 1), ("data", "model")), ((2, 1), ("data", "model"))):
    devs = np.array(jax.devices()[: shape[0] * shape[1]]).reshape(shape)
    mesh = Mesh(devs, axes)
    ctx = shd.activate(mesh)
    p_sh = shd.param_shardings(params, ctx)
    restored = ckpt.restore(d, 0, params, shardings=p_sh)
    B = 8
    batch = {"frame_embeds": jnp.ones((B, 8, cfg.d_model), jnp.float32),
             "labels": jnp.zeros((B, 8), jnp.int32)}
    loss, _ = jax.jit(lambda p, b: M.loss_fn(p, b, cfg))(restored, batch)
    losses.append(float(loss))
    shd.deactivate()
print("LOSSES", losses)
assert abs(losses[0] - losses[1]) < 1e-5
print("ELASTIC_MESH_OK")
""")
    assert "ELASTIC_MESH_OK" in out
