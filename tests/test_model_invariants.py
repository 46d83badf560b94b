"""Property tests on the model-zoo's structural invariants:

  * chunkwise-parallel mLSTM == step-by-step recurrent mLSTM
  * chunked mamba scan == single-chunk scan == step-by-step decode
  * capacity MoE dispatch == dense all-experts reference when no drops
  * sLSTM sequence == step-by-step decode
  * stack with scan_layers=True == unrolled stack
  * stack with the caches carried and addressed by layer == a per-layer
    reference that slices each layer's caches and restacks them
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.models import mamba as mb
from repro.models import model as M
from repro.models import attention as A
from repro.models import moe as moe_mod
from repro.models import transformer as tf
from repro.models import xlstm as xl

jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_enable_x64", False)


def _xl_cfg(chunk):
    return get_config("xlstm-1.3b", smoke=True).replace(chunk_size=chunk)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), chunk=st.sampled_from([2, 4, 8]),
       S=st.sampled_from([8, 16]))
def test_mlstm_chunkwise_equals_recurrent(seed, chunk, S):
    cfg = _xl_cfg(chunk)
    key = jax.random.PRNGKey(seed)
    p = xl.init_mlstm(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, S, cfg.d_model),
                          jnp.float32)
    seq, _ = xl.mlstm_sequence(p, x, cfg)
    rec = xl.mlstm_recurrent_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(rec),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), chunk=st.sampled_from([2, 4, 16]))
def test_mamba_chunked_equals_stepwise(seed, chunk):
    cfg = get_config("jamba-1.5-large-398b", smoke=True).replace(
        chunk_size=chunk)
    key = jax.random.PRNGKey(seed)
    p = mb.init_mamba(key, cfg)
    S = 16
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, S, cfg.d_model),
                          jnp.float32)
    seq, (hT, tail) = mb.mamba_sequence(p, x, cfg)

    state = mb.init_mamba_state(cfg, 2, x.dtype)
    outs = []
    for t in range(S):
        o, state = mb.mamba_step(p, x[:, t:t + 1], cfg, state)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(step),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(state[0]),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_slstm_sequence_equals_stepwise(seed):
    cfg = _xl_cfg(8)
    key = jax.random.PRNGKey(seed)
    p = xl.init_slstm(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, cfg.d_model),
                          jnp.float32)
    seq, _ = xl.slstm_sequence(p, x, cfg)
    state = xl.init_slstm_state(cfg, 2)
    outs = []
    for t in range(8):
        o, state = xl.slstm_step(p, x[:, t:t + 1], cfg, state)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(seq),
                               np.asarray(jnp.concatenate(outs, 1)),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), E=st.sampled_from([4, 8]),
       k=st.sampled_from([1, 2]))
def test_moe_capacity_dispatch_exact_when_no_drops(seed, E, k):
    cfg = get_config("dbrx-132b", smoke=True).replace(
        num_experts=E, experts_per_token=k)
    key = jax.random.PRNGKey(seed)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, cfg.d_model),
                          jnp.float32)
    # capacity_factor = E/k guarantees C = T*k/E * E/k = T — no drops ever
    out, aux = moe_mod.moe(p, x, cfg, capacity_factor=E / k)
    want = moe_mod.moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert float(aux) > 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), E=st.sampled_from([4, 8, 16]))
def test_moe_sort_dispatch_equals_cumsum(seed, E):
    """The §Perf sort-based rank-in-expert == the baseline cumsum ranks."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, E, size=200, dtype=np.int32))
    onehot = jax.nn.one_hot(a, E, dtype=jnp.int32)
    pos_cumsum = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                                     a[:, None], axis=1)[:, 0]
    pos_sort = moe_mod._rank_in_expert_sort(a, E)
    np.testing.assert_array_equal(np.asarray(pos_sort),
                                  np.asarray(pos_cumsum))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_moe_sort_dispatch_full_layer(seed):
    cfg = get_config("dbrx-132b", smoke=True)
    key = jax.random.PRNGKey(seed)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, cfg.d_model),
                          jnp.float32)
    base, _ = moe_mod.moe(p, x, cfg, capacity_factor=2.0)
    fast, _ = moe_mod.moe(p, x, cfg.replace(moe_dispatch="sort"),
                          capacity_factor=2.0)
    np.testing.assert_allclose(np.asarray(base), np.asarray(fast),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_moe_dispatch_parity_under_drops(seed):
    """sort vs one-hot-cumsum dispatch assign identical ranks, so their
    capacity-overflow DROP behavior matches too: at a squeezing capacity
    factor both paths drop the same tokens and emit identical outputs."""
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)       # E=8, top-2
    key = jax.random.PRNGKey(seed)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model),
                          jnp.float32)
    out_sort, aux_s = moe_mod.moe(p, x, cfg.replace(moe_dispatch="sort"),
                                  capacity_factor=0.5)
    out_cum, aux_c = moe_mod.moe(p, x, cfg.replace(moe_dispatch="cumsum"),
                                 capacity_factor=0.5)
    np.testing.assert_array_equal(np.asarray(out_sort), np.asarray(out_cum))
    np.testing.assert_allclose(float(aux_s), float(aux_c), rtol=1e-6)
    # cf=0.5 actually dropped something (else this test is vacuous)
    full, _ = moe_mod.moe(p, x, cfg, capacity_factor=cfg.num_experts
                          / cfg.experts_per_token)
    assert not np.array_equal(np.asarray(out_sort), np.asarray(full))


@pytest.mark.parametrize("dispatch", ["sort", "cumsum"])
def test_moe_equals_reference_no_drop_both_dispatches(dispatch):
    """moe() == dense all-experts moe_reference at no-drop capacity for
    BOTH dispatch implementations."""
    cfg = get_config("dbrx-132b", smoke=True).replace(moe_dispatch=dispatch)
    key = jax.random.PRNGKey(3)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, cfg.d_model),
                          jnp.float32)
    out, _ = moe_mod.moe(p, x, cfg, capacity_factor=cfg.num_experts
                         / cfg.experts_per_token)
    want = moe_mod.moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_moe_capacity_drops_bounded():
    """With cf=1.0, outputs differ from reference only on dropped tokens
    (which fall back to the residual path — zeros here)."""
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model),
                          jnp.float32)
    out, _ = moe_mod.moe(p, x, cfg, capacity_factor=1.0)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("arch", ["granite-8b", "jamba-1.5-large-398b",
                                  "xlstm-1.3b"])
def test_scan_equals_unrolled_stack(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size)}
    a, _, _ = M.forward(params, batch, cfg)
    b, _, _ = M.forward(params, batch, cfg.replace(scan_layers=False))
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-4, atol=2e-4)


# case -> (arch, config changes, paged layout, decode kernel, chunk rows)
CARRY_CASES = {
    "paged-kernel": ("granite-8b", {"num_layers": 3}, True, True, 1),
    "paged-gather": ("granite-8b", {"num_layers": 3}, True, False, 4),
    "dense": ("granite-8b", {"num_layers": 3}, False, False, 4),
    "int8-kv": ("granite-8b", {"num_layers": 3, "quant_kv": True}, True,
                True, 1),
    "mla": ("minicpm3-4b", {"num_layers": 3}, True, False, 4),
    "recurrent-decode": ("jamba-1.5-large-398b", {"num_layers": 16}, True,
                         True, 1),
    "recurrent-chunk": ("xlstm-1.3b", {"num_layers": 16}, False, False, 4),
}


def _filled(caches, key):
    """Every cache leaf filled with seeded values, so that a read of the
    wrong layer or of stale rows shows in the output."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(caches)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, a) in zip(keys, leaves):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128, jnp.int32)
                       .astype(jnp.int8))
        elif getattr(path[-1], "key", None) in ("ks", "vs"):   # row scales
            out.append(jax.random.uniform(k, a.shape, a.dtype, 1e-3, 2e-2))
        else:
            out.append((0.5 * jax.random.normal(k, a.shape)).astype(a.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def _per_layer_reference(params, x, cfg, caches, **kw):
    """The layer loop as it was before the caches rode in the carry: slice
    layer l out of every cache leaf (as a one-layer stack), run each
    block of the period on it, and restack the layers at the end."""
    aux_total = jnp.zeros((), jnp.float32)
    layers = []
    for l in range(cfg.n_periods):
        p_l = jax.tree_util.tree_map(lambda a: a[l], params)
        c_l = jax.tree_util.tree_map(lambda a: a[l:l + 1], caches)
        for i, spec in enumerate(cfg.layer_pattern):
            key = f"pos{i}"
            x, aux, c_l[key] = tf.block_apply(
                p_l[key], x, cfg, spec, cache=c_l[key], layer=0, **kw)
            aux_total += aux
        layers.append(c_l)
    return x, aux_total, jax.tree_util.tree_map(
        lambda *ls: jnp.concatenate(ls), *layers)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("case", sorted(CARRY_CASES))
def test_carried_caches_equal_per_layer_reference(case, scan):
    """stack_apply with the stacked caches in the layer loop's carry,
    written and read at each layer's index, matches bit for bit a loop
    that slices each layer's caches, runs block_apply on them and
    restacks: hidden states, aux loss and every cache leaf.  Slots mix
    write-masked, read-only (shared) and fresh pages, and positions
    straddle page boundaries."""
    arch, changes, paged, kernel, S = CARRY_CASES[case]
    cfg = get_config(arch, smoke=True).replace(scan_layers=scan, **changes)
    assert cfg.n_periods >= 2
    B, max_seq, ps = 3, 64, cfg.page_size
    key = jax.random.PRNGKey(7)
    params = M.init_params(cfg, key)["layers"]
    mp = max_seq // ps
    P = B * mp + 2
    caches = _filled(M.init_cache(cfg, B, max_seq,
                                  num_pages=P if paged else None),
                     jax.random.fold_in(key, 1))
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, S, cfg.d_model),
                          cfg.compute_dtype)
    cache_pos = jnp.asarray([5, 15, 31], jnp.int32)
    positions = cache_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    if paged:
        tables = jax.random.permutation(jax.random.fold_in(key, 3), P)[
            :B * mp].reshape(B, mp).astype(jnp.int32)
        owned = jnp.ones((B, mp), bool).at[1, 0].set(False)
        bundle = A.PagedKV(tables=tables,
                           n_pages=jnp.asarray([1, 2, 3], jnp.int32),
                           write_mask=jnp.asarray([True, True, False]),
                           max_seq=max_seq, page_size=ps, owned=owned,
                           decode_kernel=kernel)
    else:
        bundle = None
    kw = dict(positions=positions, cache_pos=cache_pos, paged=bundle)

    got = jax.jit(lambda p, x, c: tf.stack_apply(p, x, cfg, caches=c,
                                                 **kw))(params, x, caches)
    want = jax.jit(lambda p, x, c: _per_layer_reference(p, x, cfg, c, **kw))(
        params, x, caches)
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the call wrote something: the caches it returns differ from its input
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(got[2]),
                               jax.tree_util.tree_leaves(caches)))
