"""Attention-mixer unit tests: chunked == unchunked, GQA grouping, caches."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, strategies as st

from repro.models import attention as A

jax.config.update("jax_platform_name", "cpu")


def _qkv(key, B, S, H, Hkv, hd):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    return q, k, v


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), group=st.sampled_from([1, 2, 4]))
def test_gqa_grouping_matches_repeated_kv(seed, group):
    """GQA == MHA with kv heads repeated `group` times."""
    B, S, Hkv, hd = 2, 8, 2, 16
    H = Hkv * group
    q, k, v = _qkv(jax.random.PRNGKey(seed), B, S, H, Hkv, hd)
    got = A.causal_attention(q, k, v)
    k_rep = jnp.repeat(k, group, axis=2)
    v_rep = jnp.repeat(v, group, axis=2)
    want = A.causal_attention(q, k_rep, v_rep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_equals_unchunked():
    """Query-chunked path == single-shot attention."""
    B, H, Hkv, hd = 1, 4, 2, 8
    S = 4 * A.Q_CHUNK if A.Q_CHUNK <= 64 else 0
    old = A.Q_CHUNK
    try:
        A.Q_CHUNK = 16
        q, k, v = _qkv(jax.random.PRNGKey(0), B, 64, H, Hkv, hd)
        chunked = A.causal_attention(q, k, v)
        A.Q_CHUNK = 64
        full = A.causal_attention(q, k, v)
    finally:
        A.Q_CHUNK = old
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_masks_future():
    """Keys beyond pos contribute nothing."""
    B, H, Hkv, hd, S = 2, 2, 2, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, hd))
    pos = jnp.array([3, 7], jnp.int32)
    out = A.decode_attention(q, k, v, pos)
    # corrupt keys/values beyond each pos — output must not change
    k2 = k.at[0, 4:].set(99.0).at[1, 8:].set(99.0)
    v2 = v.at[0, 4:].set(-99.0).at[1, 8:].set(-99.0)
    out2 = A.decode_attention(q, k2, v2, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_cache_update_at_position():
    """Rows land at each sequence's position in the addressed layer of the
    stacked (n_periods, B, max_seq, ...) cache, and nowhere else."""
    cache = jnp.zeros((3, 2, 8, 2, 4))
    new = jnp.ones((2, 1, 2, 4))
    pos = jnp.array([2, 5], jnp.int32)
    out = A._update_cache(cache, new, pos, 1)
    assert float(out[1, 0, 2].sum()) == 8.0
    assert float(out[1, 1, 5].sum()) == 8.0
    assert float(out[1, 0, 5].sum()) == 0.0
    assert float(out[1, 1, 2].sum()) == 0.0
    assert float(out[0].sum()) == 0.0 and float(out[2].sum()) == 0.0


def test_int8_kv_cache_decode_close_to_fp():
    """quant_kv decode (int8 K, int8 V with scales folded into probs)
    tracks the bf16-cache decode closely, end to end."""
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("granite-8b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)

    outs = {}
    for name, c in (("fp", cfg), ("kv8", cfg.replace(quant_kv=True))):
        caches = M.init_cache(c, 2, 12)
        _, caches = M.prefill(params, {"tokens": tokens}, c, caches)
        pos = jnp.full((2,), 8, jnp.int32)
        logits, _ = M.decode_step(params, tokens[:, :1], c, caches, pos)
        outs[name] = logits
    a, b = outs["fp"].astype(jnp.float32), outs["kv8"].astype(jnp.float32)
    cos = float(jnp.sum(a * b) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
    assert cos > 0.995, cos
    # int8 cache really is int8
    caches = M.init_cache(cfg.replace(quant_kv=True), 2, 12)
    leaf = caches["pos0"]["k"]
    assert leaf.dtype == jnp.int8


def test_quant_rows_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 32)) * 5
    q, s = A._quant_rows(x)
    err = jnp.abs(q.astype(jnp.float32) * s[..., None] - x)
    assert float(jnp.max(err / s[..., None])) <= 0.5 + 1e-3


def test_rope_relative_property():
    """RoPE: q·k depends only on relative offset."""
    from repro.models.layers import apply_rope
    hd = 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    def dot_at(pq, pk):
        qr = apply_rope(q, jnp.array([[pq]]), 10_000.0)
        kr = apply_rope(k, jnp.array([[pk]]), 10_000.0)
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(3, 1) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(0, 0) - dot_at(7, 7)) < 1e-4


def test_causal_attention_ragged_tail():
    """Sq not divisible by Q_CHUNK runs the full chunks through the scanned
    body plus one trailing partial chunk — same result as unchunked."""
    B, H, Hkv, hd = 1, 4, 2, 8
    old = A.Q_CHUNK
    try:
        A.Q_CHUNK = 16
        q, k, v = _qkv(jax.random.PRNGKey(2), B, A.Q_CHUNK + 1, H, Hkv, hd)
        ragged = A.causal_attention(q, k, v)
        A.Q_CHUNK = 64
        full = A.causal_attention(q, k, v)
    finally:
        A.Q_CHUNK = old
    assert ragged.shape == full.shape
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_negative_position_never_writes_dense():
    """Regression: a padding row at position -1 used to wrap through
    numpy-style negative indexing into the cache's last row; the write
    masks must drop it."""
    cache = jnp.zeros((2, 2, 8, 2, 4))              # (n_periods, B, ...)
    new = jnp.ones((2, 1, 2, 4))
    dv = A.DenseKV(write_mask=jnp.ones((2,), bool), max_seq=8, layer=1)
    out = A.dense_update(cache, new, jnp.array([[-1], [3]], jnp.int32), dv)
    assert float(jnp.abs(out[1, 0]).sum()) == 0.0   # -1 must not alias row 7
    assert float(jnp.abs(out[1, 1, 3]).sum()) == 8.0  # in-range row lands
    assert float(jnp.abs(out[0]).sum()) == 0.0      # other layer untouched


def test_negative_position_never_writes_paged():
    """Regression: position -1 floor-divides to page index -1 (which passes
    `< n_pages`), clips to table entry 0 and wraps its row positive —
    without the lower bound it landed inside a live page."""
    P, ps = 4, 4
    pool = jnp.zeros((2, P, ps, 2, 4))              # (n_periods, P, ...)
    new = jnp.ones((2, 1, 2, 4))
    pv = A.PagedKV(tables=jnp.array([[1, 2], [3, 0]], jnp.int32),
                   n_pages=jnp.array([2, 2], jnp.int32),
                   write_mask=jnp.ones((2,), bool),
                   max_seq=8, page_size=ps)
    out = A.paged_update(pool, new, jnp.array([[-1], [5]], jnp.int32), pv)
    # slot 0's write at -1 must vanish: its pages (1 and 2) stay zero
    assert float(jnp.abs(out[0, 1]).sum()) == 0.0
    assert float(jnp.abs(out[0, 2]).sum()) == 0.0
    # slot 1's in-range write lands in page 0 (table entry 1), row 5%4
    assert float(jnp.abs(out[0, 0, 1]).sum()) == 8.0
    assert float(jnp.abs(out[1]).sum()) == 0.0      # other layer untouched
