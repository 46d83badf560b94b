"""Pallas paged-decode kernel vs the gather oracle (kernels/paged_attention).

The kernel walks each sequence's block table page by page (bounded by
`n_pages`, never `max_seq`); the oracle is the dense gather the serving
stack has always used (`paged_view` + `chunk_attention`, and
`decode_attention_q` for the int8 cache).  Everything here runs the real
kernel code in pallas interpret mode on CPU.

Pools are stacked over layers, (n_periods, P, page_size, Hkv, hd), as the
layer scan carries them, and every read and write names its layer.

Covers: direct kernel/oracle parity at positions straddling page
boundaries (fp and int8), at every layer of a stacked pool (and writes
that stay inside their layer), pools after a speculative-style rollback,
engine-level greedy stream bit-parity with the kernel on vs off across
{gqa, int8-KV} — including shared (prefix-cached, owned=False) pages —
and the free-slot (no pages) edge."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attention as pk
from repro.models import attention as A
from repro.models import model as M
from repro.runtime import pages as pg
from repro.runtime.serve import Engine

jax.config.update("jax_platform_name", "cpu")

PS = 16          # pool page size in these tests


def _pool_case(seed, B, n_pages, max_pages=4, P=16, Hkv=2, hd=16, L=1):
    """Random fp pools stacked over L layers + per-sequence block tables
    (page ids shuffled so logical and physical order differ)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_pool = jax.random.normal(ks[0], (L, P, PS, Hkv, hd), jnp.float32)
    v_pool = jax.random.normal(ks[1], (L, P, PS, Hkv, hd), jnp.float32)
    tables = jax.random.permutation(
        ks[2], P)[:B * max_pages].reshape(B, max_pages).astype(jnp.int32)
    return k_pool, v_pool, tables, jnp.asarray(n_pages, jnp.int32)


def _bundle(tables, n_pages, max_seq):
    return A.PagedKV(tables=tables, n_pages=n_pages,
                     write_mask=jnp.ones(tables.shape[0], bool),
                     max_seq=max_seq, page_size=PS)


# --- direct kernel vs oracle ------------------------------------------------

@pytest.mark.parametrize("lengths", [(15, 16, 17), (1, 32, 33), (48, 2, 31)])
def test_kernel_matches_oracle_across_page_boundaries(lengths):
    """fp kernel output equals the gather oracle at live lengths below /
    at / across page boundaries (the page loop must include the partial
    tail page and exclude everything past it)."""
    B, H, max_seq = 3, 4, 64
    n_pages = [-(-n // PS) for n in lengths]
    k_pool, v_pool, tables, n_pages = _pool_case(0, B, n_pages)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, 1, H, 16), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    out = pk.paged_decode(q[:, 0], k_pool, v_pool, tables, n_pages,
                          positions + 1, 0)
    pv = _bundle(tables, n_pages, max_seq)
    ref = A.chunk_attention(q, A.paged_view(k_pool, pv),
                            A.paged_view(v_pool, pv),
                            positions[:, None])[:, 0]
    np.testing.assert_allclose(out, ref, atol=2e-6)


@pytest.mark.parametrize("quant_kv", [False, True], ids=["fp", "int8"])
def test_kernel_reads_the_addressed_layer(quant_kv):
    """On a pool stacked over three layers, the kernel at layer l equals
    the gather oracle run on that layer alone (`pool[l]` restacked as a
    one-layer pool), for every l: the layer index reaches the page DMA
    and nothing of a neighbouring layer is read."""
    B, H, max_seq, L = 3, 4, 64, 3
    lengths = (15, 33, 48)
    n_pages = [-(-n // PS) for n in lengths]
    k_pool, v_pool, tables, n_pages = _pool_case(29, B, n_pages, L=L)
    q = jax.random.normal(jax.random.PRNGKey(31), (B, 1, H, 16), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    pv = _bundle(tables, n_pages, max_seq)
    kq, kss = A._quant_rows(k_pool)
    vq, vss = A._quant_rows(v_pool)
    qq, qs = A._quant_rows(q)
    outs = []
    for layer in range(L):
        one = lambda a: a[layer][None]  # noqa: E731
        if quant_kv:
            out = pk.paged_decode_q(qq[:, 0], qs[:, 0], kq, kss, vq, vss,
                                    tables, n_pages, positions + 1, layer,
                                    q.dtype)
            view = {key: A.paged_view(one(a), pv) for key, a in
                    (("k", kq), ("ks", kss), ("v", vq), ("vs", vss))}
            ref = A.decode_attention_q(q, view, positions[:, None])[:, 0]
            atol = 1e-6
        else:
            out = pk.paged_decode(q[:, 0], k_pool, v_pool, tables, n_pages,
                                  positions + 1, layer)
            ref = A.chunk_attention(q, A.paged_view(one(k_pool), pv),
                                    A.paged_view(one(v_pool), pv),
                                    positions[:, None])[:, 0]
            atol = 2e-6
        np.testing.assert_allclose(out, ref, atol=atol)
        outs.append(np.asarray(out))
    # the layers hold different rows, so their outputs must differ too
    assert not np.allclose(outs[0], outs[1])
    assert not np.allclose(outs[1], outs[2])


def test_writes_stay_in_their_layer():
    """Rows written through paged_update at layer l land in layer l's
    pages and leave layers l-1 and l+1 bit for bit as they were; the
    kernel then reads the new rows at l and the old ones beside it."""
    B, Hkv, hd, max_seq, L = 2, 2, 16, 64, 3
    k_pool, v_pool, tables, n_pages = _pool_case(37, B, [2, 2], L=L)
    pv = _bundle(tables, n_pages, max_seq)
    positions = jnp.asarray([[20], [5]], jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(41), (B, 1, Hkv, hd)) + 5.0
    for layer in range(L):
        pv_l = dataclasses.replace(pv, layer=layer)
        out = A.paged_update(k_pool, new, positions, pv_l)
        for other in (layer - 1, layer + 1):
            if 0 <= other < L:
                np.testing.assert_array_equal(out[other], k_pool[other])
        view = A.paged_view(out, pv_l)
        for b in range(B):
            np.testing.assert_array_equal(view[b, positions[b, 0]],
                                          new[b, 0])
        # every other row of the written layer is unchanged
        changed = np.asarray(out[layer] != k_pool[layer]).any(axis=(2, 3))
        assert changed.sum() == B
        q = jax.random.normal(jax.random.PRNGKey(43), (B, 4, hd))
        lengths = positions[:, 0] + 1
        for other in range(L):
            got = pk.paged_decode(q, out, v_pool, tables, n_pages, lengths,
                                  other)
            base = pk.paged_decode(q, k_pool, v_pool, tables, n_pages,
                                   lengths, other)
            if other == layer:
                assert not np.allclose(got, base)
            else:
                np.testing.assert_array_equal(got, base)


def test_kernel_reads_only_allocated_pages():
    """Rows past a sequence's allocated pages must not contribute even
    when its stale table entries alias another sequence's live pages —
    poisoning every non-allocated page with huge values may not change
    the output."""
    B, H = 2, 4
    k_pool, v_pool, tables, n_pages = _pool_case(1, B, [1, 2])
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, 16), jnp.float32)
    lengths = jnp.asarray([PS, 2 * PS], jnp.int32)
    out = pk.paged_decode(q, k_pool, v_pool, tables, n_pages, lengths, 0)
    # poison every page no sequence legitimately reads
    live = np.zeros(k_pool.shape[1], bool)
    tb = np.asarray(tables)
    for b, n in enumerate(np.asarray(n_pages)):
        live[tb[b, :n]] = True
    live = jnp.asarray(live)[None, :, None, None, None]
    k_bad = jnp.where(live, k_pool, 1e9)
    v_bad = jnp.where(live, v_pool, 1e9)
    out_bad = pk.paged_decode(q, k_bad, v_bad, tables, n_pages, lengths, 0)
    np.testing.assert_array_equal(out, out_bad)


def test_kernel_free_slot_emits_zeros():
    """A slot with no pages (released / never admitted) reads nothing and
    returns exact zeros instead of NaN from an empty softmax."""
    k_pool, v_pool, tables, n_pages = _pool_case(2, 2, [0, 2])
    q = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 16), jnp.float32)
    out = pk.paged_decode(q, k_pool, v_pool, tables, n_pages,
                          jnp.asarray([1, 20], jnp.int32), 0)
    assert bool(jnp.all(out[0] == 0.0))
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("lengths", [(15, 16, 17), (1, 33, 48)])
def test_kernel_int8_matches_oracle(lengths):
    """int8 variant replays decode_attention_q's arithmetic (including the
    probability requantization) — outputs agree to reassociation error."""
    B, H, max_seq = 3, 4, 64
    n_pages = [-(-n // PS) for n in lengths]
    k_pool, v_pool, tables, n_pages = _pool_case(7, B, n_pages)
    kq, kss = A._quant_rows(k_pool)
    vq, vss = A._quant_rows(v_pool)
    q = jax.random.normal(jax.random.PRNGKey(11), (B, 1, H, 16), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    qq, qs = A._quant_rows(q)
    out = pk.paged_decode_q(qq[:, 0], qs[:, 0], kq, kss, vq, vss, tables,
                            n_pages, positions + 1, 0, q.dtype)
    pv = _bundle(tables, n_pages, max_seq)
    cache = {"k": kq, "ks": kss, "v": vq, "vs": vss}
    view = {key: A.paged_view(cache[key], pv) for key in cache}
    ref = A.decode_attention_q(q, view, positions[:, None])[:, 0]
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_kernel_after_rollback_matches_oracle():
    """Speculative-style pool: a draft window is written through
    paged_update, the verify pass rejects its tail, pages.rollback zeroes
    the rejected rows — the kernel must read the exact post-rollback pool
    the oracle reads."""
    B, Hkv, hd, max_seq = 2, 2, 16, 64
    k_pool, v_pool, tables, n_pages = _pool_case(13, B, [2, 2])
    pv = _bundle(tables, n_pages, max_seq)
    # draft window of 4 rows at positions 20..23 / 10..13, bound mid-window
    window = jnp.stack([jnp.arange(20, 24), jnp.arange(10, 14)]).astype(
        jnp.int32)
    pvw = A.PagedKV(tables=pv.tables, n_pages=pv.n_pages,
                    write_mask=pv.write_mask, max_seq=max_seq, page_size=PS,
                    bound=jnp.asarray([24, 14], jnp.int32))
    new_k = jax.random.normal(jax.random.PRNGKey(17), (B, 4, Hkv, hd))
    new_v = jax.random.normal(jax.random.PRNGKey(19), (B, 4, Hkv, hd))
    k_pool = A.paged_update(k_pool, new_k, window, pvw)
    v_pool = A.paged_update(v_pool, new_v, window, pvw)
    # verify accepted 1 row for slot 0, 2 rows for slot 1: reject the rest
    rejected = jnp.asarray([[21, 22, 23, max_seq],
                            [12, 13, max_seq, max_seq]], jnp.int32)
    # rollback operates on stacked (n_periods, P, ps, ...) cache leaves
    caches = pg.rollback({"k": k_pool, "v": v_pool},
                         {"k": True, "v": True}, pvw, rejected)
    k_pool, v_pool = caches["k"], caches["v"]
    q = jax.random.normal(jax.random.PRNGKey(23), (B, 1, 4, hd), jnp.float32)
    positions = jnp.asarray([21, 12], jnp.int32)   # last accepted row
    out = pk.paged_decode(q[:, 0], k_pool, v_pool, tables,
                          n_pages, positions + 1, 0)
    ref = A.chunk_attention(q, A.paged_view(k_pool, pv),
                            A.paged_view(v_pool, pv),
                            positions[:, None])[:, 0]
    np.testing.assert_allclose(out, ref, atol=2e-6)


# --- engine-level greedy stream parity --------------------------------------

@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b", smoke=True)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _streams(cfg, params, prompts, *, decode_kernel, **kw):
    with Engine(cfg, params, num_slots=3, max_seq=64, decode_steps=4,
                decode_kernel=decode_kernel, **kw) as eng:
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run()
        return [tuple(r.out_tokens) for r in reqs]


# prompt lengths below / at / across the page_size=16 boundary, plus a
# long one that spans three pages mid-stream
PROMPTS = (15, 16, 17, 33, 5)


@pytest.mark.parametrize("quant_kv", [False, True],
                         ids=["gqa", "int8-kv"])
def test_engine_streams_bit_identical(granite, quant_kv):
    """Greedy decode through the pallas kernel emits bit-identical token
    streams to the gather oracle, fp and int8-KV alike, with prompts
    straddling page boundaries and slot contention (5 requests, 3 slots)."""
    cfg, params = granite
    if quant_kv:
        cfg = cfg.replace(quant_kv=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in PROMPTS]
    on = _streams(cfg, params, prompts, decode_kernel=True)
    off = _streams(cfg, params, prompts, decode_kernel=False)
    assert on == off


def test_engine_streams_shared_prefix_pages(granite):
    """Warm prefix-cache admissions map pages read-only (owned=False) into
    the sharers' tables; the kernel reads them through the block table
    exactly as the oracle gathers them — streams stay bit-identical."""
    cfg, params = granite
    rng = np.random.default_rng(1)
    sys_prompt = rng.integers(0, cfg.vocab_size, size=32).tolist()
    prompts = [sys_prompt + rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (3, 7, 11)]

    def run(decode_kernel):
        with Engine(cfg, params, num_slots=3, max_seq=64, decode_steps=2,
                    decode_kernel=decode_kernel, prefix_cache=True) as eng:
            warm = eng.submit(sys_prompt, max_new_tokens=4)   # registers
            eng.run()
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            eng.run()
            assert eng.pages_shared_high_water > 0, \
                "prefix shares never happened — test is vacuous"
            return [tuple(r.out_tokens) for r in (warm, *reqs)]

    assert run(True) == run(False)


def test_engine_kv_bytes_scale_with_live_tokens(granite):
    """The engine's per-step KV read accounting: under the kernel, bytes
    track live tokens and sit strictly below the gather oracle's
    num_slots*max_seq floor for short sequences."""
    cfg, params = granite
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).tolist()
               for _ in range(2)]
    per_step = {}
    for dk in (True, False):
        with Engine(cfg, params, num_slots=4, max_seq=64, decode_steps=2,
                    decode_kernel=dk) as eng:
            for p in prompts:
                eng.submit(p, max_new_tokens=8)
            eng.run()
            per_step[dk] = eng.kv_bytes_read / eng.kv_read_steps
    oracle_rows = pk.oracle_read_rows(4, 64)
    assert per_step[False] == oracle_rows * pk.kv_row_bytes(cfg)
    assert per_step[True] < per_step[False]
