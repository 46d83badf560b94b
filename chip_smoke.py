"""Smoke run of the serving main path on a TPU.

    python3 chip_smoke.py              # one chip: kernels, then serving
    python3 chip_smoke.py --chips 4    # the sharded phase alone, 4 chips

One chip, in order:
  1. report the devices; exit non-zero unless they are TPUs;
  2. `bramac_matmul` at 2/4/8-bit on granite-8b's (128, 4096) x
     (4096, 14336) projection, checked for exact integer equality against
     `ref.quant_matmul_exact`;
  3. the paged-decode kernels (bf16 and int8 KV) at the engine's shapes,
     checked against the gather oracle;
  4. granite-8b at its published widths, depth cut 36 -> 16 layers, with
     random weights from a seed, served through `Engine` as the serve
     launcher builds it: 16 requests of 64-512 prompt tokens and 64 new
     tokens, greedy, defaults on (paged KV, prefix cache, decode kernel).
     Every request must finish by budget or eos with in-vocabulary
     tokens, and every token it emits must be the argmax of a plain
     no-cache `M.forward` over its prompt and the tokens before it, up
     to near-ties.

Four chips: the full 36-layer model sharded `model=4`, with each
device's memory checked, then the 16-layer serve on the mesh and on one
chip, both streams checked against the no-cache reference.

Times, rates and memory printed before the last line are informational.
The last line of stdout is one JSON object; any failed check raises and
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-8b"
SMOKE_LAYERS = 16          # of granite-8b's 36; widths stay published
SLOTS, MAX_SEQ = 8, 2048
N_REQUESTS, NEW_TOKENS = 16, 64
PROMPT_LENS = (64, 512)
MATMUL = (128, 4096, 14336)    # granite-8b's d_model x d_ff projection
SEED = 0
GIB = 2.0 ** 30


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def devices_or_exit():
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"devices: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"no TPU found (platform {d.platform!r}); this smoke "
                 f"run has no CPU fallback")
    return devs


def timed(fn):
    """(result, seconds) of fn() with its device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(devs) -> list[int]:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devs]


def memory_line(devs) -> str:
    return ", ".join(f"dev{i} peak {b / GIB:.2f} GiB"
                     for i, b in enumerate(peak_bytes(devs)))


# ---------------------------------------------------------------------------
# phase 2: quantized matmul kernel
# ---------------------------------------------------------------------------

def phase_bramac(kind: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.quant import qrange
    from repro.kernels import ops, ref

    M, K, N = MATMUL
    one = jnp.ones((1, 1), jnp.float32)
    for bits in (2, 4, 8):
        lo, hi = qrange(bits)
        kx, kw = jax.random.split(jax.random.PRNGKey(SEED + bits))
        xq = jax.random.randint(kx, (M, K), lo, hi + 1).astype(jnp.int8)
        wq = jax.random.randint(kw, (K, N), lo, hi + 1).astype(jnp.int8)

        def run():
            return ops.quant_matmul(xq, wq, one, one, bits_a=bits,
                                    bits_w=bits)

        got, cold = timed(run)
        _, warm = timed(run)
        want = ref.quant_matmul_exact(xq, wq, one, one)
        got, want = np.asarray(got), np.asarray(want)
        # unit scales: both sides are int32 dot sums cast to f32, which
        # are exact integers below 2**24
        check(np.abs(want).max() < 2 ** 24, "sums leave f32's exact range")
        check(np.array_equal(got, want),
              f"bramac_matmul {bits}-bit differs from quant_matmul_exact "
              f"at {int((got != want).sum())} of {got.size} entries")
        print(f"bramac_matmul {bits}-bit ({M},{K})x({K},{N}): exact; "
              f"first call {cold:.2f} s (compile), warm {warm * 1e3:.3f} ms "
              f"on {kind}", flush=True)


# ---------------------------------------------------------------------------
# phase 3: paged-decode kernels
# ---------------------------------------------------------------------------

def phase_paged(cfg, kind: str) -> None:
    """Both decode kernels at the engine's attention shapes: `cfg`'s
    heads, `SLOTS` sequences, `MAX_SEQ` rows of `cfg.page_size` pages,
    read at layer 1 of pools stacked over two layers."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pk
    from repro.models import attention as A

    B, H, Hkv, hd, ps = SLOTS, cfg.num_heads, cfg.num_kv_heads, cfg.hd, \
        cfg.page_size
    mp = MAX_SEQ // ps
    P = B * mp
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    layer = 1
    k_pool = jax.random.normal(ks[0], (2, P, ps, Hkv, hd)).astype(
        jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (2, P, ps, Hkv, hd)).astype(
        jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, 1, H, hd)).astype(jnp.bfloat16)
    tables = jax.random.permutation(ks[3], P).reshape(B, mp).astype(
        jnp.int32)
    # below, at and across page boundaries, up to a full max_seq row
    lengths = jnp.asarray([1, ps, ps + 1, MAX_SEQ // 7, MAX_SEQ // 2,
                           3 * MAX_SEQ // 4, MAX_SEQ - 1, MAX_SEQ],
                          jnp.int32)
    n_pages = (lengths + ps - 1) // ps
    pos = (lengths - 1)[:, None]
    pv = A.PagedKV(tables=tables, n_pages=n_pages,
                   write_mask=jnp.ones((B,), bool), max_seq=MAX_SEQ,
                   page_size=ps, layer=layer)
    v_max = float(jnp.max(jnp.abs(v_pool[layer].astype(jnp.float32))))

    got, cold = timed(lambda: pk.paged_decode(q[:, 0], k_pool, v_pool,
                                              tables, n_pages, lengths,
                                              layer))
    _, warm = timed(lambda: pk.paged_decode(q[:, 0], k_pool, v_pool,
                                            tables, n_pages, lengths, layer))
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        want = A.chunk_attention(q.astype(f32),
                                 A.paged_view(k_pool.astype(f32), pv),
                                 A.paged_view(v_pool.astype(f32), pv),
                                 pos)[:, 0]
    got, want = np.asarray(got, np.float32), np.asarray(want)
    # The oracle runs in f32 on the same bf16 values.  The kernel rounds
    # its output to bf16 (<= 2**-9 relative) and its f32 dots may take
    # the MXU's single bf16 pass, rounding each probability to bf16
    # before the PV product (<= 2**-9 * max|v| absolute).  Allow twice
    # the sum.
    tol = 2 ** -8 * (np.abs(want) + v_max)
    err = np.abs(got - want)
    check(bool(np.all(err <= tol)),
          f"paged_decode off the oracle: max err {err.max():.3g}, "
          f"worst err/tol {(err / tol).max():.3g}")
    print(f"paged_decode bf16 B={B} H={H} Hkv={Hkv} hd={hd} page={ps} "
          f"max_seq={MAX_SEQ}: max err {err.max():.3g} "
          f"(tolerance 2^-8*(|ref|+max|v|)); first call {cold:.2f} s, "
          f"warm {warm * 1e3:.3f} ms on {kind}", flush=True)

    kq, kss = A._quant_rows(k_pool)
    vq, vss = A._quant_rows(v_pool)
    qq, qs = A._quant_rows(q)
    got, cold = timed(lambda: pk.paged_decode_q(
        qq[:, 0], qs[:, 0], kq, kss, vq, vss, tables, n_pages, lengths,
        layer, q.dtype))
    cache = {"k": kq, "ks": kss, "v": vq, "vs": vss}
    view = {key: A.paged_view(cache[key], pv) for key in cache}
    want = A.decode_attention_q(q, view, pos)[:, 0]
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    # Both sides run the same integer dots and bf16 output rounding; the
    # softmax normalizer is summed in another order, which can move a
    # requantized probability across a rounding boundary.  One such flip
    # moves an output by at most max|v| / 127; allow two, plus the
    # bf16 rounding of both outputs.
    tol = 2 ** -8 * np.abs(want) + 2 * v_max / 127
    err = np.abs(got - want)
    check(bool(np.all(err <= tol)),
          f"paged_decode_q off the oracle: max err {err.max():.3g}, "
          f"worst err/tol {(err / tol).max():.3g}")
    print(f"paged_decode_q int8 KV: max err {err.max():.3g} "
          f"(tolerance 2^-8*|ref| + 2*max|v|/127); first call {cold:.2f} s "
          f"on {kind}", flush=True)


# ---------------------------------------------------------------------------
# phase 4: serving
# ---------------------------------------------------------------------------

def serve_args(shard: str = ""):
    """The launcher's own command line for this smoke configuration."""
    from repro.launch import serve
    argv = ["--arch", ARCH, "--slots", str(SLOTS), "--max-seq", str(MAX_SEQ),
            "--requests", str(N_REQUESTS), "--new-tokens", str(NEW_TOKENS),
            "--seed", str(SEED)]
    if shard:
        argv += ["--shard", shard]
    return serve.parser().parse_args(argv)


def make_prompts(cfg, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
            for n in lens]


def serve(eng, cfg, prompts) -> tuple[list[tuple], float]:
    """Serve `prompts` to completion; returns (token streams, seconds)."""
    reqs = [eng.submit(p, NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0          # run() ends on a host sync
    check(all(r.done for r in reqs), "a request did not finish")
    by_uid = {r.uid: r for r in results}
    streams = []
    for req in reqs:
        res = by_uid[req.uid]
        check(res.finish_reason in ("budget", "eos"),
              f"request {req.uid} finished by {res.finish_reason!r}")
        toks = np.asarray(res.tokens)
        check(len(toks) == NEW_TOKENS or res.finish_reason == "eos",
              f"request {req.uid} stopped at {len(toks)} tokens")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"request {req.uid} emitted tokens outside the vocabulary")
        streams.append(tuple(int(t) for t in toks))
    return streams, dt


def check_streams(params, cfg, prompts, streams, label: str) -> None:
    """Teacher-forced check of every emitted token against a plain
    no-cache `M.forward` over prompt + stream: each token must be the
    reference argmax at its position unless it is a near-tie there.

    The engine (chunked prefill, paged cache, decode kernel) and the
    reference (one causal pass) run different bf16 op orders, so their
    logits differ by a few bf16 ulps; bf16 keeps 8 significant bits, so
    4 ulps at the top logit is at most 2**-5 * |top| (2**-5 below
    magnitude 1).  A token other than the argmax passes only if the
    reference puts its logit within that bound of the top.  Feeding the
    engine's own tokens back keeps one near-tie from derailing the rest
    of the stream's check."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    # The scored rows go to the host whole: argmax, top and the emitted
    # token's logit must come from one copy of the logits, which a fused
    # on-device reduction does not promise (XLA may skip the bf16
    # rounding in one fusion and keep it in another).
    @jax.jit
    def scored_rows(p, toks, rows):
        logits = M.forward(p, {"tokens": toks}, cfg)[0]
        return jnp.take_along_axis(logits, rows[..., None],
                                   axis=1).astype(jnp.float32)

    # one width for every batch (one compile); causal attention keeps the
    # right padding out of every scored row
    width, batch = PROMPT_LENS[1] + NEW_TOKENS, 4
    first = exact = total = 0
    worst = 0.0
    for lo in range(0, len(prompts), batch):
        toks = np.zeros((batch, width), np.int32)
        rows = np.zeros((batch, NEW_TOKENS), np.int32)
        for r, (p, s) in enumerate(zip(prompts[lo:lo + batch],
                                       streams[lo:lo + batch])):
            seq = np.concatenate([p, np.asarray(s[:-1], np.int32)])
            toks[r, :len(seq)] = seq
            rows[r, :len(s)] = len(p) - 1 + np.arange(len(s))
        logits = np.asarray(scored_rows(params, jnp.asarray(toks),
                                        jnp.asarray(rows)))
        for r, s in enumerate(streams[lo:lo + batch]):
            row, s = logits[r, :len(s)], np.asarray(s)
            top = row.max(axis=-1)
            gap = top - row[np.arange(len(s)), s]
            bound = 2 ** -5 * np.maximum(1.0, np.abs(top))
            bad = np.flatnonzero(gap > bound)
            check(bad.size == 0,
                  f"{label}: request {lo + r} token {bad[:1]} is "
                  f"{gap[bad[:1]]} below the reference top logit, bound "
                  f"{bound[bad[:1]]}")
            hit = row.argmax(axis=-1) == s
            first += int(hit[0])
            exact += int(hit.sum())
            total += len(s)
            worst = max(worst, float(np.max(gap / bound)))
    print(f"{label}: reference argmax on {first}/{len(streams)} first "
          f"tokens and {exact}/{total} tokens; the rest are near-ties "
          f"(worst gap {worst:.3g} of its bound)", flush=True)


def phase_serve(devs) -> None:
    import jax
    from repro.launch import serve as launch
    from repro.runtime.serve import Engine

    kind = devs[0].device_kind
    args = serve_args()
    cfg = launch.model_config(args).replace(num_layers=SMOKE_LAYERS)
    print(f"config: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}; depth cut 36 -> "
          f"{cfg.num_layers} layers; {cfg.param_count() / 1e9:.2f}B params; "
          f"slots={SLOTS} max_seq={MAX_SEQ} page={cfg.page_size}",
          flush=True)
    options = launch.engine_options(args)
    params, t_init = timed(lambda: launch.init_params(
        cfg, jax.random.PRNGKey(SEED), options.parallel.mesh))
    print(f"init_params: {t_init:.1f} s", flush=True)
    prompts = make_prompts(cfg, SEED)
    warm_prompts = make_prompts(cfg, SEED + 1)
    with Engine(cfg, params, options=options) as eng:
        print(f"engine: decode_kernel={eng.decode_kernel} "
              f"kv_layout={eng.kv_layout} num_pages={eng.num_pages} "
              f"prefix_cache={eng.prefix is not None}", flush=True)
        check(eng.decode_kernel, "the engine did not enable the paged "
              "decode kernel on TPU")
        streams, cold = serve(eng, cfg, prompts)
        warm_streams, warm = serve(eng, cfg, warm_prompts)
        n_tok = sum(len(s) for s in warm_streams)
        print(f"serve: {N_REQUESTS} requests x {NEW_TOKENS} new tokens; "
              f"first pass {cold:.1f} s (includes compiles); warm pass "
              f"{n_tok} tokens in {warm:.2f} s = {n_tok / warm:.1f} tok/s "
              f"on {kind}", flush=True)
        print(f"memory: {memory_line(devs[:1])}", flush=True)
    check_streams(params, cfg, prompts, streams, "1 chip")
    check_streams(params, cfg, warm_prompts, warm_streams, "1 chip, warm")


# ---------------------------------------------------------------------------
# four chips: sharded serving
# ---------------------------------------------------------------------------

def phase_sharded(devs) -> None:
    import jax
    from repro.launch import serve as launch
    from repro.runtime.serve import Engine

    kind = devs[0].device_kind
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    sharded = serve_args(shard="model=4")
    one_chip = serve_args()

    # full depth first, so each device's peak is this model's alone
    cfg = launch.model_config(sharded)
    options = launch.engine_options(sharded)
    params, t_init = timed(lambda: launch.init_params(
        cfg, jax.random.PRNGKey(SEED), options.parallel.mesh))
    share = cfg.param_count() * 2 / len(devs)          # bf16 bytes/device
    prompts = make_prompts(cfg, SEED)
    with Engine(cfg, params, options=options) as eng:
        _, dt = serve(eng, cfg, prompts)
    peaks = peak_bytes(devs)
    print(f"{cfg.num_layers} layers on model=4: init {t_init:.1f} s, "
          f"served {N_REQUESTS} requests in {dt:.1f} s (includes "
          f"compiles); weights {share / GIB:.2f} GiB per device; "
          f"{memory_line(devs)}", flush=True)
    check(min(peaks) >= share, "a device holds less than its weight share")
    check(max(peaks) <= 1.25 * min(peaks),
          "device peaks are uneven: something is staged on one device")
    del params, eng
    gc.collect()

    cfg = launch.model_config(one_chip).replace(num_layers=SMOKE_LAYERS)
    params = launch.init_params(cfg, jax.random.PRNGKey(SEED))
    with Engine(cfg, params,
                options=launch.engine_options(one_chip)) as eng:
        ref_streams, _ = serve(eng, cfg, prompts)
    with Engine(cfg, params,
                options=launch.engine_options(sharded)) as eng:
        tp_streams, dt = serve(eng, cfg, prompts)
    check_streams(params, cfg, prompts, ref_streams, "1 chip")
    check_streams(params, cfg, prompts, tp_streams, "model=4")
    agree = [next((i for i, (a, b) in enumerate(zip(r, t)) if a != b),
                  len(r)) for r, t in zip(ref_streams, tp_streams)]
    print(f"{SMOKE_LAYERS} layers, model=4 vs 1 chip: streams identical on "
          f"{sum(a == len(r) for a, r in zip(agree, ref_streams))}/"
          f"{N_REQUESTS} requests, mean common prefix "
          f"{np.mean(agree):.1f}/{NEW_TOKENS} tokens; sharded serve "
          f"{dt:.1f} s (includes compiles) on {kind}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels and serving on one chip (default); "
                         "4: the sharded serving phase only")
    args = ap.parse_args()
    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache

    devs = devices_or_exit()
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    kind = devs[0].device_kind
    if args.chips == 4:
        phase_sharded(devs)
    else:
        phase_bramac(kind)
        phase_paged(get_config(ARCH), kind)
        phase_serve(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
