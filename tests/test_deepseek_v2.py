"""DeepSeek-V2-Lite through the serving path, against its own float32
reference (`perfbench/reference_deepseek_v2.py`), at a small size on the
CPU: MLA without query LoRA at YaRN frequencies, a leading dense layer,
an expert layer that holds a share of the routed experts beside two
shared experts, and dropless serving.

Tolerances.  The program in float32 and the reference in float32 at
`HIGHEST` differ by summation order and by how each computes the YaRN
frequencies: about 2e-6 on logits of scale ~3.  `LOGIT_TOL` (1e-4)
leaves 50x room above that and sits 400x below what the same program
computing in bf16 reads (~4e-2, checked by the `bfloat16` case of the
engine test).  Expert layers alone compare at 1e-5 (one layer's
rounding, ~1e-7)."""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import layers as Ly
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.runtime import sampling

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = ROOT / "perfbench" / "configs" / "deepseek-v2-lite-bf16-ep4.json"
SEED = 2 ** 33 + 17
LOGIT_TOL = 1e-4
LAYER_TOL = 1e-5


def tiny(dtype="float32"):
    """The benchmark's configuration file cut to a CPU size, every
    mechanism kept: 1 dense + 2 MoE layers, experts [2, 6) of 8 held."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               intermediate_size=96, moe_intermediate_size=32,
               vocab_size=256, num_hidden_layers=3, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=4, num_experts_per_tok=3, first_expert=2,
               dtype=dtype, launcher_args=["--smoke", "--page-size", "8"],
               published={"n_routed_experts": 8})
    cfg["program"].update(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                          v_head_dim=16, num_experts=4, router_experts=8,
                          first_expert=2, experts_per_token=3, moe_d_ff=32)
    cfg["engine"] = {"slots": 4, "max_seq": 64, "num_pages": 32,
                     "page_size": 8}
    return cfg


def reference():
    from perfbench import reference_deepseek_v2
    return reference_deepseek_v2


# ---------------------------------------------------------------------------
# Engine against the reference
# ---------------------------------------------------------------------------

def served_logits(cfg, prompts, new_tokens, monkeypatch):
    """Serve `prompts` through the launcher's engine (paged, prefix cache,
    chunk 16, greedy) with weights from `system.make_params`; returns,
    per request, its tokens and the logits each token was chosen from."""
    from perfbench import system

    calls = []
    sample = sampling.sample

    def recording(logits, keys, sc):
        jax.debug.callback(lambda lg: calls.append(np.asarray(lg)), logits)
        return sample(logits, keys, sc)

    monkeypatch.setattr(sampling, "sample", recording)
    engine, _, mc = system.build_engine(cfg, SEED)
    with engine:
        reqs = [engine.submit(p, new_tokens) for p in prompts]
        engine.run()
        n_admit = engine.n_admit_calls
    assert mc.first_dense_layers == 1 and mc.n_router_experts == 8
    ticks = calls[n_admit:]
    out = []
    for p, r in zip(prompts, reqs):
        toks = list(r.out_tokens)
        assert len(toks) == new_tokens
        # every request enters in the first round, whose calls hold
        # ceil(len / chunk) rows of it; its slot is the row whose argmax
        # gave its decoded tokens
        first = calls[-(-len(p) // engine.prefill_chunk) - 1]
        slot = next(s for s in range(cfg["engine"]["slots"])
                    if [int(np.argmax(t[s])) for t in ticks[:new_tokens - 1]]
                    == toks[1:] and int(np.argmax(first[s])) == toks[0])
        out.append((toks, np.stack([first[slot]] + [
            t[slot] for t in ticks[:new_tokens - 1]])))
    return out


def reference_logits(cfg, prompts, served):
    R = reference()
    S = R.Q_CHUNK
    n = len(served[0][0])
    toks = np.zeros((4, S), np.int32)
    rows = np.zeros((4, n), np.int32)
    for i, (p, (out, _)) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        rows[i] = len(p) - 1 + np.arange(n)
    return np.asarray(R.logits_at(cfg, SEED, toks, rows, group=4)[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_logits_match_float32_reference(dtype, monkeypatch):
    """Chunked prefill across page and chunk boundaries (21 rows: chunks
    of 16 + 5 over pages of 8), then 9 ticks with a companion in the
    batch; every logit the engine sampled from against the reference's
    full forward pass.  The float32 program passes; the same program
    computing in bf16 fails the tolerance."""
    cfg = tiny(dtype)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (21, 13)]
    served = served_logits(cfg, prompts, 10, monkeypatch)
    ref = reference_logits(cfg, prompts, served)
    err = max(float(np.max(np.abs(lg - ref[i])))
              for i, (_, lg) in enumerate(served))
    if dtype == "float32":
        assert err < LOGIT_TOL, err
    else:
        assert err > 10 * LOGIT_TOL, err


# ---------------------------------------------------------------------------
# the expert layer: shares, dropless serving
# ---------------------------------------------------------------------------

def expert_layer(held=8, first=0):
    cfg = get_config("deepseek-v2-lite", smoke=True).replace(
        num_experts=held, router_experts=8, first_expert=first,
        experts_per_token=3)
    return cfg


def test_expert_shares_add_up_to_the_whole_layer():
    """Four shares of 2 of 8 experts: their routed parts, with the shared
    experts counted once, equal the reference's uncut layer."""
    R = reference()
    whole = expert_layer()
    p = moe_mod.init_moe(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, whole.d_model))
    shared = Ly.swiglu(p["shared"], x)
    total = -3 * shared
    for s in range(4):
        cfg = expert_layer(held=2, first=2 * s)
        ps = dict(p, **{k: p[k][2 * s:2 * s + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        out, _ = moe_mod.moe(ps, x, cfg, dropless=True)
        total = total + out
    m = {"k": 3, "held": 8, "first": 0, "renorm": False, "rsf": 1.0}
    with jax.default_matmul_precision("highest"):
        want = R.routed(m, x, p["router"], {k: p[k] for k in R.SWIGLU}) \
            + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=LAYER_TOL, rtol=0)


def test_serving_expert_layer_is_dropless():
    """A token's output from the serving layer does not depend on its
    batch companions; the training layer's capacity drops make it
    depend on them."""
    cfg = expert_layer()
    p = moe_mod.init_moe(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, cfg.d_model))
    # the companions all choose the experts token 0 chooses
    crowd = jnp.broadcast_to(x[:, :1], x.shape)
    for batch in (x, crowd):
        alone, _ = moe_mod.moe(p, batch[:, :1], cfg, dropless=True)
        full, _ = moe_mod.moe(p, batch, cfg, dropless=True)
        np.testing.assert_allclose(np.asarray(full[:, :1]),
                                   np.asarray(alone), atol=1e-6, rtol=0)
    alone, _ = moe_mod.moe(p, crowd[:, :1], cfg, capacity_factor=1.0)
    full, _ = moe_mod.moe(p, crowd, cfg, capacity_factor=1.0)
    assert not np.allclose(np.asarray(full[:, -1:]), np.asarray(alone),
                           atol=1e-3)


def test_shared_experts_unnormalised_topk_and_router_width():
    cfg = expert_layer(held=2, first=6)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    assert p["router"].shape == (cfg.d_model, 8)
    assert p["w_gate"].shape == (2, cfg.d_model, cfg.moe_d_ff)
    assert p["shared"]["w_gate"].shape == (cfg.d_model, 2 * cfg.moe_d_ff)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="dropless"):
        moe_mod.moe(p, x, cfg)


# ---------------------------------------------------------------------------
# YaRN, sizes, the configuration file
# ---------------------------------------------------------------------------

def test_yarn_constants():
    R = reference()
    full = get_config("deepseek-v2-lite")
    assert Ly.yarn_range(64, 10000.0, 32.0, 1.0, 4096) == (10, 23)
    assert Ly.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    m = R.dims(json.loads(CONFIG.read_text()))
    assert R.yarn_correction_range(m) == (10, 23)
    prog = Ly.yarn_freqs(full.qk_rope_dim, full.rope_theta,
                         full.rope_scaling_factor,
                         full.rope_original_max_position,
                         full.rope_beta_fast, full.rope_beta_slow)
    np.testing.assert_allclose(prog, R.yarn_inv_freq(m), rtol=1e-6)
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(prog[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(prog[23:], extra[23:] / 40, rtol=1e-6)
    assert R.softmax_scale(m) == pytest.approx(1.2608 ** 2 / 192 ** 0.5,
                                               rel=1e-4)


def test_rope_pairs_equal_the_reference_regrouping():
    """Rotating pairs (2i, 2i+1) in place gives the reference's scores,
    which regroup the pairs into halves before `rotate_half`."""
    R = reference()
    m = R.dims(json.loads(CONFIG.read_text()))
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 1, 64))
    pos = jnp.arange(40)[None]
    freqs = R.yarn_inv_freq(m)
    qp, kp = Ly.apply_rope_pairs(q, pos, freqs), \
        Ly.apply_rope_pairs(k, pos, freqs)
    got = jnp.einsum("bqhd,bkd->bhqk", qp, kp[:, :, 0])
    want = jnp.einsum("bqhd,bkd->bhqk", R._rope(q, m), R._rope(k, m)[:, :, 0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_full_param_count():
    n = get_config("deepseek-v2-lite").param_count()
    assert abs(n / 15.7e9 - 1) < 0.005, n


def test_file_states_the_program_and_the_catalog_widths():
    """The file's published keys and its `"program"` agree, every
    program leaf of the full configuration has a draw of its own, and
    the leading layer shares none with the expert layers."""
    from perfbench import system
    cfg = json.loads(CONFIG.read_text())
    prog = cfg["program"]
    assert (prog["kv_lora_rank"], prog["qk_nope_dim"], prog["qk_rope_dim"],
            prog["v_head_dim"], prog["moe_d_ff"], prog["experts_per_token"],
            prog["n_shared_experts"], prog["norm_topk_prob"]) == (
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["n_shared_experts"],
        cfg["norm_topk_prob"])
    assert prog["num_experts"] == cfg["n_routed_experts"] == 16
    assert prog["router_experts"] == cfg["published"]["n_routed_experts"]
    assert prog["first_dense_layers"] == cfg["first_k_dense_replace"]
    rs = cfg["rope_scaling"]
    assert (prog["rope_scaling_factor"], prog["rope_original_max_position"],
            prog["rope_mscale"], prog["rope_mscale_all_dim"]) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["mscale"],
        rs["mscale_all_dim"])
    mc = system.model_config(cfg, system.launcher_args(cfg))
    shapes = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    table = system.leaf_table(cfg)
    draws = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        pstr = system._path_name(path)
        name, _ = system.match(table, pstr)
        draws.setdefault(name, []).append(pstr)
    assert all(len(v) == 1 for v in draws.values()), draws
    assert all(n.startswith("dense0_")
               for n, (p,) in draws.items() if p.startswith("dense_layers."))


# ---------------------------------------------------------------------------
# every other preset lowers as before
# ---------------------------------------------------------------------------

# sha256 of the lowered programs' text (no debug info) on the CPU, smoke
# sizes, recorded on the tree before leading dense layers, shared experts,
# expert shares and YaRN were added: those must change no other preset.
# A change that means to alter one of these programs records it anew.
DIGESTS = {
    "tick:granite-8b":
        "cefb0670b962ee0227a36d04f3efd80c8b4282100fafa5a67f8cbf8ea98346fd",
    "admit:granite-8b":
        "a48574006624a49ffd0804e672e50804f9a19f8d12880a8d69cdcd817f260d0a",
    "forward:dbrx-132b":
        "1d8e54da360cdce74a4b6c3d44cae76cac6071fa2f3688acfedc7fe712a40492",
    "forward:granite-8b":
        "08eff4676453d4154b67735536c73ce193a647a11039cb7a89a43731d2673e46",
    "forward:internlm2-20b":
        "db2865d33f90daa098e494b7df7d277b8c54f44812ba75e7a75a6888fcf3115f",
    "forward:jamba-1.5-large-398b":
        "b466737a31e20599bfdd8390cb8de5429ff00df67c3a344ab1c95c3692274311",
    "forward:llama-3.2-vision-11b":
        "d294b7c2b35b96caa765b6ab39889291284fde09292183f9ba913e6e6d737096",
    "forward:minicpm3-4b":
        "be234d2198cedaa4c28a9ff65c0f64bbbbb66f145c7a17bcf6c0d2b0178fbb41",
    "forward:musicgen-large":
        "c54518c4ec73c7623afc00ac14bc919bff5f125d3fd8252ef452aee6a109b43b",
    "forward:qwen3-moe-30b-a3b":
        "c73b4667d15031b1607dcd251618191c7f8500b2cab5e4f7045a8a39c04dae2a",
    "forward:starcoder2-7b":
        "326fd5a45c761f74de90723dd0f30debcdfbb71785a54daeaf4d307a61cec281",
    "forward:xlstm-1.3b":
        "7fb242db06a2e57d03646ae0686f46af3ef4a32c04218203dbb2386070fbbb6d",
}


def digest(lowered) -> str:
    return hashlib.sha256(
        lowered.as_text(debug_info=False).encode()).hexdigest()


def engine_program(cfg, which):
    from repro.runtime.serve import Engine
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with Engine(cfg, params, num_slots=2, max_seq=64) as e:
        if which == "tick":
            return e.decode.tick.lower(e.params, e.state, e.caches)
        st, ca = e._admit_side()
        ns, C = e.prefill_slots, e.prefill_chunk
        mp, P = e.pages_per_slot, st.pages.refs.shape[0]

        def i32(*s):
            return jax.ShapeDtypeStruct(s, jnp.int32)

        def b(*s):
            return jax.ShapeDtypeStruct(s, jnp.bool_)
        return e.prefill._admit_chunk.lower(
            e.params, st, ca, i32(ns, C), b(ns), b(ns), i32(ns), i32(ns),
            i32(ns), i32(ns), i32(ns, e._stop_cap), b(ns), i32(ns, mp),
            i32(ns), i32(ns), i32(ns), i32(P), i32(P))


def forward_program(cfg):
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    if cfg.audio_frontend:
        batch = {"frame_embeds": jax.ShapeDtypeStruct((2, 16, cfg.d_model),
                                                      jnp.float32)}
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    if cfg.vision_tokens:
        batch["vision_embeds"] = jax.ShapeDtypeStruct(
            (2, cfg.vision_tokens, cfg.d_model), jnp.float32)
    return jax.jit(lambda p, bt: M.forward(p, bt, cfg)).lower(params, batch)


def test_every_other_preset_is_listed():
    assert {k.split(":")[1] for k in DIGESTS} \
        == set(ARCH_IDS) - {"deepseek-v2-lite"}


@pytest.mark.parametrize("key", sorted(DIGESTS))
def test_other_presets_lower_as_before(key):
    which, arch = key.split(":")
    cfg = get_config(arch, smoke=True)
    lowered = forward_program(cfg) if which == "forward" \
        else engine_program(cfg, which)
    assert digest(lowered) == DIGESTS[key]
