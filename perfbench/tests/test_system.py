"""The seeded weight builder, on the CPU at smoke sizes: the dense tree is
`weights.draw` of each leaf at layers 0..L-1, and a configuration file
with a period-2 `layer_pattern`, its own leaves, `ModelConfig` fields and
weight kinds (`data/hybrid.json`) builds with no change to the harness,
every stacked row drawn at its absolute layer."""
import time

import jax
import numpy as np
import pytest

from perfbench import correct, run, system
from perfbench import weights as W
from perfbench.tests.conftest import load

SEED = 2 ** 33 + 5


def params_of(cfg):
    mc = system.model_config(cfg, system.launcher_args(cfg))
    return mc, system.make_params(cfg, mc, SEED)


def flat(tree):
    return {system._path_name(p): a for p, a in
            jax.tree_util.tree_leaves_with_path(tree)}


def expected(cfg, pstr, layer, shape, dtype):
    name, kind = system.match(system.leaf_table(cfg), pstr)
    return system.drawer(cfg)(W.root_key(SEED), name, layer, shape, kind,
                              dtype)


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


# granite's program leaves and the reference leaves they are drawn as
DENSE = {
    "embed.embedding": ("embedding", "embed"),
    "embed.unembed": ("unembed", "matrix"),
    "final_norm.scale": ("final_norm", "norm"),
    "layers.pos0.norm1.scale": ("attn_norm", "norm"),
    "layers.pos0.norm2.scale": ("mlp_norm", "norm"),
    **{f"layers.pos0.mixer.{n}": (n, "matrix")
       for n in ("wq", "wk", "wv", "wo")},
    **{f"layers.pos0.mlp.{n}": (n, "matrix")
       for n in ("w_gate", "w_up", "w_down")},
}


def test_dense_tree_is_the_layer_draws():
    cfg = load("tiny")
    _, params = params_of(cfg)
    leaves = flat(params)
    assert set(leaves) == set(DENSE)
    root = W.root_key(SEED)
    L = cfg["num_hidden_layers"]
    for pstr, got in leaves.items():
        name, kind = DENSE[pstr]
        if pstr.startswith("layers."):
            assert got.shape[0] == L
            for i in range(L):
                want = W.draw(root, name, i, got.shape[1:], kind, got.dtype)
                assert same_bits(got[i], want), (pstr, i)
        else:
            want = W.draw(root, name, 0, got.shape, kind, got.dtype)
            assert same_bits(got, want), pstr


def test_period_two_rows_are_absolute_layers():
    cfg = load("hybrid")
    engine, params, mc = system.build_engine(cfg, SEED)
    engine.close()
    period = len(mc.layer_pattern)
    assert period == 2 and mc.n_periods == 2
    leaves = flat(params)
    drawn = {}
    for pstr, got in leaves.items():
        if not pstr.startswith("layers."):
            continue
        k = int(pstr.split(".")[1][3:])
        name, _ = system.match(system.leaf_table(cfg), pstr)
        for j in range(got.shape[0]):
            layer = j * period + k
            want = expected(cfg, pstr, layer, got.shape[1:], got.dtype)
            assert same_bits(got[j], want), (pstr, j)
            assert (name, layer) not in drawn, (pstr, drawn[name, layer])
            drawn[name, layer] = pstr
    # the norms sit at both positions: four layers, four different draws
    norms = [np.asarray(leaves[f"layers.pos{k}.norm1.scale"][j])
             for j in range(2) for k in range(2)]
    assert len({n.tobytes() for n in norms}) == 4


def test_period_two_dense_decoder_is_correct():
    """The dense decoder scanned two layers a period serves what the dense
    reference, which draws layer i for layer i, computes: a row drawn at
    any other layer would fail the limit test_control.py sets."""
    cfg = dict(load("small"), program={"layer_pattern": ["attn+dense"] * 2})
    r = run.run_cell("small.p2.test", cfg, load("batch"),
                     [{"name": "output_tok_s", "unit": "u"}], SEED, 3.0, 0,
                     jax.devices()[:1], 0.002, time.perf_counter())
    assert r["correct"], r["checks"]


def test_file_keys_take_effect():
    cfg = load("hybrid")
    mc, params = params_of(cfg)
    # "program": the architecture's own fields; head_dim defaults to d/H
    assert mc.layer_pattern == ("attn+moe", "mamba+dense")
    assert (mc.num_experts, mc.experts_per_token, mc.moe_d_ff,
            mc.mamba_d_state) == (4, 2, 48, 4)
    assert mc.head_dim is None and mc.hd == 16
    leaves = flat(params)
    root = W.root_key(SEED)
    # "leaves": the longest suffix wins over the dense table's `w_gate`
    gate = leaves["layers.pos0.moe.w_gate"]
    assert same_bits(gate[1], W.draw(root, "expert_w_gate", 2,
                                     gate.shape[1:], "matrix", gate.dtype))
    assert not same_bits(gate[1], W.draw(root, "w_gate", 2, gate.shape[1:],
                                         "matrix", gate.dtype))
    # KINDS of the reference module the file names
    kinds = correct.reference(cfg).KINDS
    conv_b = leaves["layers.pos1.mixer.conv_b"]
    assert same_bits(conv_b[1], kinds["small"](
        W.leaf_key(root, "ssm_conv_bias", 3), conv_b.shape[1:],
        conv_b.dtype))
    assert np.all(np.asarray(leaves["layers.pos1.mixer.d_skip"]) == 1)
    a_log = np.asarray(leaves["layers.pos1.mixer.a_log"])
    np.testing.assert_array_equal(
        a_log[0, 0], np.log(np.arange(1, 5, dtype=np.float32)))


def test_unmatched_leaf_names_itself():
    cfg = load("hybrid")
    del cfg["leaves"]["mixer.a_log"]
    with pytest.raises(ValueError, match=r"layers\.pos1\.mixer\.a_log"):
        params_of(cfg)


@pytest.mark.parametrize("field", ["num_layers", "d_model", "head_dim",
                                   "dtype"])
def test_program_cannot_restate_a_dense_field(field):
    """One file states one model: the reference and the roofline counts
    read the dense keys, so `"program"` may not set what they set."""
    cfg = load("hybrid")
    cfg["program"][field] = 8
    with pytest.raises(ValueError, match=field):
        system.model_config(cfg, system.launcher_args(cfg))
