"""Pin the architecture configs to their published sizes."""
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.archs import SHAPES, all_cells, shape_applicable

ASSIGNMENT = {
    # arch: (L, d_model, H, kv, d_ff, vocab)
    "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
    "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
    "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
    "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
    "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
    "granite-8b": (36, 4096, 32, 8, 14336, 49152),
    "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
    "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    "deepseek-v2-lite": (27, 2048, 16, 16, 1408, 102400),
}

MOE = {"dbrx-132b": (16, 4), "qwen3-moe-30b-a3b": (128, 8),
       "jamba-1.5-large-398b": (16, 2), "deepseek-v2-lite": (64, 6)}


def test_all_archs_registered():
    assert set(ARCH_IDS) == set(ASSIGNMENT)


@pytest.mark.parametrize("arch", sorted(ASSIGNMENT))
def test_exact_dims(arch):
    c = get_config(arch)
    L, d, H, kv, ff, V = ASSIGNMENT[arch]
    assert c.num_layers == L and c.d_model == d
    assert c.num_heads == H and c.num_kv_heads == kv
    assert c.vocab_size == V
    if arch in MOE:
        assert (c.num_experts, c.experts_per_token) == MOE[arch]
        assert c.expert_d_ff == ff or c.d_ff == ff
    else:
        assert c.d_ff == ff


def test_param_counts_match_advertised():
    expect = {"dbrx-132b": 132, "qwen3-moe-30b-a3b": 30,
              "jamba-1.5-large-398b": 398, "minicpm3-4b": 4.3,
              "internlm2-20b": 20, "granite-8b": 8.3,
              "musicgen-large": 3.3}
    for arch, bn in expect.items():
        got = get_config(arch).param_count() / 1e9
        assert abs(got - bn) / bn < 0.12, (arch, got)


def test_moe_active_params():
    c = get_config("jamba-1.5-large-398b")
    assert abs(c.active_param_count() / 1e9 - 94) < 6      # 94B active
    q = get_config("qwen3-moe-30b-a3b")
    assert abs(q.active_param_count() / 1e9 - 3.0) < 0.6   # A3B


def test_hybrid_pattern_ratios():
    c = get_config("jamba-1.5-large-398b")
    mixers = [s.split("+")[0] for s in c.layer_pattern]
    assert mixers.count("attn") == 1 and mixers.count("mamba") == 7
    ffs = [s.split("+")[1] for s in c.layer_pattern]
    assert ffs.count("moe") == 4                            # every other
    x = get_config("xlstm-1.3b")
    mixers = [s.split("+")[0] for s in x.layer_pattern]
    assert mixers.count("mlstm") == 7 and mixers.count("slstm") == 1
    v = get_config("llama-3.2-vision-11b")
    assert [s.split("+")[0] for s in v.layer_pattern].count("xattn") == 1


def test_cells_and_applicability():
    cells = all_cells()
    assert len(cells) == 44                                 # 11 archs × 4
    skipped = [(a, s) for a, s in cells if not shape_applicable(a, s)]
    assert len(skipped) == 9                                # full-attn long_500k
    assert all(s == "long_500k" for _, s in skipped)
    assert shape_applicable("jamba-1.5-large-398b", "long_500k")
    assert shape_applicable("xlstm-1.3b", "long_500k")


def test_shapes_table():
    assert SHAPES["train_4k"] == {"seq": 4096, "batch": 256, "kind": "train"}
    assert SHAPES["long_500k"]["seq"] == 524288
    assert SHAPES["decode_32k"]["kind"] == "decode"


def test_smoke_configs_are_small():
    for arch in ARCH_IDS:
        c = get_config(arch, smoke=True)
        assert c.param_count() < 5e6, arch
        assert c.layer_pattern == get_config(arch).layer_pattern or \
            c.family in ("dense", "moe", "audio")
