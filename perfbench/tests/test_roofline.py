"""Byte and FLOP counts against sums worked out by hand."""
import pytest

from perfbench import roofline as RF
from perfbench.tests.conftest import load

# granite-8b at its published widths, 16 layers, bf16
G = {"num_hidden_layers": 16, "hidden_size": 4096, "num_attention_heads": 32,
     "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
     "vocab_size": 49152, "weights": "bf16"}
# per layer: q 4096x4096, k/v 4096x1024 each, o 4096x4096, 3 x 4096x14336
LAYER_PARAMS = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336


def test_kv_row_and_pages():
    assert RF.kv_row_bytes(G) == 2 * 8 * 128 * 2          # K and V, bf16
    assert RF.read_rows([1, 16, 17, 0], 16) == 16 + 16 + 32
    assert RF.read_rows([], 16) == 0


def test_weight_bytes_bf16_and_int8():
    head = 2 * 4096 * 49152
    norms = 16 * 2 * 4096 * 2 + 2 * 4096
    assert RF.weight_bytes(G) == 16 * 2 * LAYER_PARAMS + head + norms
    q = dict(G, weights="int8")
    scales = 16 * 4 * (4096 + 1024 + 1024 + 4096 + 2 * 14336 + 4096)
    assert RF.weight_bytes(q) == (16 * LAYER_PARAMS + scales + 4096 * 49152
                                  + 49152 * 4 + norms)


def test_tick_and_kernel_bytes():
    lengths = [100, 17]                  # 7 + 2 pages of 16 rows
    kv = 2 * 8 * 128 * 2 * 16 * (144 + 2)  # 144 rows read, 2 written
    assert RF.tick_bytes(G, lengths, 16, 32) == (RF.weight_bytes(G) + kv
                                                 + 32 * 4096 * 2)
    qo = 2 * 32 * 32 * 128 * 2
    assert RF.paged_decode_bytes(G, lengths, 16, 32) == 16 * (
        2 * 8 * 128 * 2 * 144 + qo)


def test_flops():
    attn = 4 * 32 * 128
    assert RF.token_flops(G, 10, False) == 16 * (2 * LAYER_PARAMS
                                                 + attn * 10)
    assert RF.token_flops(G, 1, True) - RF.token_flops(G, 1, False) == \
        2 * 4096 * 49152
    # a prefill of n tokens is n tokens at contexts 1..n, logits once
    n = 5
    assert RF.prefill_flops(G, n) == sum(
        RF.token_flops(G, c, False) for c in range(1, n + 1)) \
        + 2 * 4096 * 49152


def test_tiny_config_counts_agree_with_its_shapes():
    cfg = load("tiny")
    assert RF.kv_row_bytes(cfg) == 2 * 2 * 16 * 2
    with pytest.raises(KeyError):
        RF.weight_bytes({k: v for k, v in cfg.items() if k != "weights"})
