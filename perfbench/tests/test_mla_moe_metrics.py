"""The DeepSeek-V2 cell's per-layer metrics: its FLOP count
(`flops_mla_moe.py`) against a hand sum, the inner-scope reduction
(`scope_paths.py`) on hand-made events, and the five readers."""
import json
import types

import pytest

from perfbench import flops_mla_moe as F
from perfbench import run
from perfbench import scope_paths as sc
from perfbench.tests.conftest import ROOT

MS = 1e6
CELL = "deepseek-v2-lite-bf16-ep4.batch"
METRICS = ("tick.mla_attention_ms", "tick.moe_route_ms",
           "tick.moe_experts_ms", "admit.moe_experts_ms", "step.mfu.mla_moe")


def config():
    return json.loads(
        (ROOT / "perfbench/configs/deepseek-v2-lite-bf16-ep4.json").read_text())


def test_flops_by_hand():
    cfg = config()
    mla = (2048 * 16 * 192          # query
           + 2048 * (512 + 64)      # latent and rope key
           + 512 * 16 * 256         # key and value up-projections
           + 16 * 128 * 2048)       # output
    assert mla == 13_762_560
    dense = 3 * 2048 * 10944
    moe = (2048 * 64                # router over the published 64
           + 3 * 2048 * 2816        # two shared experts
           + 3 * 2048 * 1408 * 6 * 16 / 64)   # 6 of 64 chosen, 16 held
    lw = 27 * mla + 1 * dense + 26 * moe
    assert F.linear_weights(cfg) == lw == 1_229_455_360
    attn = 2 * 16 * (192 + 128)     # per attended position and layer
    head = 2 * 2048 * 102400
    assert F.token_flops(cfg, 100, True) == 2 * lw + 27 * attn * 100 + head
    assert F.token_flops(cfg, 100, False) == 2 * lw + 27 * attn * 100
    assert F.prefill_flops(cfg, 3) == 2 * lw * 3 + 27 * attn * 6 + head


NAMES = {"tick": {"fusion.1": "jit(tick)/while/body/attention/dot_general",
                  "fusion.2": "jit(tick)/while/body/mlp/route/dot_general",
                  "fusion.3":
                      "jit(tick)/while/body/mlp/experts/dot_general",
                  "fusion.4": "jit(tick)/while/body/mlp/shared/dot_general",
                  "fusion.5": "jit(tick)/while/body/mlp/dot_general"},
         "admit": {"fusion.6":
                       "jit(admit)/while/body/mlp/experts/dot_general",
                   "fusion.7": "jit(admit)/while/body/mlp/route/top_k"}}


def op(name, start, dur):
    return [name, start * MS, dur * MS, {}]


def synthetic():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        op("harness.window", 0, 20), op("engine.tick", 1, 2),
        op("engine.admit", 9, 1)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            op("jit_tick(4)", 1, 6), op("jit_admit(3)", 10, 5),
            op("jit_tick(4)", 16, 3)]},
        {"name": "XLA Ops", "events": [
            op("%while.9", 1, 6),
            op("%fusion.1", 1, 2), op("%fusion.2", 3, 0.5),
            op("%fusion.3", 3.5, 2), op("%fusion.4", 5.5, 0.5),
            op("%fusion.5", 6, 1), op("%fusion.6", 10, 4),
            op("%fusion.7", 14, 1), op("%fusion.3", 16, 1),
            op("%fusion.2", 17, 0.25), op("%fusion.3", 25, 1)]}]}
    return {"planes": [host, dev]}


def test_path_of_matches_whole_scope_names():
    assert sc.path_of("jit(tick)/mlp/route/dot") == "mlp/route"
    assert sc.path_of("jit(tick)/mlp/experts") == "mlp/experts"
    assert sc.path_of("jit(tick)/mlp/router/dot") is None
    assert sc.path_of("jit(tick)/mlp/dot") is None


def test_reduce_by_hand():
    got = sc.reduce(synthetic(), NAMES)
    # the op past the window (25 ms) and the loop that holds ops are left out
    assert got == {"tick": {"mlp/route": pytest.approx(0.00075),
                            "mlp/experts": pytest.approx(0.003),
                            "mlp/shared": pytest.approx(0.0005)},
                   "admit": {"mlp/experts": pytest.approx(0.004),
                             "mlp/route": pytest.approx(0.001)}}


def test_readers_by_hand():
    cfg = config()
    tracked = [types.SimpleNamespace(req=types.SimpleNamespace(
        prompt=[0] * 100), stamps=[1.5])]
    win = types.SimpleNamespace(t_open=1.0, t_end=3.0, tracked=tracked,
                                ticks=[[200, 300], [201]])
    ctx = {"cfg": cfg, "chips": 1, "window": win,
           "peaks": {"bf16_flops": 197e12},
           "spans": {"scopes": {"tick": {"attention": 0.002}}},
           "scope_paths": sc.reduce(synthetic(), NAMES),
           "trace": {"programs": {"tick": {"count": 2, "seconds": 0.009},
                                  "admit": {"count": 1, "seconds": 0.005}}}}
    got = {m: run.reader(m)(ctx) for m in METRICS}
    flops = F.prefill_flops(cfg, 100) + sum(
        F.token_flops(cfg, n, True) for n in (200, 300, 201))
    assert got == {"tick.mla_attention_ms": pytest.approx(1.0),
                   "tick.moe_route_ms": pytest.approx(0.375),
                   "tick.moe_experts_ms": pytest.approx(1.5),
                   "admit.moe_experts_ms": pytest.approx(4.0),
                   "step.mfu.mla_moe": pytest.approx(
                       100 * flops / (2.0 * 197e12))}


def test_a_program_without_spans_reads_nothing():
    ctx = {"spans": None, "scope_paths": None,
           "trace": {"programs": {"tick": {"count": 2, "seconds": 1.0}}}}
    assert all(run.reader(m)(ctx) is None for m in METRICS[:4])


def test_benchmark_lists_the_readers_for_the_cell_only():
    bench = run.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == [CELL]
    cell = run.find(bench["workloads"], CELL, "workload")
    assert [m["name"] for m in run.metrics_of(bench, cell, 1)] == \
        list(METRICS)
