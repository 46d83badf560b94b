"""The reference that decides `correct` is the module the configuration
file names, for a run (`correct.check`) and for calibration
(`correct.gaps` through `calibrate.reading`), and a file's `"kernels"`
reach the trace reduction of a traced run."""
import contextlib
import json
import time

import jax
import numpy as np
import pytest

from perfbench import calibrate, correct, driver, peaks, run
from perfbench import trace as tr
from perfbench.tests.conftest import DATA, load

SEED = 2 ** 33 + 11


def streams_of(vocab, n=5, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        prompt = rng.integers(0, vocab, rng.integers(8, 33)).astype(np.int32)
        n_out = rng.integers(8, 41)
        out.append((prompt, list(rng.integers(0, vocab, n_out))))
    return out


def recorder(cfg):
    mod = correct.reference(cfg)
    mod.CALLS.clear()
    return mod


def test_check_and_gaps_call_the_named_module():
    cfg, mix = load("hybrid"), load("batch")
    mod = recorder(cfg)
    streams = streams_of(cfg["vocab_size"])
    S, Rn = correct.pads(mix, cfg)
    assert S % mod.Q_CHUNK == 0 and S % 128 != 0         # its own Q_CHUNK
    checks = correct.check(cfg, SEED, streams, [len(o) for _, o in streams],
                           mix, cfg["vocab_size"], 0.001)
    assert checks["mean_logit_gap"]["value"] == 0.0
    prog, ctl = correct.gaps(cfg, SEED, streams, S, Rn, control=(8, 8))
    assert np.nanmax(prog) == 0.0 and np.nanmax(ctl) == 0.0
    assert [(c["seed"], c["tokens"], c["quant"]) for c in mod.CALLS] == [
        (SEED, (8, S), None), (SEED, (8, S), None), (SEED, (8, S), (8, 8))]


def test_default_reference_is_the_dense_module():
    from perfbench import reference
    cfg, mix = load("tiny"), load("batch")
    assert "reference" not in cfg
    streams = streams_of(cfg["vocab_size"])
    budgets = [len(o) for _, o in streams]
    absent = correct.check(cfg, SEED, streams, budgets, mix,
                           cfg["vocab_size"], 1.0)["mean_logit_gap"]["value"]
    named = dict(cfg, reference="perfbench/reference.py")
    assert correct.reference(named).Q_CHUNK == reference.Q_CHUNK
    value = correct.check(named, SEED, streams, budgets, mix,
                          cfg["vocab_size"], 1.0)["mean_logit_gap"]["value"]
    assert absent > 0 and value == absent


def test_calibration_reads_the_named_module():
    cfg, mix = load("hybrid"), load("batch")
    mod = recorder(cfg)
    r = calibrate.reading(cfg, mix, SEED, 2.0, driver.CompileCounter(), True)
    assert r["program"] == 0.0 and r["control"] == 0.0
    assert [c["quant"] for c in mod.CALLS] == [None,
                                               calibrate.CONTROL["bf16"]]


def test_run_reduces_the_files_kernels(monkeypatch):
    """A traced run of the hybrid configuration, the profiler stubbed out:
    the file's kernel names reach `trace.reduce` and its reference decides
    `correct`."""
    cfg = load("hybrid")
    mod = recorder(cfg)
    seen = {}

    def reduce(trace, kernels=()):
        seen["kernels"] = kernels
        return {"busy_s": 1.0, "window_s": 2.0, "device_ops": [],
                "idle_gaps": [], "programs": {}, "kernels": {}}

    monkeypatch.setattr(tr, "capture", lambda d: contextlib.nullcontext())
    monkeypatch.setattr(tr, "export", lambda d: {})
    monkeypatch.setattr(tr, "reduce", reduce)
    monkeypatch.setattr(peaks, "for_kind", lambda kind: {})
    r = run.run_cell("hybrid.test", cfg, load("batch"), [], SEED, 2.0, 1,
                     jax.devices()[:1], 0.001, time.perf_counter())
    assert seen["kernels"] == ("paged_decode", "add_rsqrt_fusion")
    assert r["correct"] and len(mod.CALLS) == 1


@pytest.mark.parametrize("cfg,extra", [({}, None),
                                       ({"kernels": ["paged_decode",
                                                     "add_rsqrt_fusion"]},
                                        "add_rsqrt_fusion")])
def test_kernel_names_on_the_recorded_slice(cfg, extra):
    """On the recorded v5e slice the default reduces `paged_decode` as
    before (16 calls, one a layer), and an added name (here the RMSNorm
    fusion, standing for a new kernel's instruction name) gets an entry
    of its own."""
    trace = json.loads((DATA / "trace_slice.json").read_text())
    got = tr.reduce(trace, kernels=run.kernels(cfg))["kernels"]
    base = tr.reduce(trace, kernels=("paged_decode",))["kernels"]
    assert got["paged_decode"] == base["paged_decode"]
    assert base["paged_decode"]["count"] == 16
    assert set(got) == {"paged_decode"} | ({extra} if extra else set())
    if extra:
        assert got[extra]["count"] > 0 and got[extra]["seconds"] > 0
