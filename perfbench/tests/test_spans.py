"""The reduction of the program's spans and named scopes (`spans.py`),
on hand-made events and on a recorded slice of a chip trace
(`data/spans_slice.json`: a 61 ms stretch of a traced window of the
`granite-8b-bf16-16l.batch` cell on a TPU v5 lite, from the last 15 ms
of a tick through the host's step into the first admit call of a round;
op names cut to their instruction, `op_names` maps those instructions to
the op names of the compiled programs that ran; its `harness.window` is
the slice)."""
import json
import os

import jax
import pytest

from perfbench import run
from perfbench import spans as sp
from perfbench import trace as tr
from perfbench.tests.conftest import DATA

MS = 1e6
NEW_METRICS = ("admit.row_util", "admit.host_idle_ms", "tick.host_idle_ms",
               "tick.kv_write_ms", "admit.attention_ms")


# op names as the compiled HLO's metadata gives them (`spans.op_names`)
NAMES = {"admit": {"fusion.1": "jit(admit)/while/body/attention/dot",
                   "copy.2": "jit(admit)/copy",
                   "fusion.3": "jit(admit)/mlp/dot_general"},
         "tick": {"while.5": "jit(tick)/while",
                  "fusion.4": "jit(tick)/while/body/kv_write/scatter",
                  "fusion.6": "jit(tick)/sample/argmax",
                  "fusion.7":
                      "jit(tick)/while/body/kv_write/attention/scatter"}}


def op(name, start, dur):
    return [name, start * MS, dur * MS, {}]


def plain(trace):
    """The export without args, as `trace.reduce` reads it."""
    return {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in trace["planes"]]}


def synthetic():
    def span(name, a, b, **args):
        return [name, a * MS, (b - a) * MS, args]

    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        span("harness.window", 0, 20),
        span("engine.step", 1, 9),
        span("engine.admit", 1, 6),
        span("sched.plan_round", 1, 1.5),
        span("prefill.call", 1.5, 3, rows=10, padded=64),
        span("engine.admit_sync", 3, 5.5),
        span("engine.tick", 6, 6.5),
        span("engine.tick_sync", 6.5, 8.5),
        span("engine.emit", 8.5, 9),
        span("harness.observe", 9, 9.5),
        span("engine.step", 10, 18),
        span("engine.admit", 10, 10.2),       # planned, admitted nothing
        span("sched.plan_round", 10.05, 10.15),
        span("engine.tick", 10.2, 10.5),
        span("engine.tick_sync", 10.5, 17.5),
        span("engine.emit", 17.5, 18)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            op("jit_admit(3)", 2, 2.5), op("jit_tick(4)", 7, 1),
            op("jit_tick(4)", 11, 6)]},
        {"name": "XLA Ops", "events": [
            op("%fusion.1 = bf16[8] fusion(%p.1)", 2, 1),
            op("%copy.2", 3, 0.5),
            op("%fusion.3", 3.5, 1),
            op("%while.5", 7, 1),
            op("%fusion.4", 7, 0.5),
            op("%fusion.6", 7.5, 0.5),
            op("%fusion.7", 11, 6)]}]}
    return {"planes": [host, dev]}


def test_synthetic_by_hand():
    r = sp.reduce(synthetic(), NAMES)
    # idle: [0, 2], [4.5, 7], [8, 11], [17, 20] ms of the 20-ms window
    assert r["idle"]["seconds"] == pytest.approx(0.0105)
    by = r["idle"]["by_span"]
    want = {"harness.outside": 3.5, "sched.plan_round": 0.6,
            "prefill.call": 0.5, "engine.admit_sync": 1.0, "engine.admit": 0.6,
            "engine.tick": 0.8, "engine.tick_sync": 2.0,
            "engine.emit": 1.0, "harness.observe": 0.5}
    assert by == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    # [1, 2] + [4.5, 6] + [10, 10.2] ms lie inside engine.admit
    assert r["idle"]["in_admit"] == pytest.approx(0.0027)
    assert r["rounds"] == 1
    assert r["spans"]["prefill.call"] == {
        "count": 1, "seconds": pytest.approx(0.0015),
        "args": {"rows": 10, "padded": 64}}
    assert r["spans"]["engine.tick"]["count"] == 2
    # the outermost scope names an op; the loop that holds ops is skipped
    assert r["scopes"] == {
        "admit": {"attention": pytest.approx(0.001),
                  "mlp": pytest.approx(0.001),
                  "(none)": pytest.approx(0.0005)},
        "tick": {"kv_write": pytest.approx(0.0065),
                 "sample": pytest.approx(0.0005)}}
    assert r["none_ops"] == {"admit": [["copy.2", pytest.approx(0.0005)]]}
    assert r["unmapped"] == {}
    gaps = r["gaps"]
    assert [g[0] for g in gaps] == pytest.approx([0.003, 0.003, 0.0025,
                                                  0.002])
    assert gaps[2][1] == {k: pytest.approx(v) for k, v in {
        "engine.admit_sync": 1e-3, "engine.admit": 5e-4,
        "engine.tick": 5e-4, "engine.tick_sync": 5e-4}.items()}


def reduced(trace, names=NAMES):
    return {"spans": sp.reduce(trace, names),
            "trace": tr.reduce(plain(trace), kernels=("paged_decode",))}


def test_metrics_by_hand():
    ctx = reduced(synthetic())
    got = {m: run.reader(m)(ctx) for m in NEW_METRICS}
    assert got == {"admit.row_util": pytest.approx(100 * 10 / 64),
                   "admit.host_idle_ms": pytest.approx(2.7),
                   "tick.host_idle_ms": pytest.approx((10.5 - 2.7) / 2),
                   "tick.kv_write_ms": pytest.approx(6.5 / 2),
                   "admit.attention_ms": pytest.approx(1.0)}


def test_a_program_without_spans_or_scopes_reads_nothing():
    trace = synthetic()
    host = trace["planes"][0]["lines"][0]
    host["events"] = [e for e in host["events"] if e[0] not in sp.PROGRAM]
    unscoped = {p: {k: "jit(p)/x" for k in m} for p, m in NAMES.items()}
    ctx = reduced(trace, unscoped)
    assert all(run.reader(m)(ctx) is None for m in NEW_METRICS)
    assert set(ctx["spans"]["scopes"]["tick"]) == {"(none)"}
    # an op the compiled HLO does not name is (none), and counted
    r = sp.reduce(synthetic(), {})
    assert r["unmapped"] == {"admit": pytest.approx(0.0025),
                             "tick": pytest.approx(0.007)}


def test_recorded_slice():
    trace = json.loads((DATA / "spans_slice.json").read_text())
    r = sp.reduce(trace, trace["op_names"])
    t = tr.reduce(plain(trace), kernels=("paged_decode",))
    # the split of the idle time sums to the reduction's idle time
    idle = t["window_s"] - t["busy_s"]
    assert sum(r["idle"]["by_span"].values()) == pytest.approx(idle)
    assert r["idle"]["seconds"] == pytest.approx(idle)
    assert 0 < r["idle"]["in_admit"] < idle
    # every op of a program is in one scope: the scopes sum to the
    # program's op time, counted here on its own
    lo, hi = tr.window_of(plain(trace))
    plane = tr.device_planes(trace)[0]
    mods = [(s, s + d, tr.program_name(n))
            for n, s, d, _ in tr._line(plane, tr.MODULES_LINE)]
    own = {}
    for name, s, d, _ in tr._line(plane, tr.OPS_LINE):
        if lo <= s + d / 2 < hi and not name.startswith("%while"):
            prog = next(p for a, b, p in mods if a <= s < b)
            own[prog] = own.get(prog, 0.0) + d * 1e-9
    assert set(r["scopes"]) == set(own) == {"tick", "admit"}
    assert r["unmapped"] == {}
    for prog, secs in own.items():
        scopes = r["scopes"][prog]
        assert sum(scopes.values()) == pytest.approx(secs)
        # what no scope names is listed by instruction
        named = secs - scopes.get(sp.NONE, 0.0)
        listed = sum(v for _, v in r["none_ops"].get(prog, []))
        assert named + listed >= 0.9 * secs
    assert {"attention", "mlp", "kv_write"} <= set(r["scopes"]["admit"])
    # the longest gap, from the tick's end into the round's first call,
    # is named by the program's spans
    assert r["gaps"][0][0] > 1e-3
    assert set(r["gaps"][0][1]) <= set(sp.PROGRAM) | {
        "harness.observe", "engine.step", sp.OUTSIDE}
    assert max(r["gaps"][0][1], key=r["gaps"][0][1].get) in sp.PROGRAM


def test_a_run_without_program_spans_reads_nothing(tmp_path, monkeypatch):
    """A profile whose program marks no span reduces to None, without an
    engine being built, and every new reader reads nothing from it."""
    stale = tmp_path / "older-cell"
    stale.mkdir()
    (stale / "x.xplane.pb").write_bytes(b"")
    os.utime(stale / "x.xplane.pb", (0, 0))
    log_dir = tmp_path / "cell"
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(sp, "TRACES", tmp_path)
    assert sp.newest_trace() == str(log_dir)
    ctx = {"cfg": None}
    assert all(run.reader(m)(ctx) is None for m in NEW_METRICS)
    assert ctx["spans"] is None


def test_cell_op_names_are_the_served_engines():
    """The engine rebuilt with abstract weights compiles the same `tick`
    and `admit`, instruction for instruction, as the one the run served
    with."""
    from perfbench import system
    from perfbench.tests.conftest import load
    cfg = load("tiny")
    engine, _, _ = system.build_engine(cfg, 7)
    served = sp.op_names(engine)
    assert set(served) == {"tick", "admit"}
    assert sp.cell_op_names(cfg) == served
    assert any(sp.scope_of(n) == "kv_write" for n in served["tick"].values())
