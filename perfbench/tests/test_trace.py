"""The trace reduction, on hand-made events and on a recorded slice of a
chip trace (`data/trace_slice.json`: the first 40 ms of a window of the
`granite-8b-bf16-16l.batch` cell on a TPU v5 lite, op names cut to
120 characters)."""
import json

import numpy as np
import pytest

from perfbench import trace as tr
from perfbench.tests.conftest import DATA


def synthetic():
    ms = 1e6
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["harness.window", 0.0, 10 * ms],
        ["engine.step", 1 * ms, 4 * ms],
        ["harness.idle", 6 * ms, 3 * ms]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_admit(7)", 1 * ms, 2 * ms],
            ["jit_tick(9)", 3.5 * ms, 1 * ms],
            ["jit_tick(9)", 11 * ms, 1 * ms]]},        # after the window
        {"name": "XLA Ops", "events": [
            ["%while.9 = (s32[]) while(...)", 1 * ms, 2 * ms],
            ["%fusion.1 = bf16[8] fusion(%_paged_decode.1)", 1 * ms,
             1.5 * ms],
            ["fusion.2", 2 * ms, 1 * ms],              # overlaps fusion.1
            ["paged_decode_kernel", 3.5 * ms, 0.5 * ms],
            ["copy.3", 4 * ms, 0.5 * ms],
            ["fusion.4", 11 * ms, 1 * ms]]}]}          # after the window
    return {"planes": [host, dev, {"name": "/device:CPU:0", "lines": []}]}


def test_synthetic_by_hand():
    r = tr.reduce(synthetic(), kernels=("paged_decode",))
    assert r["window_s"] == pytest.approx(0.010)
    # busy: [1, 3] ms and [3.5, 4.5] ms
    assert r["busy_s"] == pytest.approx(0.003)
    assert r["programs"]["admit"] == {"seconds": pytest.approx(0.002),
                                      "count": 1}
    assert r["programs"]["tick"]["count"] == 1
    assert r["kernels"]["paged_decode"]["seconds"] == pytest.approx(0.0005)
    ops = dict(r["device_ops"])
    assert ops["admit/fusion.1"] == pytest.approx(0.0015)
    assert ops["tick/copy.3"] == pytest.approx(0.0005)
    assert not any(k.split("/")[1].startswith("while") for k in ops)
    # idle gaps: [0,1] step? no, [0,1] is outside any inner span;
    # [3,3.5] in engine.step; [4.5,10] longest, midpoint 7.25 in idle
    gaps = r["idle_gaps"]
    assert gaps[0] == ["harness.idle", pytest.approx(0.0055)]
    assert ["engine.step", pytest.approx(0.0005)] in gaps
    assert ["harness.outside", pytest.approx(0.001)] in gaps


def test_union_and_names():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [[1, 4], [5, 6]]
    assert tr.program_name("jit_tick(123)") == "tick"
    assert tr.program_name("jit__admit_chunk(4)") == "_admit_chunk"


def test_recorded_slice_against_a_timeline():
    path = DATA / "trace_slice.json"
    trace = json.loads(path.read_text())
    r = tr.reduce(trace, kernels=("paged_decode",))
    # an independent reading: mark every microsecond some op covers
    for plane in tr.device_planes(trace):
        ops = next(ln["events"] for ln in plane["lines"]
                   if ln["name"] == "XLA Ops")
        start, end = tr.window_of(trace)
        grid = np.zeros(int((end - start) / 1e3) + 1, bool)
        for _, s, d in ops:
            a = max(0, int((s - start) / 1e3))
            b = min(len(grid), int((s + d - start) / 1e3))
            grid[a:b] = True
        assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    # the slice holds one tick: 16 layers, one kernel call each
    assert r["programs"]["tick"]["count"] == 1
    assert r["kernels"]["paged_decode"]["count"] == 16
