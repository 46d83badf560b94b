"""Profiler capture and the reduction from a device trace to numbers.

`capture` wraps `jax.profiler` around the measured window; `export`
turns the written `.xplane.pb` into plain lists
`{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}`, which `reduce` reads (and which the tests record).

Device planes are those named `/device:TPU:<n>`.  On each, the events of
the "XLA Ops" line are the operations that ran, named by their HLO text
(`%_paged_decode.8 = bf16[...] custom-call(...)`): their union over the
traced window is the busy time, a kernel is matched on the instruction
name alone (operand lists name other instructions), and the busiest ops
are reported per program without the loops that contain them.  Program executions are the events of
the "XLA Modules" line, named `jit_<function>(<id>)`.  The harness marks
its window and its own host work with `TraceAnnotation`s named
`harness.*` and `engine.step` on the host plane, on the same clock.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from collections import defaultdict

import jax

WINDOW = "harness.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def export(log_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_name(event_name: str) -> str:
    """`jit_tick(123)` -> `tick`."""
    name = re.sub(r"\(.*\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"]
            if re.fullmatch(r"/device:TPU:\d+", p["name"])]


def _line(plane: dict, name: str) -> list:
    return next((ln["events"] for ln in plane["lines"]
                 if ln["name"] == name), [])


def host_spans(trace: dict) -> list:
    """(name, start, end) of the harness's own annotations."""
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith("harness.") or name == "engine.step":
                    out.append((name, s, s + d))
    return out


def window_of(trace: dict) -> tuple:
    spans = [(s, e) for n, s, e in host_spans(trace) if n == WINDOW]
    if not spans:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return spans[0]


def instruction(event_name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


CONTAINERS = ("while", "conditional", "call")


def reduce(trace: dict, kernels=()) -> dict:
    """Per-device busy time, per-program and per-kernel device time and
    counts inside the window, the busiest ops and the longest idle gaps
    named by the host span around their midpoint.  Times in seconds."""
    lo, hi = window_of(trace)
    devs = device_planes(trace)
    if not devs:
        raise ValueError("trace has no TPU device plane")
    busy, programs, kernel_t, ops = [], defaultdict(lambda: [0.0, 0]), \
        defaultdict(lambda: [0.0, 0]), defaultdict(float)
    gaps = []
    for plane in devs:
        op_ev = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        iv = union(_clip([(s, s + d) for _, s, d in op_ev], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = sorted((s, s + d, program_name(name))
                      for name, s, d in _line(plane, MODULES_LINE))
        for s, e, prog in mods:
            if lo <= (s + e) / 2 < hi:
                programs[prog][0] += (e - s) * 1e-9
                programs[prog][1] += 1
        starts = [m[0] for m in mods]
        for name, s, d in _line(plane, OPS_LINE):
            if not lo <= s + d / 2 < hi:
                continue
            inst = instruction(name)
            for k in kernels:
                if k in inst:
                    kernel_t[k][0] += d * 1e-9
                    kernel_t[k][1] += 1
            if re.sub(r"[.\d]+$", "", inst) in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            ops[f"{prog}/{inst}"] += d * 1e-9
    spans = sorted(host_spans(trace), key=lambda t: t[2] - t[1])
    named = []
    for s, e in sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]:
        mid = (s + e) / 2
        host = next((n for n, a, b in spans
                     if a <= mid < b and n != WINDOW), "harness.outside")
        named.append([host, (e - s) * 1e-9])
    n = len(devs)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": n,
        "programs": {k: {"seconds": v[0] / n, "count": v[1] / n}
                     for k, v in programs.items()},
        "kernels": {k: {"seconds": v[0] / n, "count": v[1] / n}
                    for k, v in kernel_t.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": named,
    }
