"""Device time by an inner named-scope path, for metrics that need more
than `spans.reduce`'s outermost scope (which puts an expert layer's
router and matmuls alike under `mlp`).

`of(ctx)` reduces the run's newest profile once, with the op names of
the cell's compiled `tick` and `admit` (`spans.cell_op_names`), and
keeps `{program: {path: seconds}}` in `ctx["scope_paths"]`: the device
time of the ops whose op name holds each of `PATHS`, over the traced
window, averaged over the device planes as `trace.reduce` averages
them.  A profile without any program span (a program that marks none)
reduces to None, and its readers read nothing.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict

from perfbench import spans
from perfbench import trace as tr

PATHS = ("mlp/route", "mlp/experts", "mlp/shared")


def path_of(op_name: str, paths=PATHS):
    """The first of `paths` that `op_name` holds as whole scope names."""
    name = "/" + op_name + "/"
    return next((p for p in paths if "/" + p + "/" in name), None)


def reduce(trace: dict, op_names: dict, paths=PATHS) -> dict:
    every = spans._host_spans(trace)
    lo, hi = next(((s, e) for name, s, e, _ in every if name == tr.WINDOW),
                  (None, None))
    if lo is None:
        raise ValueError(f"trace has no {tr.WINDOW!r} annotation")
    devs = tr.device_planes(trace)
    if not devs:
        raise ValueError("trace has no TPU device plane")
    out = defaultdict(lambda: defaultdict(float))
    for plane in devs:
        mods = sorted((s, s + d, tr.program_name(name))
                      for name, s, d, _ in tr._line(plane, tr.MODULES_LINE))
        starts = [m[0] for m in mods]
        for name, s, d, _ in tr._line(plane, tr.OPS_LINE):
            inst = tr.instruction(name)
            if not lo <= s + d / 2 < hi or \
                    re.sub(r"[.\d]+$", "", inst) in tr.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            path = path_of(op_names.get(prog, {}).get(inst, ""), paths)
            if path is not None:
                out[prog][path] += d * 1e-9
    n = len(devs)
    return {p: {k: v / n for k, v in sc.items()} for p, sc in out.items()}


def of(ctx: dict):
    """`{program: {path: seconds}}` of this traced run, made once and kept
    in `ctx["scope_paths"]`; None where the profile holds no program
    span."""
    if "scope_paths" not in ctx:
        trace = spans.export(spans.newest_trace())
        red = None
        if any(name in spans.PROGRAM for name, *_ in
               spans._host_spans(trace)):
            red = reduce(trace, spans.cell_op_names(ctx["cfg"]))
            print(json.dumps({"info": {"scope_paths": red}}), flush=True)
        ctx["scope_paths"] = red
    return ctx["scope_paths"]


def per_execution_ms(ctx: dict, program: str, path: str):
    """Device ms of `path`'s ops per execution of `program` in the traced
    window, or None where nothing was read."""
    red = of(ctx)
    if red is None:
        return None
    t = red.get(program, {}).get(path)
    rec = ctx["trace"]["programs"].get(program)
    if t is None or not rec or not rec["count"]:
        return None
    return 1e3 * t / rec["count"]
