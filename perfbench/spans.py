"""The program's own spans and named scopes, read from the same
`.xplane.pb` that `trace.py` reduces, on the same clock.

The engine marks each boundary inside `Engine.step` with a
`TraceAnnotation` (`PROGRAM`; `prefill.call` carries the args `rows` and
`padded`), and its device code with `jax.named_scope`s (`SCOPES`).  A
TPU trace's "XLA Ops" events carry no op name among their stats (only
the instruction's HLO text and its device offsets), so `op_names` maps
each instruction of the compiled `tick` and `admit` to the `op_name`
metadata of their compiled HLO; `cell_op_names` compiles them again after
the run, from an engine of the cell's configuration with abstract
weights (a compile-cache read).

The metric readers get the reduction through `of(ctx)`: it reduces the
newest profile under `perfbench/.trace` (the one the run has just
written) once, keeps it in `ctx["spans"]` and prints it as one `info`
line.  A profile without any program span (a program that marks none)
reduces to None, and its readers read nothing.

`export` keeps what the reduction needs: the harness's and the program's
spans on the host planes, with their args, and the "XLA Modules" and
"XLA Ops" lines of the device planes.  Events are
`[name, start_ns, dur_ns, args]`.  `reduce` returns
  spans    — per program span in the window: count, seconds, summed args;
  rounds   — `engine.admit` spans that admitted or evicted (a round);
  scopes   — per program, device seconds of its ops by the outermost
             named scope of their op name, `(none)` for ops with none;
  none_ops — per program, the busiest instructions of `(none)`;
  unmapped — per program, seconds of ops the op-name map lacks (none
             when the map is of the programs that ran);
  idle     — the device's idle seconds in the window, split across the
             innermost host span that covers each part (`harness.outside`
             where none does), with the part inside `engine.admit` spans;
  gaps     — the ten longest idle gaps, each split the same way.
Times are seconds, averaged over the device planes as `trace.reduce`
averages them, so the split sums to its idle time.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import json
import os
import pathlib
import re
from collections import defaultdict

import jax

from perfbench import trace as tr

PROGRAM = ("engine.admit", "sched.plan_round", "engine.evict",
           "prefill.call", "engine.admit_sync", "engine.tick",
           "engine.tick_sync", "engine.emit", "engine.transfer")
SCOPES = ("attention", "kv_write", "mlp", "unembed", "sample", "pages")
NONE = "(none)"
OUTSIDE = "harness.outside"
ROUND_MARKS = ("prefill.call", "engine.evict")
TRACES = pathlib.Path(__file__).resolve().parent / ".trace"


def is_span(name: str) -> bool:
    """A span of the harness or the program (the runtime's own host
    events are left out)."""
    return (name in PROGRAM or name == "engine.step"
            or name.startswith("harness."))


def _value(v):
    try:
        return int(v)
    except (TypeError, ValueError):
        return str(v)


def export(log_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = re.fullmatch(r"/device:TPU:\d+", plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            events = []
            for e in line.events:
                name = e.name
                if device:
                    args = {}
                elif is_span(name):
                    args = {k: _value(v) for k, v in e.stats
                            if not k.startswith("_")}
                else:
                    continue
                events.append([name, float(e.start_ns),
                               float(e.duration_ns), args])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def parse_op_names(hlo_text: str) -> dict:
    """{instruction: op_name} of a compiled HLO module's text; "" for an
    instruction without one."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if m:
            name = re.search(r'metadata=\{op_name="([^"]*)"', line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


def op_names(engine) -> dict:
    """{program: {instruction: op_name}} of the engine's compiled `tick`
    and `admit`, lowered from the engine's own arrays and argument
    types."""
    import jax.numpy as jnp

    st, ca = engine._admit_side()
    ns, C = engine.prefill_slots, engine.prefill_chunk
    mp, P = engine.pages_per_slot, st.pages.refs.shape[0]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
    admit = engine.prefill._admit_chunk.lower(
        engine.params, st, ca, i32(ns, C), b(ns), b(ns), i32(ns), i32(ns),
        i32(ns), i32(ns), i32(ns, engine._stop_cap), b(ns), i32(ns, mp),
        i32(ns), i32(ns), i32(ns), i32(P), i32(P))
    tick = engine.decode.tick.lower(engine.params, engine.state,
                                    engine.caches)
    out = {}
    for lowered in (tick, admit):
        text = lowered.compile().as_text()
        module = re.match(r"HloModule (\S+?),", text).group(1)
        out[tr.program_name(module)] = parse_op_names(text)
    return out


def cell_op_names(cfg: dict) -> dict:
    """`op_names` of the engine `system.build_engine` makes for the
    configuration file `cfg`, built with its weights left abstract (only
    the slot state and the KV pool are allocated), so it can run after
    the measured engine is freed.  The programs are the ones the run
    compiled: the same shapes and options, read from the compile cache
    where there is one."""
    from perfbench import system
    from repro.launch import serve as launch
    from repro.runtime.serve import Engine

    args = system.launcher_args(cfg)
    mc = system.model_config(cfg, args)
    params = jax.eval_shape(lambda: system.make_params(cfg, mc, 0))
    with Engine(mc, params, options=launch.engine_options(args)) as engine:
        return op_names(engine)


def newest_trace() -> str:
    """The cell directory under `TRACES` that holds the newest
    profile."""
    paths = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {TRACES}")
    rel = os.path.relpath(max(paths, key=os.path.getmtime), TRACES)
    return os.path.join(TRACES, rel.split(os.sep)[0])


def of(ctx: dict):
    """The reduction of this traced run, for a metric reader: made once
    from the newest profile and the op names of `ctx["cfg"]`'s programs,
    kept in `ctx["spans"]` and printed as an `info` line.  None where the
    profile holds no program span."""
    if "spans" not in ctx:
        trace = export(newest_trace())
        red = None
        if any(name in PROGRAM for name, *_ in _host_spans(trace)):
            red = reduce(trace, cell_op_names(ctx["cfg"]))
            print(json.dumps({"info": {"spans": red}}), flush=True)
        ctx["spans"] = red
    return ctx["spans"]


def scope_of(op_name: str) -> str:
    """The outermost named scope of an op name such as
    `jit(tick)/while/body/attention/dot_general`."""
    return next((c for c in op_name.split("/") if c in SCOPES), NONE)


def _host_spans(trace: dict) -> list:
    """(name, start, end, stats) of every span on the host planes."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                for name, s, d, stats in ln["events"]:
                    if is_span(name):
                        out.append((name, s, s + d, stats))
    return out


def innermost(spans) -> list:
    """Disjoint `[start, end, name]` segments: at each instant the
    covering span that started last (the shortest on a tie)."""
    edges = sorted({t for _, s, e, *_ in spans for t in (s, e)})
    order = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    segs, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            top = max(active, key=lambda sp: (sp[1], -sp[2]))
            segs.append([a, b, top[0]])
    return segs


def split(gaps, segs):
    """Cut each of the sorted, disjoint `(start, end)` gaps by the
    segments: yields `(gap index, name, ns)` per part, `OUTSIDE` where
    no segment covers it."""
    j = 0
    for gi, (a, b) in enumerate(gaps):
        t = a
        while t < b:
            while j < len(segs) and segs[j][1] <= t:
                j += 1
            if j < len(segs) and segs[j][0] <= t:
                end, name = min(b, segs[j][1]), segs[j][2]
            else:
                end = min(b, segs[j][0]) if j < len(segs) else b
                name = OUTSIDE
            yield gi, name, end - t
            t = end


def covered(gaps, intervals) -> float:
    """Nanoseconds of the sorted, disjoint gaps that the intervals
    cover."""
    total, j = 0.0, 0
    iv = tr.union(intervals)
    for a, b in gaps:
        while j < len(iv) and iv[j][1] <= a:
            j += 1
        k = j
        while k < len(iv) and iv[k][0] < b:
            total += min(b, iv[k][1]) - max(a, iv[k][0])
            k += 1
    return total


def reduce(trace: dict, op_names: dict) -> dict:
    every = _host_spans(trace)
    lo, hi = next(((s, e) for name, s, e, _ in every if name == tr.WINDOW),
                  (None, None))
    if lo is None:
        raise ValueError(f"trace has no {tr.WINDOW!r} annotation")
    devs = tr.device_planes(trace)
    if not devs:
        raise ValueError("trace has no TPU device plane")
    n = len(devs)
    every = [sp for sp in every if sp[0] != tr.WINDOW]
    host = [sp for sp in every if lo <= (sp[1] + sp[2]) / 2 < hi]

    spans = defaultdict(lambda: {"count": 0, "seconds": 0.0, "args": {}})
    for name, s, e, stats in host:
        if name not in PROGRAM:
            continue
        rec = spans[name]
        rec["count"] += 1
        rec["seconds"] += (e - s) * 1e-9
        for k, v in stats.items():
            if isinstance(v, int) and not k.startswith("_"):
                rec["args"][k] = rec["args"].get(k, 0) + v
    # a round is counted where its span's midpoint falls; its idle time
    # wherever it falls in the window
    admits = [(s, e) for name, s, e, _ in every if name == "engine.admit"]
    marks = sorted(s for name, s, _, _ in every if name in ROUND_MARKS)
    rounds = sum(bisect.bisect_left(marks, e) > bisect.bisect_left(marks, s)
                 for s, e in admits if lo <= (s + e) / 2 < hi)

    segs = innermost([sp[:3] for sp in every])
    idle, gaps_named, admit_ns = defaultdict(float), [], 0.0
    scopes = defaultdict(lambda: defaultdict(float))
    none_ops = defaultdict(lambda: defaultdict(float))
    unmapped = defaultdict(float)
    for plane in devs:
        ops = tr._line(plane, tr.OPS_LINE)
        busy_ev = ops or tr._line(plane, tr.MODULES_LINE)
        iv = tr.union(tr._clip([(s, s + d) for _, s, d, _ in busy_ev],
                               lo, hi))
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        longest = heapq.nlargest(10, range(len(gaps)),
                                 key=lambda g: gaps[g][1] - gaps[g][0])
        per_gap = {g: defaultdict(float) for g in longest}
        for gi, name, ns in split(gaps, segs):
            idle[name] += ns
            if gi in per_gap:
                per_gap[gi][name] += ns
        admit_ns += covered(gaps, admits)
        gaps_named += [[(gaps[g][1] - gaps[g][0]) * 1e-9,
                        {k: v * 1e-9 for k, v in sorted(
                            per_gap[g].items(), key=lambda kv: -kv[1])}]
                       for g in longest]

        mods = sorted((s, s + d, tr.program_name(name))
                      for name, s, d, _ in tr._line(plane, tr.MODULES_LINE))
        starts = [m[0] for m in mods]
        for name, s, d, _ in ops:
            inst = tr.instruction(name)
            if not lo <= s + d / 2 < hi or \
                    re.sub(r"[.\d]+$", "", inst) in tr.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            names = op_names.get(prog, {})
            if inst not in names:
                unmapped[prog] += d * 1e-9
            scope = scope_of(names.get(inst, ""))
            scopes[prog][scope] += d * 1e-9
            if scope == NONE:
                none_ops[prog][inst] += d * 1e-9

    idle_s = {k: v * 1e-9 / n for k, v in
              sorted(idle.items(), key=lambda kv: -kv[1])}
    return {
        "spans": {k: dict(v) for k, v in spans.items()},
        "rounds": rounds,
        "scopes": {p: {k: v / n for k, v in sorted(
            sc.items(), key=lambda kv: -kv[1])} for p, sc in scopes.items()},
        "none_ops": {p: [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:20]]
            for p, ops in none_ops.items()},
        "unmapped": {p: v / n for p, v in unmapped.items()},
        "idle": {"seconds": sum(idle_s.values()),
                 "in_admit": admit_ns * 1e-9 / n, "by_span": idle_s},
        "gaps": sorted(gaps_named, key=lambda g: -g[0])[:10],
    }
