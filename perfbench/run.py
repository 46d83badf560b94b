"""Run one benchmark cell once on the chip(s) of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights from the seed, the engine, compiles or cache reads, the
cell's warm-up traffic) runs from process start to the window's open;
the window then runs `--seconds`; afterwards peak device memory is read,
the engine is freed and the served tokens are checked against the
reference.  Earlier stdout lines are informational JSON; the last is
the result.  The compared numbers also end stderr.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "perfbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(entry: dict) -> dict:
    return json.loads((ROOT / entry["file"]).read_text())


def kernels(cfg: dict) -> tuple:
    """The kernels whose device time the trace reduction sums: the
    configuration's `"kernels"`, by default the paged decode kernel."""
    return tuple(cfg.get("kernels", ["paged_decode"]))


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell: dict, trace: int) -> list:
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def info(**kw) -> None:
    print(json.dumps({"info": kw}), flush=True)


def run_cell(name: str, cfg: dict, mix: dict, metrics: list, seed: int,
             seconds: float, trace: int, devices, limit: float,
             t_start: float) -> dict:
    """One run of a cell; `metrics` are the BENCHMARK.json entries this
    run reports (end-to-end, or per-layer when tracing)."""
    import jax

    from perfbench import correct, driver, peaks, system, traffic
    from perfbench import trace as tr
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = driver.CompileCounter()
    vocab = cfg["vocab_size"]

    engine, params, _ = system.build_engine(cfg, seed)
    driver.warm_compile(engine, vocab)
    planned = traffic.plan(mix, seed, seconds, cfg["engine"]["slots"], vocab)
    trace_dir = HERE / ".trace" / name
    shutil.rmtree(trace_dir, ignore_errors=True)
    cm = tr.capture(str(trace_dir)) if trace else None
    win = driver.run(engine, planned, mix, seconds, counter, cm)
    setup_s = win.t_open - t_start
    open_loop = mix["kind"] == "open_loop"
    e2e, counts = driver.end_to_end(win, open_loop)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)

    late = win.late_s or [0.0]
    info(window_s=win.t_end - win.t_open, setup_s=setup_s, **counts,
         admitted=win.admitted, ticks=len(win.ticks),
         compiles_in_window=win.compiles,
         compile_events=sorted(set(counter.names)),
         generator_late_p50_ms=1e3 * driver.pct(late, 50),
         generator_late_max_ms=1e3 * max(late),
         slots_per_tick_mean=(sum(map(len, win.ticks)) / len(win.ticks)
                              if win.ticks else 0.0),
         slots_per_tick_min=min(map(len, win.ticks), default=0),
         engine_counters={k: getattr(engine, k) for k in (
             "n_admit_calls", "n_ticks", "n_syncs", "n_generated",
             "pages_high_water")})

    done = [t for t in win.tracked if t.req is not None and t.req.done]
    streams = [(t.planned.prompt, list(t.req.out_tokens)) for t in done]
    pick = correct.choose(streams, seed, mix["sample"])
    sample = [streams[i] for i in pick]
    budgets = [done[i].planned.max_new for i in pick]
    attempted = sum(t.req is not None for t in win.tracked)

    ctx = {"cfg": cfg, "window": win, "chips": len(devices)}
    del engine, params
    gc.collect()
    result = {}
    if trace:
        red = tr.reduce(tr.export(str(trace_dir)), kernels=kernels(cfg))
        ctx["trace"] = red
        ctx["peaks"] = peaks.for_kind(devices[0].device_kind)
        vals = {m["name"]: reader(m["name"])(ctx) for m in metrics}
    else:
        vals = dict(e2e, setup_s=setup_s)
    checks = correct.check(cfg, seed, sample, budgets, mix, vocab, limit)
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    units = {m["name"]: m["unit"] for m in metrics}
    result["correct"] = ok
    result["attempted"] = attempted
    result["failed"] = 0 if ok else len(sample) or 1
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in vals.items()
                         if k in units and v is not None}
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        info(trace_programs=red["programs"], trace_kernels=red["kernels"])
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    from perfbench import correct, traffic
    cfg = load_config(find(bench["configs"], cell["config"], "config"))
    mix = traffic.load(cell["traffic"])
    limit = correct.limits(cell["name"])["mean_logit_gap"]["limit"]
    devices = devices_or_exit(cell["chips"])
    result = run_cell(cell["name"], cfg, mix,
                      metrics_of(bench, cell, args.trace), args.seed,
                      args.seconds, args.trace, devices, limit, T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
