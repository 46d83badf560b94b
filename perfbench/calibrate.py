"""Readings that set a cell's correctness limit (run on the chip).

    python3 perfbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        --seconds <s> [--control-seeds 3] [--out path]

For each seed, in one process: the cell's engine serves its traffic for
`--seconds` at the cell's own load, exactly as a run's window does; the
sampled finished requests are then checked against the reference (the
program's reading: mean gap over its served tokens).  For the first
`--control-seeds` seeds the same prompts and tokens are also read with
the reference one precision below the configuration (the control's
reading: mean gap of the tokens the lower precision puts first): int8
weights and activations for a bf16 configuration, int4 for an int8 one.
The limit goes between the program's largest and the control's smallest
reading (see PERF.md).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from perfbench.run import find, load_benchmark, load_config  # noqa: E402

CONTROL = {"bf16": (8, 8), "int8": (4, 4)}


def reading(cfg: dict, mix: dict, seed: int, seconds: float, counter,
            control: bool) -> dict:
    import numpy as np

    from perfbench import correct, driver, system, traffic

    t0 = time.perf_counter()
    engine, params, _ = system.build_engine(cfg, seed)
    driver.warm_compile(engine, cfg["vocab_size"])
    planned = traffic.plan(mix, seed, seconds, cfg["engine"]["slots"],
                           cfg["vocab_size"])
    win = driver.run(engine, planned, mix, seconds, counter)
    done = [t for t in win.tracked if t.req is not None and t.req.done]
    streams = [(t.planned.prompt, list(t.req.out_tokens)) for t in done]
    pick = correct.choose(streams, seed, mix["sample"])
    sample = [streams[i] for i in pick]
    del engine, params
    gc.collect()
    t1 = time.perf_counter()
    out = {"seed": seed, "sampled": len(sample), "serve_s": t1 - t0}
    if not sample:
        return out
    S, Rn = correct.pads(mix, cfg)
    prog, ctl = correct.gaps(cfg, seed, sample, S, Rn,
                             CONTROL[cfg["weights"]] if control else None)
    out["reference_s"] = time.perf_counter() - t1
    out["served_tokens"] = int(np.sum(~np.isnan(prog)))
    for name, g in (("program", prog), ("control", ctl)):
        if g is None:
            continue
        v = g[~np.isnan(g)]
        out[name] = float(v.mean())          # the number compared
        out[name + "_max"] = float(v.max())
        out[name + "_p99"] = float(np.percentile(v, 99))
        out[name + "_off_argmax"] = float(np.mean(v > 0))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    from perfbench.run import devices_or_exit
    devices_or_exit(cell["chips"])
    import jax

    from perfbench import driver, traffic
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = load_config(find(bench["configs"], cell["config"], "config"))
    mix = traffic.load(cell["traffic"])
    counter = driver.CompileCounter()
    rows = []
    for i, seed in enumerate(args.seeds):
        r = reading(cfg, mix, seed, args.seconds, counter,
                    i < args.control_seeds)
        print(json.dumps(r), flush=True)
        rows.append(r)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(json.dumps({"program_max": max(r.get("program", 0) for r in rows),
                      "control_min": min((r["control"] for r in rows
                                          if "control" in r),
                                         default=None)}))


if __name__ == "__main__":
    sys.exit(main())
