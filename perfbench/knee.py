"""Sweep of offered rates for an open-loop cell (run once on the chip
when a cell is defined, to place its rate below the knee).

    python3 perfbench/knee.py --workload <cell> --rates 0.3 0.5 ... \
        --seconds 40 --seed 1

One process, one engine: for each rate the cell's traffic runs at that
rate (warm-up then window, as in a run), then the engine drains.  Per
rate it prints the window's numbers and whether the queue grew: the
median time to first token of the window's second half against its
first half, and the requests still without a first token at the close.
"""
from __future__ import annotations

import argparse
import json
import sys
import pathlib

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from perfbench.run import devices_or_exit, find, load_benchmark, load_config


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    devices_or_exit(cell["chips"])
    import jax

    from perfbench import driver, system, traffic
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = load_config(find(bench["configs"], cell["config"], "config"))
    mix = traffic.load(cell["traffic"])
    engine, _, _ = system.build_engine(cfg, args.seed)
    driver.warm_compile(engine, cfg["vocab_size"])
    counter = driver.CompileCounter()
    for i, rate in enumerate(args.rates):
        m = dict(mix, rate_per_s=rate)
        planned = traffic.plan(m, args.seed + i, args.seconds,
                               cfg["engine"]["slots"], cfg["vocab_size"])
        win = driver.run(engine, planned, m, args.seconds, counter)
        e2e, counts = driver.end_to_end(win, True)
        w = sorted((t for t in win.tracked if t.planned.phase == "window"),
                   key=lambda t: t.due)
        ttft = [(t.stamps[0] if t.stamps else win.t_end) - t.due for t in w]
        half = len(ttft) // 2
        waiting = sum(1 for t in w if not t.stamps)
        late = win.late_s or [0.0]
        print(json.dumps({
            "rate": rate, **e2e, **counts, "waiting_at_close": waiting,
            "ttft_p50_first_half_s": float(np.median(ttft[:half])),
            "ttft_p50_second_half_s": float(np.median(ttft[half:])),
            "late_p50_ms": 1e3 * driver.pct(late, 50),
            "late_max_ms": 1e3 * max(late),
            "admit_calls": win.admit_calls, "admitted": win.admitted,
            "compiles": win.compiles}), flush=True)
        while any(t.req is not None and not t.req.done for t in win.tracked):
            engine.step()


if __name__ == "__main__":
    main()
