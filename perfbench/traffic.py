"""One generator for every traffic mix file under `traffic/`.

Sizes and arrival gaps are stratified quantiles of the mix's
distributions, so every seed serves the same multiset of prompt lengths,
output lengths and gaps.  In an open loop the seed shuffles their order
and draws the token ids.  A backlog is served first in, first out, so
there the order of sizes is the window's work (which prompt enters when
a slot frees, and when that slot frees again): its sizes come in one
fixed order for every seed, and the seed draws every token id and which
slot each first-wave request takes, neither of which changes the work.

Mix file keys:
  kind           "open_loop" (Poisson arrivals at `rate_per_s`, timed from
                 each request's due time) or "backlog" (every request
                 queued before the window opens).
  prompt/output  {"dist": "lognormal", "median", "sigma", "min", "max"} or
                 {"dist": "uniform", "min", "max"}: token counts.
  warmup_s       open loop: seconds of the same arrivals before the window.
  backlog        backlog: requests queued behind the first wave, which
                 fills every slot with a request of the mix part-way
                 through its output (context = prompt + age, budget =
                 output - age, ages spread uniformly), so the window
                 opens on a stationary mix of ages.
  sample         requests the correctness check samples (longest included).
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Planned:
    prompt: np.ndarray        # int32 token ids
    max_new: int
    due: float                # seconds after the open loop starts; 0 backlog
    phase: str                # "warm" | "window" | "wave" | "backlog"


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified quantiles (i + 0.5) / n of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
    elif dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _tokens(rng, lengths, vocab: int) -> list[np.ndarray]:
    flat = rng.integers(0, vocab, int(np.sum(lengths)), dtype=np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1])


def open_loop(mix: dict, seed: int, seconds: float, vocab: int):
    """Warm-up then window requests, due times in seconds from t=0; the
    window opens at `mix["warmup_s"]`.  Each phase's gaps are scaled to
    fill its span exactly, so the offered rate is the mix's."""
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    out, t0 = [], 0.0
    for phase, span in (("warm", float(mix["warmup_s"])),
                        ("window", float(seconds))):
        n = max(1, math.ceil(rate * span))
        p = rng.permutation(quantiles(mix["prompt"], n))
        o = rng.permutation(quantiles(mix["output"], n))
        g = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
        g = g / g.sum() * span
        due = t0 + np.cumsum(g) - g
        for toks, m, d in zip(_tokens(rng, p, vocab), o, due):
            out.append(Planned(toks, int(m), float(d), phase))
        t0 += span
    return out


# steps of the Weyl sequences that order a backlog's sizes
PHI, RT2, RT3 = (5 ** 0.5 - 1) / 2, 2 ** 0.5 - 1, 3 ** 0.5 - 1


def spread_order(n: int, alpha: float) -> np.ndarray:
    """A fixed order of n strata in which every run of consecutive
    entries spans the range: the ranks of k * alpha mod 1, k = 1..n."""
    return np.argsort(np.argsort(np.modf(alpha * np.arange(1, n + 1))[0]))


def backlog(mix: dict, seed: int, slots: int, vocab: int):
    """The first wave (one request per slot) then the backlog, in one
    order of sizes for every seed (module docstring)."""
    rng = np.random.default_rng(seed)
    out = []
    o = quantiles(mix["output"], slots)
    age = np.floor((spread_order(slots, RT3) + 0.5) / slots
                   * o).astype(np.int64)
    p = quantiles(mix["prompt"], slots)[spread_order(slots, RT2)]
    wave = rng.permutation(slots)
    for toks, i in zip(_tokens(rng, (p + age)[wave], vocab), wave):
        out.append(Planned(toks, int(o[i] - age[i]), 0.0, "wave"))
    n = int(mix["backlog"])
    p = quantiles(mix["prompt"], n)[spread_order(n, PHI)]
    o = quantiles(mix["output"], n)[spread_order(n, RT2)]
    for toks, m in zip(_tokens(rng, p, vocab), o):
        out.append(Planned(toks, int(m), 0.0, "backlog"))
    return out


def plan(mix: dict, seed: int, seconds: float, slots: int, vocab: int):
    if mix["kind"] == "open_loop":
        return open_loop(mix, seed, seconds, vocab)
    if mix["kind"] == "backlog":
        return backlog(mix, seed, slots, vocab)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")
