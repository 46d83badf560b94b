"""What decides `correct`: served tokens against the plain reference.

After the window a sample of finished requests, drawn from the seed and
always holding the one with the most served tokens, is run through the
configuration's reference teacher-forced over prompt + served tokens.
The reference is the module the file names under `"reference"` (a path
from the checkout's root; `perfbench/reference.py`, the dense decoder,
when it names none), loaded by path as the metric readers are; its
contract is in `reference.py`'s docstring.  For every
served token the reference's best logit at that position minus its
logit for the served token is a gap (0 where the engine chose the
reference's argmax); the number compared is the mean gap over every
served token of the sample.  The widest gap grows with the logits' error
and the mean with its square (how often a near tie flips times how far),
so the mean sets a bf16 program apart from its int8 control where the
widest gap does not (PERF.md, section 6).  Greedy serving is what makes
this valid.

`control` reads the same positions with the reference one precision
below the configuration (`logits_at(quant=...)`): the gap of the token
the lower precision puts first.  Only calibration runs it.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
GROUP = 4
DEFAULT_REFERENCE = "perfbench/reference.py"
_MODULES: dict = {}


def reference(cfg: dict):
    """The reference module the configuration names, loaded once per
    path."""
    rel = cfg.get("reference", DEFAULT_REFERENCE)
    if rel not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "perfbench_reference_" + re.sub(r"\W", "_", rel), ROOT / rel)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[rel] = mod
    return _MODULES[rel]


def pads(mix: dict, cfg: dict) -> tuple[int, int]:
    """(sequence length, scored rows) that hold any request of the mix,
    the length a multiple of the reference's `Q_CHUNK`."""
    q = reference(cfg).Q_CHUNK
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    return -(-longest // q) * q, mix["output"]["max"]


def choose(streams: list, seed: int, k: int) -> list:
    """Indices of up to k streams: the one with most tokens, then a
    seeded draw of the rest."""
    if not streams:
        return []
    order = np.argsort([-len(s[1]) for s in streams], kind="stable")
    rest = np.random.default_rng([seed, 7]).permutation(order[1:])
    return sorted([int(order[0])] + [int(i) for i in rest[:k - 1]])


def _arrays(streams, S, Rn):
    N = -(-len(streams) // GROUP) * GROUP
    toks = np.zeros((N, S), np.int32)
    rows = np.zeros((N, Rn), np.int32)
    served = np.zeros((N, Rn), np.int32)
    valid = np.zeros((N, Rn), bool)
    for i, (prompt, out) in enumerate(streams):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        n = len(out)
        rows[i, :n] = len(prompt) - 1 + np.arange(n)
        rows[i, n:] = rows[i, n - 1]
        served[i, :n] = out
        valid[i, :n] = True
    return toks, rows, served, valid


def _gap(ref, tok):
    top = jnp.max(ref, axis=-1)
    return top - jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]


def gaps(cfg: dict, seed: int, streams: list, S: int, Rn: int,
         control: tuple | None = None):
    """Per served token: (program gaps, control gaps or None), each an
    (n_streams, Rn) array with NaN past a stream's end."""
    R = reference(cfg)
    toks, rows, served, valid = _arrays(streams, S, Rn)
    ref = R.logits_at(cfg, seed, toks, rows, group=GROUP)
    prog = np.concatenate([np.asarray(_gap(r, jnp.asarray(
        served[i * GROUP:(i + 1) * GROUP]))) for i, r in enumerate(ref)])
    ctl = None
    if control is not None:
        low = R.logits_at(cfg, seed, toks, rows, quant=control, group=GROUP)
        ctl = np.concatenate([np.asarray(_gap(r, jnp.argmax(lo, axis=-1)))
                              for r, lo in zip(ref, low)])
        ctl = np.where(valid, ctl, np.nan)[:len(streams)]
    return np.where(valid, prog, np.nan)[:len(streams)], ctl


def limits(cell: str) -> dict:
    path = HERE / "limits" / f"{cell}.json"
    return json.loads(path.read_text())


def check(cfg: dict, seed: int, streams: list, budgets: list, mix: dict,
          vocab: int, limit: float) -> dict:
    """{check name: {"value", "limit"}}; a check passes when value <=
    limit."""
    S, Rn = pads(mix, cfg)
    wrong_len = sum(len(o) != b for (_, o), b in zip(streams, budgets))
    oov = sum(int(np.sum((np.asarray(o) < 0) | (np.asarray(o) >= vocab)))
              for _, o in streams)
    if streams and not oov:
        g, _ = gaps(cfg, seed, streams, S, Rn)
        mean = float(np.nanmean(g))
    else:
        mean = None
    return {"wrong_length_streams": {"value": wrong_len, "limit": 0},
            "out_of_vocab_tokens": {"value": oov, "limit": 0},
            "mean_logit_gap": {"value": mean, "limit": limit}}
