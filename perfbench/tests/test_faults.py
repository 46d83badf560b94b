"""A whole run, chip check skipped, at a small size on the CPU: sound, it
is correct; with the timed path broken underneath, `correct` is false.

Faults a serving cell can have: a token altered where it is produced
(greedy sampling returns the runner-up id), and a step that returns its
state unchanged (the KV writes of every step are dropped)."""
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import run
from perfbench.tests.conftest import load

E2E = [{"name": n, "unit": "u"} for n in
       ("output_tok_s", "ttft_p90_ms", "itl_p50_ms", "itl_p95_ms",
        "setup_s")]
LIMIT = 0.002      # as test_control.py sets it for the small config


def one_run(cfg, mix):
    return run.run_cell("tiny.test", cfg, mix, E2E, 2 ** 33 + 1, 3.0, 0,
                        jax.devices()[:1], LIMIT, time.perf_counter())


def runner_up(logits, keys, sc):
    top2 = jax.lax.top_k(logits, 2)[1][:, 1]
    return top2.astype(jnp.int32), keys


@pytest.mark.parametrize("mix", ["code", "batch"])
def test_sound_run_is_correct(mix):
    r = one_run(load("small"), load(mix))
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    if fault == "token_altered":
        from repro.runtime import sampling
        monkeypatch.setattr(sampling, "sample", runner_up)
    else:
        from repro.models import attention
        monkeypatch.setattr(attention, "paged_update",
                            lambda pool, new, positions, pv: pool)
    r = one_run(load("small"), load("code"))
    assert not r["correct"]
    assert r["checks"]["mean_logit_gap"]["value"] > LIMIT
