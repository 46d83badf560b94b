"""The engine's profiler spans and named scopes (runtime/serve.py,
runtime/scheduler.py, runtime/workers.py; models/ and
runtime/sampling.py for the scopes).

A tiny engine serves under `jax.profiler` on the CPU and the trace is read
back: every span of `Engine.step` appears, nested as designed; the
`prefill.call` args add up to the engine's admit counters; the profiler
adds no host sync.  The compiled `tick` and `admit` programs keep their
jit names and carry every named scope in their op-name metadata, which is
what a chip trace groups device time by."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.runtime.serve import Engine

jax.config.update("jax_platform_name", "cpu")

ADMIT_CHILDREN = ("sched.plan_round", "engine.evict", "prefill.call",
                  "engine.admit_sync")
TOP = ("engine.admit", "engine.tick", "engine.tick_sync", "engine.emit",
       "engine.transfer")
SCOPES = ("attention", "kv_write", "mlp", "unembed", "sample", "pages")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-8b", smoke=True)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def serve(cfg, params):
    """An eviction-only round (as in test_prefix_cache's leak test), then
    a disaggregated engine's page transfer.  Returns both engines."""
    rng = np.random.default_rng(5)
    eng = Engine(cfg, params, num_slots=2, max_seq=64, num_pages=6)
    a = eng.submit(rng.integers(0, cfg.vocab_size, size=20), 25)
    c = eng.submit(rng.integers(0, cfg.vocab_size, size=17), 2)
    while not c.done:
        eng.step()
    eng.submit(rng.integers(0, cfg.vocab_size, size=50), 8)
    eng.run()
    assert a.done and eng.prefix.evictions >= 1
    with Engine(cfg, params, num_slots=2, max_seq=64, disagg=True,
                prefix_cache=False) as dis:
        for n in (9, 21):
            dis.submit(rng.integers(0, cfg.vocab_size, size=n), 4)
        dis.run()
    return eng, dis


def host_spans(log_dir):
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("engine.", "sched.", "prefill.")):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    {k: int(v) for k, v in e.stats}))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        engines = serve(*setup)
    finally:
        jax.profiler.stop_trace()
    return engines, host_spans(log_dir)


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_appears_nested_as_designed(traced):
    _, spans = traced
    names = {sp[0] for sp in spans}
    assert names == set(ADMIT_CHILDREN + TOP)
    admits = [sp for sp in spans if sp[0] == "engine.admit"]
    for sp in spans:
        within = [p for p in admits if inside(sp, p)]
        if sp[0] in ADMIT_CHILDREN:
            assert within, sp
        elif sp[0] != "engine.admit":
            assert not within, sp
    # a step's decode spans come in order: dispatch, sync, token loop
    order = [sp[0] for sp in spans if sp[0] in TOP[1:4]]
    assert order == ["engine.tick", "engine.tick_sync",
                     "engine.emit"] * (len(order) // 3)


def test_prefill_args_add_up_to_the_counters(traced):
    (eng, dis), spans = traced
    calls = [sp[3] for sp in spans if sp[0] == "prefill.call"]
    assert len(calls) == eng.n_admit_calls + dis.n_admit_calls
    assert sum(c["rows"] for c in calls) == \
        eng.n_admit_rows + dis.n_admit_rows
    assert sum(c["padded"] for c in calls) == sum(
        e.n_admit_calls * e.prefill_slots * e.prefill_chunk
        for e in (eng, dis))
    # every prompt row is computed once: 20 + 17 + 50 and 9 + 21
    assert eng.n_admit_rows == 87 and dis.n_admit_rows == 30


def test_profiler_adds_no_sync(setup, traced):
    (eng, dis), _ = traced
    plain, plain_dis = serve(*setup)
    assert (plain.n_syncs, plain_dis.n_syncs) == (eng.n_syncs, dis.n_syncs)
    assert (plain.n_ticks, plain.n_admit_calls) == \
        (eng.n_ticks, eng.n_admit_calls)


def test_programs_keep_their_names_and_carry_every_scope(setup):
    cfg, params = setup
    eng = Engine(cfg, params, num_slots=2, max_seq=64, num_pages=8)
    ns, C, cap = eng.prefill_slots, eng.prefill_chunk, eng._stop_cap
    P, mp = eng.num_pages, eng.pages_per_slot

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    flag = dict(dtype=jnp.bool_)
    admit = eng.prefill._admit_chunk.lower(
        eng.params, eng.state, eng.caches, arr(ns, C), arr(ns, **flag),
        arr(ns, **flag), arr(ns), arr(ns), arr(ns), arr(ns), arr(ns, cap),
        arr(ns, **flag), arr(ns, mp), arr(ns), arr(ns), arr(ns), arr(P),
        arr(P)).compile().as_text()
    tick = eng.decode.tick.lower(eng.params, eng.state,
                                 eng.caches).compile().as_text()
    assert admit.startswith("HloModule jit_admit")
    assert tick.startswith("HloModule jit_tick")
    for text in (admit, tick):
        for scope in SCOPES:
            assert f"/{scope}/" in text, scope
