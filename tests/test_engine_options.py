"""EngineOptions / RequestResult surface (runtime/options.py) and the
engine behavior it controls: sectioned-options vs legacy flat-kwarg
construction equivalence, validation error parity with the historic loose
kwargs, the submit-time max_seq budget clamp, per-request stop sets,
abort, and the structured completion record (finish reasons + serving
counters)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.runtime.options import (DebugOptions, EngineOptions,
                                   PagingOptions, RequestResult,
                                   ScheduleOptions, SpeculationOptions)
from repro.runtime.sampling import SamplingConfig
from repro.runtime.serve import Engine

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b", smoke=True)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


# --- construction and validation --------------------------------------------

def test_options_sections_validate_at_construction():
    with pytest.raises(ValueError, match="decode_steps must be >= 1"):
        ScheduleOptions(decode_steps=0)
    with pytest.raises(ValueError, match="kv_layout must be 'paged'"):
        PagingOptions(kv_layout="ragged")
    with pytest.raises(ValueError, match="num_pages must be >= 1"):
        PagingOptions(num_pages=0)
    with pytest.raises(ValueError, match="draft_len must be >= 0"):
        SpeculationOptions(draft_len=-1)
    with pytest.raises(ValueError, match="ngram must be >= 2"):
        SpeculationOptions(ngram=1)
    with pytest.raises(ValueError, match="drafter must be 'ngram' or"):
        SpeculationOptions(drafter="oracle")
    with pytest.raises(ValueError, match="draft_bits"):
        SpeculationOptions(draft_bits=3)
    with pytest.raises(ValueError, match="draft_layers"):
        SpeculationOptions(draft_layers=0)
    with pytest.raises(ValueError, match="sampling method"):
        EngineOptions(sampling="argmax")
    with pytest.raises(TypeError, match="EngineOptions.schedule"):
        EngineOptions(schedule={"num_slots": 2})


def test_build_merges_legacy_kwargs_over_base():
    """EngineOptions.build reproduces the loose-kwarg semantics: sampling
    method + knobs assemble in one shot, eos_id becomes a one-token stop
    set (explicit stop_tokens wins), None means not-given, and unknown
    names raise like bad keywords."""
    o = EngineOptions.build(sampling="top_k", top_k=5, num_slots=3,
                            eos_id=7, draft_len=2)
    assert o.sampling == SamplingConfig(method="top_k", top_k=5)
    assert o.schedule.num_slots == 3
    assert o.schedule.stop_tokens == (7,)
    assert o.speculation.draft_len == 2
    # explicit stop_tokens beats eos_id; base fields survive the merge
    base = EngineOptions(schedule=ScheduleOptions(max_seq=48, seed=9))
    o = EngineOptions.build(base=base, eos_id=7, stop_tokens=(1, 2),
                            num_slots=2)
    assert o.schedule.stop_tokens == (1, 2)
    assert o.schedule.max_seq == 48 and o.schedule.seed == 9
    assert o.schedule.num_slots == 2
    # None = not given (the launcher passes `x or None` everywhere)
    o = EngineOptions.build(num_pages=None, prefix_chunk=None)
    assert o.paging.num_pages is None
    with pytest.raises(ValueError, match="top_k sampling needs top_k"):
        EngineOptions.build(sampling="top_k")
    with pytest.raises(TypeError, match="unknown Engine option 'pages'"):
        EngineOptions.build(pages=4)


def test_launcher_builds_engine_inputs_from_its_command_line(granite):
    """`launch.serve`'s builders (shared with chip_smoke.py) turn the
    command line into the config, the options and weights of the same
    shapes as `M.init_params`, drawn inside one jit."""
    from repro.launch import serve
    cfg, params = granite
    args = serve.parser().parse_args(
        ["--arch", "granite-8b", "--smoke", "--slots", "3", "--max-seq",
         "64", "--decode-kernel", "off", "--prefix-cache", "off"])
    assert serve.model_config(args) == cfg
    opts = serve.engine_options(args)
    assert (opts.schedule.num_slots, opts.schedule.max_seq) == (3, 64)
    assert opts.paging.decode_kernel is False
    assert not opts.prefix.enabled and opts.parallel.mesh is None
    got = serve.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_engine_validation_errors_survive_the_redesign(granite):
    """The exact error messages older callers match on still raise from
    Engine(...) whichever construction path is used."""
    cfg, params = granite
    with pytest.raises(ValueError, match="decode_steps must be >= 1"):
        Engine(cfg, params, num_slots=1, max_seq=8, decode_steps=0)
    with pytest.raises(ValueError, match="kv_layout must be 'paged'"):
        Engine(cfg, params, num_slots=1, max_seq=8, kv_layout="x")
    with pytest.raises(ValueError, match="dispatch must be 'global'"):
        Engine(cfg, params, num_slots=1, max_seq=8, dispatch="round_robin")


def test_legacy_kwargs_equal_options_construction(granite):
    """Same engine both ways: identical streams, identical baked knobs."""
    cfg, params = granite
    prompt = [2, 4, 6, 8, 2, 4]
    legacy = Engine(cfg, params, num_slots=2, max_seq=32, decode_steps=2,
                    sampling="temperature", temperature=0.7, seed=5,
                    draft_len=3, eos_id=None, check_invariants=True)
    opts = EngineOptions(
        sampling=SamplingConfig(method="temperature", temperature=0.7),
        schedule=ScheduleOptions(num_slots=2, max_seq=32, decode_steps=2,
                                 seed=5),
        speculation=SpeculationOptions(draft_len=3),
        debug=DebugOptions(check_invariants=True))
    modern = Engine(cfg, params, options=opts)
    assert modern.options == legacy.options
    ra = legacy.submit(prompt, 10, seed=1)
    rb = modern.submit(prompt, 10, seed=1)
    legacy.run(), modern.run()
    assert ra.out_tokens == rb.out_tokens
    # per-call legacy kwargs override a base options bundle
    over = Engine(cfg, params, options=opts, decode_steps=1)
    assert over.decode_steps == 1
    assert over.options.schedule.max_seq == 32


# --- submit clamp, stop sets, finish reasons --------------------------------

def test_submit_clamps_budget_to_max_seq_deterministically(granite):
    """Bugfix: len(prompt) + max_new_tokens > max_seq used to run the
    request into the ceiling silently.  Now the budget clamps at submit
    (visible on the Request) and the result says finish_reason='max_seq';
    the emitted stream is unchanged by the clamp."""
    cfg, params = granite
    prompt = np.arange(1, 29, dtype=np.int32)            # plen 28
    eng = Engine(cfg, params, num_slots=1, max_seq=32)
    r = eng.submit(prompt, 16)
    assert r.clamped and r.requested == 16 and r.max_new_tokens == 4
    (res,) = eng.run()
    assert res.finish_reason == "max_seq"
    assert len(res.tokens) == 4
    # an exact fit is not a clamp: the budget is the binding constraint
    eng = Engine(cfg, params, num_slots=1, max_seq=32)
    r = eng.submit(prompt, 4)
    assert not r.clamped
    (res,) = eng.run()
    assert res.finish_reason == "budget" and res.tokens == tuple(r.out_tokens)


def test_per_request_stop_tokens_and_eos_reason(granite):
    cfg, params = granite
    prompt = [5, 9, 5, 9, 5, 9]
    eng = Engine(cfg, params, num_slots=1, max_seq=64)
    ref = eng.submit(prompt, 16)
    eng.run()
    stream = ref.out_tokens
    # multi-token stop set: first member reached wins
    stops = (stream[5], stream[2])
    cut = min(stream.index(s) for s in stops)
    eng = Engine(cfg, params, num_slots=1, max_seq=64)
    r = eng.submit(prompt, 16, stop_tokens=stops)
    (res,) = eng.run()
    assert res.finish_reason == "eos"
    assert list(res.tokens) == stream[:cut + 1]
    # engine-level default stop set applies when submit passes none
    eng = Engine(cfg, params, num_slots=1, max_seq=64,
                 stop_tokens=(stream[2],))
    r = eng.submit(prompt, 16)
    eng.run()
    assert r.result.finish_reason == "eos"
    assert list(r.result.tokens) == stream[:stream.index(stream[2]) + 1]
    # a stop set past the baked capacity is rejected eagerly
    with pytest.raises(ValueError, match="stop_tokens"):
        eng.submit(prompt, 4, stop_tokens=(1, 2, 3, 4, 5))


def test_abort_queued_and_running(granite):
    cfg, params = granite
    prompt = [3, 1, 4, 1, 5, 9]
    eng = Engine(cfg, params, num_slots=1, max_seq=64,
                 check_invariants=True)
    run_req = eng.submit(prompt, 30)
    queued = eng.submit(prompt, 30)
    # queued: removed before it ever runs, zero tokens
    assert eng.abort(queued)
    assert queued.done and queued.result.finish_reason == "aborted"
    assert queued.result.tokens == ()
    eng.step()
    # running: slot freed immediately, emitted tokens kept
    held = eng.pages_in_use
    assert eng.abort(run_req)
    assert run_req.result.finish_reason == "aborted"
    assert len(run_req.result.tokens) >= 1
    assert eng.pages_in_use < held
    assert not eng.abort(run_req)          # already finished
    # the engine keeps serving after aborts; run() drains everything
    # completed since the last drain, the aborts included
    nxt = eng.submit(prompt, 4)
    results = eng.run()
    assert [r.uid for r in results] == [queued.uid, run_req.uid, nxt.uid]
    assert nxt.result.finish_reason == "budget"


def test_run_returns_results_in_completion_order(granite):
    cfg, params = granite
    eng = Engine(cfg, params, num_slots=2, max_seq=48)
    short = eng.submit([1, 2, 3], 3)
    long = eng.submit([4, 5, 6], 12)
    results = eng.run()
    assert [r.uid for r in results] == [short.uid, long.uid]
    assert all(isinstance(r, RequestResult) for r in results)
    assert eng.run() == []                 # drained
    assert short.result is results[0]


def test_result_counters_prefill_and_pages_shared(granite):
    """prefill_tokens counts the prompt rows whose compute actually ran
    (warm prefix admissions skip the shared pages), and pages_shared
    counts the read-only page mappings."""
    cfg, params = granite
    rng = np.random.default_rng(0)
    sys_p = list(rng.integers(1, cfg.vocab_size, 2 * cfg.page_size))
    eng = Engine(cfg, params, num_slots=2, max_seq=96, prefix_cache=True)
    cold = eng.submit(sys_p + [1, 2, 3], 4)
    eng.run()
    warm = eng.submit(sys_p + [7, 8, 9], 4)
    eng.run()
    assert cold.result.prefill_tokens == len(sys_p) + 3
    assert cold.result.pages_shared == 0
    assert warm.result.pages_shared == 2
    assert warm.result.prefill_tokens == 3
    assert warm.result.finish_reason == "budget"


def test_request_result_is_validated_and_frozen():
    r = RequestResult(uid=0, tokens=[np.int32(3), 4], finish_reason="eos")
    assert r.tokens == (3, 4) and all(isinstance(t, int) for t in r.tokens)
    with pytest.raises(ValueError, match="finish_reason"):
        RequestResult(uid=0, tokens=(), finish_reason="done")
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.tokens = ()
