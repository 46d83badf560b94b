"""The measured loop: requests go in through `Engine.submit`, time
advances through `Engine.step`, and nothing else of the engine is
called.  Every token is stamped on the host when the `step` that
delivered it returns; a request's first token and its first decoded
token arrive in one step and so read a gap of 0.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

TraceAnnotation = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Tracked:
    planned: object
    req: object = None
    due: float = 0.0              # absolute perf_counter due time
    submitted: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    t_open: float
    t_end: float
    tracked: list
    ticks: list                   # per tick: read lengths of its sequences
    admitted: int                 # requests whose first token came in it
    prefill_tokens: int           # their prompt tokens
    admit_calls: int              # Engine.n_admit_calls in the window
    compiles: int                 # compile events in the window
    late_s: list                  # submit - due, open-loop window requests


class CompileCounter:
    """Counts JAX compile events (and keeps their names) while `on`."""

    def __init__(self):
        self.on, self.names = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @property
    def count(self) -> int:
        return len(self.names)

    def _event(self, name, _secs, **_kw):
        if self.on and "compile" in name:
            self.names.append(name)


def _observe(live, now, win, open_):
    """Stamp tokens that appeared since the last step; record the tick's
    read lengths.  Returns the requests still unfinished."""
    lengths, still = [], []
    for t in live:
        req = t.req
        n, seen = len(req.out_tokens), len(t.stamps)
        if n > seen:
            if seen == 0 and open_:
                win.admitted += 1
                win.prefill_tokens += len(req.prompt)
            first_tick = max(seen, 1)
            lengths += [len(req.prompt) + j for j in range(first_tick, n)]
            t.stamps += [now] * (n - seen)
        if not req.done:
            still.append(t)
    if open_ and lengths:
        win.ticks.append(lengths)
    return still


def warm_compile(engine, vocab: int) -> None:
    """One short request through admit and tick: every program this cell
    uses is compiled (or read from the persistent cache) here.  An
    admission round that only evicts cached prefixes commits its
    refcount delta with one eager add on the pool's refcounts
    (`pages.apply_refs_delta`); the same add, on the same shapes, is made
    here and thrown away, so that round compiles nothing in the window."""
    req = engine.submit(np.arange(1, 17, dtype=np.int32) % vocab, 3)
    while not req.done:
        engine.step()
    refs = engine.state.pages.refs
    jax.block_until_ready(refs + jnp.zeros(refs.shape, refs.dtype))


def run(engine, planned: list, mix: dict, seconds: float,
        counter: CompileCounter, trace_cm=None) -> Window:
    """Serve `planned` and measure a window of `seconds`.  The window
    opens at the first step boundary at or after its planned start (an
    open loop's warm-up ends there, and its window arrivals move with it;
    a backlog's first wave is admitted before it) and closes at the first boundary at or after `seconds`
    later; the trace, when asked for, spans exactly the window."""
    trace_cm = trace_cm or contextlib.nullcontext()
    open_loop = mix["kind"] == "open_loop"
    tracked = [Tracked(p) for p in planned]
    live = []
    win = Window(0.0, 0.0, tracked, [], 0, 0, 0, 0, [])
    t0 = time.perf_counter()
    if open_loop:
        for t in tracked:
            t.due = t0 + t.planned.due
        t_open = t0 + float(mix["warmup_s"])
    else:
        for t in tracked:
            t.req = engine.submit(t.planned.prompt, t.planned.max_new)
            t.due = t.submitted = t0
        live = list(tracked)
        engine.step()                 # admits the first wave
        live = _observe(live, time.perf_counter(), win, False)
        t_open = time.perf_counter()
    pending = [t for t in tracked if t.req is None]
    admit0 = None
    opened = False
    with contextlib.ExitStack() as stack:
        while True:
            now = time.perf_counter()
            if not opened and now >= t_open:
                opened = True
                stack.enter_context(trace_cm)
                stack.enter_context(TraceAnnotation("harness.window"))
                shift = time.perf_counter() - t_open
                t_open += shift
                t_close = t_open + seconds
                # a step that ran past the planned open delays the window's
                # arrivals with it, so the window holds all of them
                for t in pending:
                    if t.planned.phase == "window":
                        t.due += shift
                admit0 = engine.n_admit_calls
                counter.on = True
            if opened and now >= t_close:
                break
            if pending and pending[0].due <= now:
                with TraceAnnotation("harness.submit"):
                    while pending and pending[0].due <= now:
                        t = pending.pop(0)
                        t.req = engine.submit(t.planned.prompt,
                                              t.planned.max_new)
                        t.submitted = time.perf_counter()
                        if opened:
                            win.late_s.append(t.submitted - t.due)
                        live.append(t)
            if live:
                with TraceAnnotation("engine.step"):
                    engine.step()
                with TraceAnnotation("harness.observe"):
                    live = _observe(live, time.perf_counter(), win, opened)
            else:
                wake = t_close if opened else t_open
                if pending:
                    wake = min(wake, pending[0].due)
                with TraceAnnotation("harness.idle"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
        win.t_end = time.perf_counter()
        counter.on = False
    win.t_open = t_open
    win.admit_calls = engine.n_admit_calls - admit0
    win.compiles = counter.count
    return win


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(win: Window, open_loop: bool) -> dict:
    """The end-to-end numbers of one window, all on the host clock."""
    lo, hi = win.t_open, win.t_end
    tokens, gaps, ttft = 0, [], []
    for t in win.tracked:
        st = t.stamps
        tokens += sum(1 for s in st if lo < s <= hi)
        gaps += [b - a for a, b in zip(st, st[1:]) if lo < b <= hi]
        if open_loop and t.planned.phase == "window":
            first = st[0] if st else hi
            ttft.append(min(first, hi) - t.due)
    out = {"output_tok_s": tokens / (hi - lo),
           "itl_p50_ms": 1e3 * pct(gaps, 50),
           "itl_p95_ms": 1e3 * pct(gaps, 95)}
    if open_loop:
        out["ttft_p90_ms"] = 1e3 * pct(ttft, 90)
    counts = {"tokens": tokens, "gaps": len(gaps), "first_tokens": len(ttft)}
    return out, counts
