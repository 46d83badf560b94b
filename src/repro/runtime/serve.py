"""Device-resident continuous-batching engine (the paper's §VI-D
tiling-based inference mode: quantized weights stay resident, inputs
stream).

Since PR 10 the engine is a thin COMPOSITION of three layers:

  runtime.scheduler  — every host decision: the FIFO queue, admission
                       planning with backpressure, the `pages.HostPool`
                       mirror(s), the prefix registry, request
                       lifecycle and results.
  runtime.workers    — every device computation: `PrefillWorker` owns
                       the chunked admit path, `DecodeWorker` the fused
                       multi-step tick (plain or speculative) — both
                       compiled once at construction.
  Engine (here)      — the composition and the public API (`submit`,
                       `step`, `run`, `abort`, telemetry), unchanged
                       from the pre-split engine: a colocated Engine
                       points both workers at the SAME state/caches and
                       streams bit-identically to the monolith.

KV layouts (`kv_layout=`):

  "paged" (default) — the BRAMAC memory discipline applied to the cache:
           attention KV lives in a shared pool of fixed `cfg.page_size`-row
           pages ("BRAM-array-sized" blocks) addressed through per-slot
           int32 block tables.  ALL pool mutation goes through the
           refcounted allocator in `repro.runtime.pages` — grants at
           admission (lowest free page id first — deterministic),
           refcount-bumped read-only shares for prefix-cache hits,
           release-to-zero reclaim the moment a request terminates inside
           the fused tick (or at admission, for first-token EOS).  When
           the pool runs dry the admitter first evicts idle cached
           prefixes (LRU), then exerts backpressure: queued requests
           wait, FIFO, until a terminating request reclaims enough pages.
           Greedy token streams stay bit-identical to the dense layout
           (masked pool rows contribute exact zeros to the softmax, like
           the dense cache's untouched rows).

  "dense" — the PR-4 layout: every slot reserves `max_seq` KV rows up
           front; kept as the parity oracle and for kernels that want the
           contiguous reservation.

Disaggregated mode (`disagg=True` / `EngineOptions.disagg`, paged
layout only): prefill and decode run as SEPARATE workers with separate
page pools and slot sets.  A prompt admits into the prefill worker's
pool, prefills there (first token sampled at admission, so TTFT is
unchanged), then its KV pages move into the decode worker's pool at
page granularity — `pages.export_pages` gathers the tiles, the decode
mirror grants destination ids by the same lowest-free-id rule, and
`pages.import_pages`/`adopt` land contents bit-exactly (invariant I7
in `runtime/pages.py`; `check_invariants=True` verifies I1–I7 on BOTH
pools after every transfer round).  When the decode pool is dry or no
decode slot is free the transfer backpressures FIFO; greedy streams
stay bit-identical to the colocated engine.  `role="both"` runs both
workers in-process (today's only transport); "prefill"/"decode" name
the endpoints of the future multi-process transport and raise
NotImplementedError.  Prefix caching and speculation switch off under
disaggregation, and archs with per-slot cache leaves (recurrent
hybrids, xattn) are rejected — their state has no page representation
to transfer.

Prefix caching (`prefix_cache=True`, paged layout only): prompts are
hashed at `submit` in fixed `prefix_chunk`-token pieces; admission maps
the longest cached prefix's full pages into the slot's block table
read-only (skipping their prefill compute entirely, so warm-prefix TTFT
drops by ~the shared length) and the slot's own prefill starts at the
matched offset.  A partially covered page is handed over as a private
copy (copy-on-write) instead, so cached pages are never written.
Recurrent-hybrid archs opt out silently (their state accumulates over
every token) but stream identically.

Speculative decoding (`EngineOptions.speculation.draft_len > 0`): each
tick step drafts `draft_len` tokens from the configured drafter —
`drafter="ngram"` (default), a device-resident per-slot n-gram table,
or `drafter="model"`, the serving model's own weights requantized to
`draft_bits` (2-bit BRAMAC datapath) decoding through a private
per-slot draft KV cache that rides inside SlotState
(`runtime/speculate.py`) — scores the whole window [last_tok, g_1..g_d]
in ONE forward through the same chunked path prefill uses, and
accepts/replaces every position on device (`sampling.spec_verify`).
Greedy streams are bit-identical to non-speculative decoding
(invariants A1-A6 in speculate.py); the host still syncs once per tick
whatever the acceptance length.  Recurrent-hybrid, cross-attention and
MoE archs opt out silently, and the model drafter additionally opts
out of the prefix cache.

Construction: `Engine(cfg, params, options=EngineOptions(...))` is the
primary constructor (`repro.runtime.options`); the historic flat kwargs
are still accepted and merged over `options` via `EngineOptions.build`.
Completed requests carry a structured `options.RequestResult` (tokens,
finish_reason, prefill/speculation/page-sharing counters) in
`Request.result`, and `Engine.run` returns the results completed during
the call.

The Engine is a context manager so the process-global sharding ctx
activated by `mesh=` is released even when serving raises — including
when `__init__` itself raises after activation (construction cleans up
behind itself and `close()` is idempotent).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.kernels import paged_attention as pk_kernel
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.runtime import pages as pg
from repro.runtime import speculate as spc
from repro.runtime.options import EngineOptions, RequestResult
from repro.runtime.scheduler import Request, Scheduler
from repro.runtime.workers import (DecodeWorker, PrefillWorker, SlotState,
                                   init_slot_state)

__all__ = ["Engine", "Request", "SlotState", "RequestResult"]


class Engine:
    """`mesh` switches on the sharded decode path: the inference sharding
    profile (`serve_rules`: weights tensor-parallel over `model`, no FSDP
    all-gathers) is activated for the engine's lifetime and the parameter
    tree — float or pre-quantized `QuantizedTensor` leaves alike — is
    placed onto the mesh, so every jit'd prefill/decode below runs
    tensor-parallel.

    Sampling and scheduling knobs (all baked into the compiled functions,
    so they must be set at construction):
      sampling      — "greedy" | "temperature" | "top_k" | "top_p", or a
                      ready-made `sampling.SamplingConfig`
      temperature / top_k / top_p — parameters of the stochastic methods
      decode_steps  — decode steps fused per tick (host syncs per
                      generated token scale as 1/decode_steps)
      prefill_chunk — prompt chunk size for admission (forced to 1 on
                      recurrent mixers); one jit serves every length
      seed          — engine base seed; a request's stream is keyed by
                      fold_in(base, request.seed) only, so it reproduces
                      across slots and co-batched traffic
      stop_tokens   — default per-request stop set; `eos_id=N` is legacy
                      shorthand for stop_tokens=(N,), and submit's
                      stop_tokens= overrides per request
      draft_len     — speculative draft window per decode step; 0 (the
                      default) disables speculation entirely
      spec_ngram / spec_table — n-gram order and per-slot table buckets
                      of the self-speculation drafter (speculate.py)
      drafter       — "ngram" (default) or "model": the 2-bit BRAMAC
                      draft model (the serving weights requantized to
                      draft_bits, optionally truncated to draft_layers
                      blocks) proposing through a private draft KV cache
      kv_layout     — "paged" (default) or "dense" (see module docstring)
      num_pages     — paged pool size; default num_slots * ceil(max_seq /
                      cfg.page_size) (capacity-equal to dense — shrink it
                      to trade co-residency for memory)
      prefix_cache  — share cached prompt prefixes across requests
                      (paged layout only; recurrent mixers opt out)
      prefix_chunk  — prefix hash granularity in tokens (default
                      cfg.page_size; smaller values trade more
                      copy-on-write splits for finer matching)
      prefix_max_chains — registry capacity: LRU chains beyond this are
                      evicted at registration time, bounding host memory
                      under high-cardinality traffic (default 4096)
      disagg        — split prefill and decode into separate workers with
                      separate page pools; see the module docstring and
                      `options.DisaggOptions` (role / prefill_slots /
                      prefill_pages)
      check_invariants — verify the HostPool mirror(s) against the device
                      allocator(s) (refcounts, free popcount, block
                      tables; under disagg also the I7 bit-exact transfer
                      check) after every sync; debug aid, costs extra
                      transfers
    """

    def __init__(self, cfg, params, num_slots: int | None = None,
                 max_seq: int | None = None, *,
                 options: EngineOptions | None = None, **legacy):
        # `options` is the primary constructor surface; any flat legacy
        # kwargs (including the positional num_slots/max_seq) are merged
        # over it by EngineOptions.build, which owns all validation.
        if num_slots is not None:
            legacy["num_slots"] = num_slots
        if max_seq is not None:
            legacy["max_seq"] = max_seq
        options = EngineOptions.build(base=options, **legacy)
        # close() must be callable on a partially constructed engine: the
        # sharding ctx is process-global, so a construction that raises
        # AFTER activate (drafter validation, cache init OOM, ...) would
        # otherwise leave it held and poison every later Engine/trainer
        # in the process.
        self._ctx = None
        self.mesh = None
        try:
            self._build(cfg, params, options)
        except BaseException:
            self.close()
            raise

    def _build(self, cfg, params, options: EngineOptions) -> None:
        self.options = options
        sch, par, dis = options.schedule, options.parallel, options.disagg
        num_slots, max_seq = sch.num_slots, sch.max_seq
        # capacity_factor / dispatch override the MoE routing knobs on cfg
        # (moe_capacity_factor / ep_dispatch) for this engine — the jit'd
        # prefill/decode close over cfg, so the override must happen here,
        # before any tracing.
        if par.dispatch is not None:
            cfg = cfg.replace(ep_dispatch=par.dispatch)
        if par.capacity_factor is not None:
            cfg = cfg.replace(moe_capacity_factor=float(par.capacity_factor))
        mesh = par.mesh
        if mesh is not None and not isinstance(mesh, jax.sharding.Mesh):
            mesh = shd.build_mesh(mesh)
        self.mesh = mesh
        if mesh is not None:
            self._ctx = shd.activate(mesh,
                                     shd.serve_rules("pod" in mesh.axis_names))
            params = jax.device_put(params,
                                    shd.param_shardings(params, self._ctx))
        self.cfg, self.params = cfg, params
        self.num_slots, self.max_seq = num_slots, max_seq
        self.stop_tokens = sch.stop_tokens
        # legacy attr: the single-token stop set older callers passed
        self.eos_id = sch.stop_tokens[0] if len(sch.stop_tokens) == 1 \
            else None
        self.sampling = options.sampling
        self.decode_steps = sch.decode_steps
        self.check_invariants = options.debug.check_invariants
        # recurrent mixers (mamba/mlstm/slstm) can't skip padding in their
        # state, so their prompts are fed token-by-token (chunk = 1); a
        # chunk can never exceed the cache (its write must fit max_seq)
        recurrent = any(m in spec for spec in cfg.layer_pattern
                        for m in ("mamba", "mlstm", "slstm"))
        self.prefill_chunk = 1 if recurrent \
            else max(1, min(sch.prefill_chunk, max_seq - 1))
        # --- disaggregation (paged + meshless + pool-representable only:
        # the transfer unit is the page, so every cache leaf must live in
        # the shared pool — recurrent/xattn per-slot state cannot move)
        self.disagg = bool(dis.enabled)
        if self.disagg:
            if dis.role in ("prefill", "decode"):
                raise NotImplementedError(
                    f"role={dis.role!r} is the single-process endpoint of "
                    f"the multi-process transport, which is not implemented "
                    f"yet — the page-transfer seam (pages.export_pages / "
                    f"import_pages) is where it plugs in; use role='both'")
            if options.paging.kv_layout != "paged":
                raise ValueError("disaggregation requires kv_layout="
                                 "'paged': pages are the transfer unit")
            if mesh is not None:
                raise ValueError("disaggregation and mesh= are mutually "
                                 "exclusive (single-process transport)")
            if not all(jax.tree_util.tree_leaves(M.cache_pool_flags(cfg))):
                raise ValueError(
                    "disaggregation requires every cache leaf to live in "
                    "the shared page pool; recurrent/xattn per-slot state "
                    f"has no page representation to transfer "
                    f"(layer_pattern={cfg.layer_pattern})")
        # --- speculation (silent opt-outs: recurrent state cannot rewind
        # a rejected draft; xattn decode needs vision inputs; MoE stays
        # off although serving is dropless (no cell runs speculation on
        # experts yet: ROADMAP Queue 3, item 4); disagg drafter state has
        # no page representation to transfer)
        spec_ok = not recurrent and not self.disagg \
            and not any("xattn" in s or "moe" in s
                        for s in cfg.layer_pattern)
        self.draft_len = min(options.speculation.draft_len,
                             max(0, max_seq - 2)) if spec_ok else 0
        self.drafter_kind = options.speculation.drafter \
            if self.draft_len else None
        if not self.draft_len:
            self.drafter = None
        elif self.drafter_kind == "model":
            # the 2-bit BRAMAC draft model: the engine's own weights
            # requantized, with a private per-slot draft KV cache riding
            # inside SlotState (speculate.QuantDrafter, invariant A6)
            self.drafter = spc.QuantDrafter.build(
                cfg, params, max_seq,
                bits=options.speculation.draft_bits,
                draft_layers=options.speculation.draft_layers)
        else:
            self.drafter = spc.NGramDrafter(options.speculation.ngram,
                                            options.speculation.table)
        self._stop_cap = max(4, len(self.stop_tokens))
        self._base_key = jax.random.PRNGKey(sch.seed)
        # --- KV layout ---
        self.kv_layout = options.paging.kv_layout
        self.page_size = cfg.page_size
        self.pages_per_slot = -(-max_seq // self.page_size)  # table length
        paged = self.kv_layout == "paged"
        if paged:
            self.num_pages = int(options.paging.num_pages) \
                if options.paging.num_pages is not None \
                else num_slots * self.pages_per_slot
            self._pool_flags = M.cache_pool_flags(cfg)
            mp, P = self.pages_per_slot, self.num_pages
        else:
            self.num_pages = 0
            self._pool_flags = None
            mp, P = 0, 0
        # disagg sizing: the prefill worker's own slot set and pool
        self.prefill_slots = (int(dis.prefill_slots)
                              if dis.prefill_slots is not None
                              else num_slots) if self.disagg else num_slots
        self.prefill_pages = (int(dis.prefill_pages)
                              if dis.prefill_pages is not None
                              else self.prefill_slots * self.pages_per_slot) \
            if self.disagg else self.num_pages
        # dense speculative rollback routes through the KV leaf flags
        # (same tree structure as the paged pool flags)
        self._kv_flags = M.cache_pool_flags(cfg) \
            if self.draft_len and self.kv_layout == "dense" else None
        # --- pallas decode kernel (Sq=1 paged reads walk the block table
        # page by page; None = auto: real TPU only — interpret mode on CPU
        # is correct but slow, so CPU callers opt in).  The speculative
        # tick verifies Sq=draft+1 windows and keeps the gather oracle, as
        # does any mesh-sharded engine (the kernel carries no partition
        # annotations).
        dk = options.paging.decode_kernel
        self.decode_kernel = bool(
            paged and mesh is None and not self.draft_len
            and (dk if dk is not None
                 else jax.default_backend() == "tpu"))
        # --- prefix cache (paged only; recurrent state accumulates over
        # every token, so those archs cannot share prefixes — they opt out
        # silently but stream identically.  The model drafter opts out
        # too: a warm-prefix chunk skips its prefill compute, which would
        # leave the corresponding DRAFT-cache rows unwritten and break
        # invariant A6.  Disaggregation opts out as well: cached pages
        # would pin the prefill pool while the decode reads happen in the
        # other pool — streams stay bit-identical, admission just runs
        # the full prefill) ---
        self.prefix_chunk = int(options.prefix.chunk) \
            if options.prefix.chunk is not None else self.page_size
        enabled = options.prefix.enabled and paged and not recurrent \
            and self.drafter_kind != "model" and not self.disagg
        prefix = pg.PrefixCache(self.prefix_chunk, self.page_size,
                                max_chains=options.prefix.max_chains) \
            if enabled else None
        # --- the host-side scheduler (admission side = prefill side) ---
        self.sched = Scheduler(
            num_slots=self.prefill_slots, max_seq=max_seq,
            page_size=self.page_size, prefill_chunk=self.prefill_chunk,
            paged=paged,
            num_pages=self.prefill_pages if self.disagg else self.num_pages,
            stop_cap=self._stop_cap, stop_tokens=self.stop_tokens,
            prefix=prefix)
        if self.disagg:
            self.sched.attach_decode(num_slots, self.num_pages)
        # --- the device-facing workers ---
        self.prefill = PrefillWorker(
            cfg=cfg, num_slots=self.prefill_slots, max_seq=max_seq,
            prefill_chunk=self.prefill_chunk, stop_cap=self._stop_cap,
            sampling=self.sampling, base_key=self._base_key,
            kv_layout=self.kv_layout, pool_flags=self._pool_flags,
            draft_len=self.draft_len, drafter=self.drafter)
        self.decode = DecodeWorker(
            cfg=cfg, num_slots=num_slots, max_seq=max_seq,
            decode_steps=self.decode_steps, sampling=self.sampling,
            kv_layout=self.kv_layout, decode_kernel=self.decode_kernel,
            draft_len=self.draft_len, drafter=self.drafter,
            pool_flags=self._pool_flags, kv_flags=self._kv_flags)
        # --- device state: one state/caches pair per pool (a colocated
        # engine has exactly one — both workers share it)
        draft0 = self.drafter.init_state(num_slots) if self.draft_len \
            else spc.empty_state(num_slots)
        self.state = init_slot_state(num_slots, self._stop_cap, mp,
                                     self.num_pages if paged else 0, draft0)
        make_caches = functools.partial(
            M.init_cache, cfg, num_slots, max_seq,
            num_pages=self.num_pages if paged else None)
        if mesh is None:
            self.caches = make_caches()
        else:
            # built in place on the mesh: an eager init would stage the
            # whole cache on the first device
            self.caches = jax.jit(make_caches, out_shardings=(
                shd.param_shardings(jax.eval_shape(make_caches), self._ctx,
                                    shd.CACHE_RULES)))()
        if self.disagg:
            self.p_state = init_slot_state(
                self.prefill_slots, self._stop_cap, mp, self.prefill_pages,
                spc.empty_state(self.prefill_slots))
            self.p_caches = M.init_cache(cfg, self.prefill_slots, max_seq,
                                         num_pages=self.prefill_pages)
        # pool-occupancy telemetry; occupancy itself lives in the HostPool
        # mirror(s), kept in lockstep with the device allocator(s) so
        # backpressure never needs an extra sync.  pages_high_water always
        # tracks the DECODE-side pool (the colocated engine's only pool).
        self.pages_high_water = 0
        self.pages_shared_high_water = 0
        self.prefill_pages_high_water = 0
        self.prefill_chunks_skipped = 0
        # disagg transfer telemetry
        self.pages_transferred = 0
        self.transfer_rounds = 0
        # host<->device sync accounting for the serving bench: one sync per
        # jit'd tick / per admission round, regardless of decode_steps
        self.n_ticks = 0
        self.n_admit_calls = 0
        # prompt rows those admit calls computed (each pads to
        # prefill_slots x prefill_chunk rows)
        self.n_admit_rows = 0
        self.n_syncs = 0
        self.n_generated = 0
        # decode KV read accounting (kernels/paged_attention currency):
        # bytes the decode path reads from the KV cache, accumulated per
        # tick from the tick-start slot lengths (allocation is fixed
        # within a tick, so this undercounts each slot by at most one
        # page over the tick — deterministic given the same schedule).
        self.kv_bytes_read = 0
        self.kv_read_steps = 0
        self._kv_row_bytes = pk_kernel.kv_row_bytes(cfg)

    # ------------------------------------------------------------------
    # back-compat surface: the host structures moved into the Scheduler
    # ------------------------------------------------------------------

    @property
    def pool(self) -> pg.HostPool | None:
        """The decode-side HostPool mirror (the colocated engine's only
        pool); None under the dense layout."""
        return self.sched.decode_pool

    @property
    def prefix(self) -> pg.PrefixCache | None:
        return self.sched.prefix

    @property
    def slot_req(self) -> list:
        """Decode-side slot occupancy (the colocated engine's only slot
        registry)."""
        return self.sched.decode_slot_req

    @property
    def _queue(self) -> list:
        return self.sched.queue

    @property
    def tokens_drafted(self) -> int:
        return self.sched.tokens_drafted

    @property
    def tokens_accepted(self) -> int:
        return self.sched.tokens_accepted

    # ------------------------------------------------------------------
    # host-side request plumbing
    # ------------------------------------------------------------------

    def _need_pages(self, prompt_len: int, max_new: int) -> int:
        return self.sched._need_pages(prompt_len, max_new)

    def submit(self, prompt, max_new_tokens: int = 16,
               seed: int | None = None,
               stop_tokens: tuple | None = None) -> Request:
        """Queue a prompt.  `stop_tokens` overrides the engine's default
        stop set for this request (any emitted token in the set ends the
        stream, finish_reason="eos").  A budget that cannot fit the cache
        is clamped deterministically here — the request then runs to the
        max_seq ceiling and finishes with reason "max_seq" instead of
        silently stopping short."""
        return self.sched.submit(prompt, max_new_tokens, seed, stop_tokens)

    def _admit_side(self):
        """(state, caches) of the pool admission lands in: the prefill
        worker's own pool under disagg, THE pool otherwise."""
        return (self.p_state, self.p_caches) if self.disagg \
            else (self.state, self.caches)

    def _set_admit_side(self, state, caches) -> None:
        if self.disagg:
            self.p_state, self.p_caches = state, caches
        else:
            self.state, self.caches = state, caches

    @functools.partial(annotate_function, name="engine.admit")
    def _admit(self) -> None:
        paged = self.kv_layout == "paged"
        rnd = self.sched.plan_round()
        if rnd is None:
            return
        if not rnd.admitted:
            # eviction-only round: the registry already dropped its
            # chains host-side; commit the decrements on the device pool
            with TraceAnnotation("engine.evict"):
                st, ca = self._admit_side()
                P = st.pages.refs.shape[0]
                ev = np.zeros((P,), np.int32)
                for p, d in rnd.evict_delta.items():
                    ev[p] = d
                st = st._replace(pages=pg.apply_refs_delta(st.pages,
                                                           jnp.asarray(ev)))
                self._set_admit_side(st, ca)
            if self.check_invariants:
                self._verify_invariants()
            return
        if paged:
            hw = self.sched.pool.pages_in_use
            if self.disagg:
                self.prefill_pages_high_water = max(
                    self.prefill_pages_high_water, hw)
            else:
                self.pages_high_water = max(self.pages_high_water, hw)
            self.pages_shared_high_water = max(self.pages_shared_high_water,
                                               self.sched.pool.pages_shared)
        self.prefill_chunks_skipped += rnd.chunks_skipped
        st, ca = self._admit_side()
        st, ca, finals, n_calls, n_rows = self.prefill.run_round(
            self.params, st, ca, rnd)
        self._set_admit_side(st, ca)
        self.n_admit_calls += n_calls
        self.n_admit_rows += n_rows
        # one blocking sync for the whole admission round
        with TraceAnnotation("engine.admit_sync"):
            active = np.asarray(st.active)
            first = {slot: int(np.asarray(finals[slot])[slot])
                     for slot, _ in rnd.admitted}
        now = time.perf_counter()
        for slot, req in rnd.admitted:
            req.out_tokens.append(first[slot])
            req.t_first = now
            self.n_generated += 1
            if not active[slot]:
                # terminated at admission (first-token EOS / no decode
                # room): the compiled admit already released its device
                # refs; retire it on the admission side — it never
                # transfers
                self.sched.release_admit_slot(slot)
            elif self.disagg:
                self.sched.mark_ready(slot)
        self.n_syncs += 1
        if self.check_invariants and paged:
            self._verify_invariants()

    @functools.partial(annotate_function, name="engine.transfer")
    def _transfer(self) -> None:
        """Disagg: move every transferable prefilled request's pages
        into the decode pool (FIFO, backpressured by the scheduler)."""
        plans = self.sched.plan_transfers()
        if not plans:
            return
        mp = self.pages_per_slot
        checked = [] if self.check_invariants else None
        for t in plans:
            src = np.zeros((mp,), np.int32)
            src[:t.n] = t.src_ids
            dst = np.zeros((mp,), np.int32)
            dst[:t.n] = t.dst_ids
            self.p_state, tiles, scalars = self.prefill.export_request(
                self.p_state, self.p_caches, jnp.asarray(src), t.src_slot)
            self.state, self.caches = self.decode.import_request(
                self.state, self.caches, tiles, scalars, jnp.asarray(dst),
                t.n, t.dst_slot)
            self.pages_transferred += t.n
            if checked is not None:
                checked.append((t, tiles))
        self.transfer_rounds += 1
        self.pages_high_water = max(self.pages_high_water,
                                    self.sched.decode_pool.pages_in_use)
        if self.check_invariants:
            self._verify_invariants()
            for t, tiles in checked:
                self._verify_transfer(t, tiles)

    # ------------------------------------------------------------------
    # telemetry / debug
    # ------------------------------------------------------------------

    @property
    def pages_in_use(self) -> int:
        """Decode-pool pages with refcount > 0 — slot-held and
        cache-held alike."""
        pool = self.sched.decode_pool
        return pool.pages_in_use if pool is not None else 0

    def prefix_stats(self) -> dict:
        """Prefix-cache telemetry for reports and benches."""
        if self.prefix is None:
            return {"enabled": False, "hits": 0, "misses": 0,
                    "hit_rate": 0.0, "tokens_skipped": 0, "evictions": 0,
                    "cached_pages": 0, "chunks_skipped": 0}
        c = self.prefix
        looked = c.hits + c.misses
        return {"enabled": True, "hits": c.hits, "misses": c.misses,
                "hit_rate": c.hits / looked if looked else 0.0,
                "tokens_skipped": c.tokens_skipped,
                "evictions": c.evictions, "cached_pages": c.cached_pages,
                "chunks_skipped": self.prefill_chunks_skipped}

    def spec_stats(self) -> dict:
        """Speculation telemetry: the active drafter's identity ("ngram"
        | "model", None when speculation is off) and drafted/accepted
        totals over retired requests plus the live slots' in-flight
        counters.  `abort()` retires a running request through the same
        finish path as normal completion, so its in-flight split folds
        into the totals rather than vanishing."""
        drafted, accepted = self.tokens_drafted, self.tokens_accepted
        for r in self.sched.decode_slot_req:
            if r is not None:
                drafted += r.drafted_tokens
                accepted += r.accepted_tokens
        return {"enabled": bool(self.draft_len),
                "draft_len": self.draft_len,
                "drafter": self.drafter_kind,
                "drafted": drafted, "accepted": accepted,
                "acceptance_rate": accepted / drafted if drafted else 0.0}

    def disagg_stats(self) -> dict:
        """Disaggregation telemetry: the transfer volume and both pools'
        high-water occupancy (all zeros on a colocated engine)."""
        return {"enabled": self.disagg,
                "pages_transferred": self.pages_transferred,
                "transfer_rounds": self.transfer_rounds,
                "transfers_backpressured":
                    self.sched.transfers_backpressured,
                "decode_pages_high_water": self.pages_high_water,
                "decode_pages": self.num_pages,
                "prefill_pages_high_water": self.prefill_pages_high_water,
                "prefill_pages": self.prefill_pages if self.disagg else 0,
                "prefill_slots": self.prefill_slots if self.disagg else 0}

    def _verify_pool(self, host: pg.HostPool, dev: pg.PagePool,
                     num_slots: int, cached: int, label: str) -> None:
        """One pool's mirror-vs-device cross-check: refcounts, free
        popcount, per-slot block tables/ownership, and the I3 identity."""
        refs = np.asarray(dev.refs)
        if (refs < 0).any():
            raise AssertionError(f"[{label}] device refcounts negative: "
                                 f"{refs}")
        if not np.array_equal(refs, host.refs):
            raise AssertionError(
                f"[{label}] host/device refcount drift:\n host "
                f"{host.refs}\n device {refs}")
        if int((refs == 0).sum()) != host.free_pages:
            raise AssertionError(
                f"[{label}] free popcount drift: host {host.free_pages}, "
                f"device {int((refs == 0).sum())}")
        n_pages = np.asarray(dev.n_pages)
        tables = np.asarray(dev.tables)
        owned = np.asarray(dev.owned)
        for s in range(num_slots):
            t = host.slot_tables[s]
            if int(n_pages[s]) != len(t):
                raise AssertionError(
                    f"[{label}] slot {s} n_pages drift: host {len(t)}, "
                    f"device {int(n_pages[s])}")
            if list(tables[s, :len(t)]) != t:
                raise AssertionError(
                    f"[{label}] slot {s} table drift: host {t}, "
                    f"device {list(tables[s, :len(t)])}")
            if list(owned[s, :len(t)]) != host.slot_owned[s]:
                raise AssertionError(
                    f"[{label}] slot {s} ownership drift: host "
                    f"{host.slot_owned[s]}, "
                    f"device {list(owned[s, :len(t)])}")
        if int(n_pages.sum()) != int(refs.sum()) - cached:
            raise AssertionError(
                f"[{label}] refcount identity broken: sum(n_pages)="
                f"{int(n_pages.sum())}, sum(refs)={int(refs.sum())}, "
                f"cached={cached}")

    def _verify_invariants(self) -> None:
        """Debug-mode cross-check (`check_invariants=True`): every
        HostPool mirror must equal its device allocator exactly —
        refcounts, free popcount, per-slot block tables and ownership —
        and the global refcount identity (I3 in `repro.runtime.pages`)
        must hold.  Under disagg BOTH pools are checked (I7: each side
        independently satisfies I1–I6 after every transfer round)."""
        if self.kv_layout != "paged":
            return
        if self.disagg:
            self._verify_pool(self.sched.pool, self.p_state.pages,
                              self.prefill_slots, 0, "prefill")
            self._verify_pool(self.sched.decode_pool, self.state.pages,
                              self.num_slots, 0, "decode")
        else:
            cached = self.prefix.cached_pages \
                if self.prefix is not None else 0
            self._verify_pool(self.sched.pool, self.state.pages,
                              self.num_slots, cached, "pool")

    def _verify_transfer(self, t, tiles) -> None:
        """I7 content check: the imported pages' rows must read back
        bit-identical to the exported tiles."""
        mp = self.pages_per_slot
        dst = np.zeros((mp,), np.int32)
        dst[:t.n] = t.dst_ids
        got = pg.export_pages(self.caches, self._pool_flags,
                              jnp.asarray(dst))
        for a, b in zip(jax.tree_util.tree_leaves(tiles),
                        jax.tree_util.tree_leaves(got)):
            a = np.asarray(a)[:, :t.n]
            b = np.asarray(b)[:, :t.n]
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"I7 broken: transferred pages for request "
                    f"{t.req.uid} differ between pools")

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine tick: admit queued prompts (disagg: after moving
        transferable prefilled requests into the decode pool, freeing
        prefill slots for this round), then `decode_steps` fused decode
        steps for all active decode slots (a single jit call and a
        single host sync).  Each part is a profiler span on the host
        (`engine.admit`, `engine.tick`, `engine.tick_sync`, `engine.emit`,
        `engine.transfer`; see PERF.md for the spans and what reads
        them)."""
        if self.disagg:
            self._transfer()
        self._admit()
        if self.disagg:
            # freshly prefilled prompts move the same step, so their
            # first decode tick lands exactly when the colocated
            # engine's would
            self._transfer()
        if not any(r is not None for r in self.sched.decode_slot_req):
            if self.disagg:
                # prefill-side work (queued, mid-prefill or awaiting
                # transfer) still counts as engine progress
                return bool(self.sched.queue or self.sched.ready
                            or any(r is not None
                                   for r in self.sched.slot_req))
            return False
        # KV bytes this tick's decode steps read (tick-start lengths; the
        # kernel touches live pages only, the gather oracle — dense decode
        # and the speculative verify window included — always materializes
        # num_slots × max_seq rows)
        with TraceAnnotation("engine.tick"):
            if self.decode_kernel:
                rows = pk_kernel.decode_read_rows(
                    [len(r.prompt) + len(r.out_tokens)
                     for r in self.sched.decode_slot_req if r is not None],
                    self.page_size)
            else:
                rows = pk_kernel.oracle_read_rows(self.num_slots,
                                                  self.max_seq)
            self.kv_bytes_read += self.decode_steps * rows \
                * self._kv_row_bytes
            self.kv_read_steps += self.decode_steps
            self.state, self.caches, toks, emitted = self.decode.tick(
                self.params, self.state, self.caches)
        with TraceAnnotation("engine.tick_sync"):
            toks = np.asarray(toks)
            emitted = np.asarray(emitted)
            active = np.asarray(self.state.active)
            if self.draft_len:
                n_dr = np.asarray(self.state.n_drafted)
                n_ac = np.asarray(self.state.n_accepted)
        # non-spec tick: (steps, slots); spec tick: (steps, slots, window)
        # — normalize to a trailing window axis of 1
        if toks.ndim == 2:
            toks, emitted = toks[..., None], emitted[..., None]
        self.n_ticks += 1
        self.n_syncs += 1
        with TraceAnnotation("engine.emit"):
            for slot, req in enumerate(self.sched.decode_slot_req):
                if req is None:
                    continue
                for t in range(toks.shape[0]):
                    for j in range(toks.shape[2]):
                        if emitted[t, slot, j]:
                            req.out_tokens.append(int(toks[t, slot, j]))
                            self.n_generated += 1
                if self.draft_len:
                    req.drafted_tokens = int(n_dr[slot])
                    req.accepted_tokens = int(n_ac[slot])
                if not active[slot]:
                    self.sched.release_decode_slot(slot)
        if self.check_invariants and self.kv_layout == "paged":
            self._verify_invariants()
        return True

    def run(self, max_ticks: int = 10_000) -> list[RequestResult]:
        """Serve until the queue drains (or max_ticks), returning the
        RequestResults completed during this call, completion order."""
        for _ in range(max_ticks):
            if not self.step() and not self.sched.queue:
                break
        done, self.sched.finished = self.sched.finished, []
        return done

    def abort(self, req: Request) -> bool:
        """Cancel a request.  Queued: removed before it ever runs.
        Running: its slot is deactivated and (paged) its page references
        released immediately — the freed pages are grantable in the very
        next admission round.  Disagg: a prefilled request awaiting
        transfer is dropped on the prefill side and never moves.
        Returns False if the request had already finished.  Either way
        an aborted request keeps the tokens it emitted, with
        finish_reason "aborted"."""
        if req.done:
            return False
        req.aborted = True
        if req in self.sched.queue:
            self.sched.queue.remove(req)
            self.sched.finish(req)
            return True
        if self.disagg and req.uid in self.sched._ready_slot:
            slot = self.sched.drop_ready(req)
            dead = jnp.zeros((self.prefill_slots,), bool).at[slot].set(True)
            self.p_state = self.p_state._replace(
                active=self.p_state.active & ~dead,
                pages=pg.release(self.p_state.pages, dead))
            self.sched.release_admit_slot(slot)
            if self.check_invariants:
                self._verify_invariants()
            return True
        for slot, r in enumerate(self.sched.decode_slot_req):
            if r is req:
                dead = jnp.zeros((self.num_slots,), bool).at[slot].set(True)
                state = self.state._replace(active=self.state.active & ~dead)
                if self.kv_layout == "paged":
                    state = state._replace(pages=pg.release(state.pages,
                                                            dead))
                self.state = state
                self.sched.release_decode_slot(slot)
                if self.check_invariants and self.kv_layout == "paged":
                    self._verify_invariants()
                return True
        # not queued, not in a slot, not done — unreachable by construction
        raise AssertionError(f"request {req.uid} is in no engine structure")

    def close(self) -> None:
        """Release the engine's sharding context (the activate() in
        __init__ is process-global; a later meshless Engine or trainer in
        the same process would otherwise trace against this engine's
        serve rules).  Idempotent, and safe on a partially constructed
        engine — __init__ calls it before re-raising."""
        if self._ctx is not None and shd.active() is self._ctx:
            shd.deactivate()
        self._ctx = None

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
