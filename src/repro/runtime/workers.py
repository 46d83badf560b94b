"""Device-facing worker roles: prefill admission and fused decode.

The engine split (PR 10) pairs this module with `runtime.scheduler`:
the Scheduler decides everything on the host, the workers here compile
and run everything on the device.

  PrefillWorker — owns the chunked admit path: one jit serves every
                  prompt length (fixed `prefill_chunk` chunks, all
                  admitting slots per call), the first chunk of a round
                  carrying the whole pool transaction + copy-on-write
                  split, the final chunk sampling the first token on
                  device.  `run_round` executes a Scheduler
                  AdmissionRound; `export_request` (disagg) gathers a
                  finished prompt's page tiles + slot scalars and
                  releases the source references in the same traced
                  call (I7).

  DecodeWorker  — owns the fused tick (`decode_steps` scanned
                  decode→sample→terminate steps, or the speculative
                  draft→verify→rollback variant) and, in disagg mode,
                  `import_request`: scatter the exported tiles into
                  this pool's granted pages and install the slot state,
                  one compile for every transfer (slot/count are traced
                  scalars).

A colocated Engine points both workers at the SAME state/caches pytree,
which reproduces the pre-split engine exactly; a disaggregated Engine
gives each worker its own pool and moves requests between them at page
granularity.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import attention as attn
from repro.models import model as M
from repro.runtime import pages as pg
from repro.runtime import sampling as smp
from repro.runtime import speculate as spc


class SlotState(NamedTuple):
    """Per-slot decode state; one device-resident pytree for all slots.

    `pages` is the refcounted paged-KV allocator state (empty arrays
    under the dense layout); see `repro.runtime.pages.PagePool`.
    `draft` is the per-slot drafter state (zero-width when speculation
    is off): n-gram tables (`speculate.DraftState`) or the model
    drafter's requantized params + private draft KV cache
    (`speculate.QuantDraftState`)."""
    last_tok: jax.Array     # (S,) i32  last sampled token (next decode input)
    pos: jax.Array          # (S,) i32  next cache index to write
    budget: jax.Array       # (S,) i32  tokens still to emit after this one
    active: jax.Array       # (S,) bool slot is mid-generation
    rng: jax.Array          # (S, 2) u32 per-request sampling key chain
    stop: jax.Array         # (S, K) i32 per-request stop set, -1 padded
    pages: pg.PagePool      # refcounted page allocator (paged layout)
    draft: Any              # drafter state (n-gram tables / draft KV)
    n_drafted: jax.Array    # (S,) i32 drafted tokens, current occupant
    n_accepted: jax.Array   # (S,) i32 drafted tokens emitted


def init_slot_state(num_slots: int, stop_cap: int, table_len: int,
                    num_pages: int, draft) -> SlotState:
    return SlotState(
        last_tok=jnp.zeros((num_slots,), jnp.int32),
        pos=jnp.zeros((num_slots,), jnp.int32),
        budget=jnp.zeros((num_slots,), jnp.int32),
        active=jnp.zeros((num_slots,), bool),
        rng=jnp.zeros((num_slots, 2), jnp.uint32),
        stop=jnp.full((num_slots, stop_cap), -1, jnp.int32),
        pages=pg.init_pool(num_slots, table_len, num_pages),
        draft=draft,
        n_drafted=jnp.zeros((num_slots,), jnp.int32),
        n_accepted=jnp.zeros((num_slots,), jnp.int32))


def _paged_bundle(pool: pg.PagePool, max_seq: int, page_size: int):
    """The PagedKV bundle for one traced call; write_mask is supplied
    by the caller (valid slots at admit, active slots in the tick).
    `owned` routes writes aimed at shared prefix pages to the drop
    index — a slot can never corrupt a page other consumers read.
    `bound` (speculation) additionally drops rows at or past the
    per-slot accepted-length bound.  `kernel` marks the bundle for the
    pallas paged-decode kernel (the Sq=1 tick only — admit chunks and
    the speculative verify window read through the gather oracle)."""
    def bundle(write_mask, bound=None, kernel=False):
        return attn.PagedKV(tables=pool.tables, n_pages=pool.n_pages,
                            write_mask=write_mask, max_seq=max_seq,
                            page_size=page_size, owned=pool.owned,
                            bound=bound, decode_kernel=kernel)
    return bundle


def _donate() -> tuple:
    # buffer donation lets caches/state update in place; the CPU
    # backend doesn't implement donation and would warn on every call
    return () if jax.default_backend() == "cpu" else (1, 2)


class DecodeWorker:
    """Compiles and runs the fused decode tick (and, disaggregated, the
    import half of the page transfer) against ONE pool's state."""

    def __init__(self, *, cfg, num_slots: int, max_seq: int,
                 decode_steps: int, sampling, kv_layout: str,
                 decode_kernel: bool, draft_len: int, drafter,
                 pool_flags, kv_flags):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.page_size = cfg.page_size
        self.decode_steps = decode_steps
        self.sampling = sampling
        self.paged = kv_layout == "paged"
        self.decode_kernel = decode_kernel
        self.draft_len = draft_len
        self.drafter = drafter
        self._pool_flags = pool_flags
        self._kv_flags = kv_flags
        tick = self._make_spec_tick() if draft_len else self._make_tick()
        self.tick = jax.jit(tick, donate_argnums=_donate())
        self._import = jax.jit(
            self._make_import(),
            donate_argnums=() if jax.default_backend() == "cpu" else (0, 1)) \
            if self.paged else None

    def _make_tick(self):
        """N fused decode steps: decode -> sample -> terminate, scanned;
        under the paged layout, every reference a slot that terminates
        inside the tick holds is released before the host ever syncs —
        pages reaching refcount zero rejoin the free set."""
        cfg, sc = self.cfg, self.sampling
        max_seq, steps = self.max_seq, self.decode_steps
        page_size = self.page_size
        paged_mode = self.paged
        use_kernel = self.decode_kernel

        def tick(params, state, caches):
            def body(carry, _):
                state, caches = carry
                # inactive slots must not write: their stale block-table
                # entries may point at pages since re-granted to another
                # request (dense slots own their rows, so masking there is
                # unnecessary — and the PR-4 path stays untouched)
                pv = _paged_bundle(state.pages, max_seq, page_size)(
                    state.active, kernel=use_kernel) if paged_mode else None
                logits, caches = M.decode_step(
                    params, state.last_tok[:, None], cfg, caches, state.pos,
                    paged=pv)
                toks, keys = smp.sample(logits, state.rng, sc)
                emit = state.active
                tok = jnp.where(emit, toks, state.last_tok)
                rng = jnp.where(emit[:, None], keys, state.rng)
                pos = jnp.where(emit, state.pos + 1, state.pos)
                budget = jnp.where(emit, state.budget - 1, state.budget)
                # -1-padded stop rows match no real token id
                hit_stop = emit & jnp.any(tok[:, None] == state.stop, axis=1)
                active = emit & (budget > 0) & ~hit_stop & (pos < max_seq - 1)
                new = state._replace(last_tok=tok, pos=pos, budget=budget,
                                     active=active, rng=rng)
                return (new, caches), (tok, emit)

            pre_active = state.active
            (state, caches), (toks, emitted) = jax.lax.scan(
                body, (state, caches), None, length=steps)
            if paged_mode:
                dead = pre_active & ~state.active
                with jax.named_scope("pages"):
                    state = state._replace(pages=pg.release(state.pages,
                                                            dead))
            return state, caches, toks, emitted

        return tick

    def _make_spec_tick(self):
        """The speculative tick: each of the `decode_steps` scanned steps
        drafts `draft_len` tokens from the slot's n-gram table, scores
        the window [last_tok, g_1..g_d] in ONE chunked forward (the same
        path prefill uses — logits[:, i] conditions on the first i
        drafts), accepts/replaces on device (`sampling.spec_verify`) and
        clamps the emission count by stop tokens / budget / max_seq
        exactly as the sequential loop would (invariant A3).  Rejected
        draft rows are rolled back before the step ends (A4).  One host
        sync per tick, however many tokens each window lands."""
        cfg, sc = self.cfg, self.sampling
        max_seq, steps, d = self.max_seq, self.decode_steps, self.draft_len
        L = d + 1
        page_size = self.page_size
        paged_mode = self.paged
        pool_flags, kv_flags = self._pool_flags, self._kv_flags
        drafter = self.drafter

        def tick(params, state, caches):
            def body(carry, _):
                state, caches = carry
                drafts = drafter.propose(state.draft, d)          # (S, d)
                chunk = jnp.concatenate([state.last_tok[:, None], drafts],
                                        axis=1)
                win = state.pos[:, None] \
                    + jnp.arange(L, dtype=jnp.int32)[None]
                # rows a non-speculative run could never reach are dropped
                # at write time (the per-slot accepted-length bound)
                bound = state.pos + state.budget
                if paged_mode:
                    pv = _paged_bundle(state.pages, max_seq, page_size)(
                        state.active, bound)
                else:
                    pv = attn.DenseKV(write_mask=state.active,
                                      max_seq=max_seq, bound=bound)
                logits, _, caches = M.forward(
                    params, {"tokens": chunk}, cfg, caches=caches,
                    cache_pos=state.pos, paged=pv)
                out, n_acc, keys = smp.spec_verify(logits, drafts,
                                                   state.rng, sc)
                idx = jnp.arange(L, dtype=jnp.int32)[None]
                is_stop = jnp.any(out[..., None] == state.stop[:, None, :],
                                  axis=-1)                        # (S, L)
                stop_at = jnp.min(jnp.where(is_stop, idx, L), axis=1)
                # emitted tokens this window: accepted drafts + the
                # model's correction/bonus, clamped exactly as the
                # sequential loop clamps per token (A3); >= 1 for active
                # slots (budget >= 1 and pos < max_seq - 1 while active)
                n_emit = jnp.minimum(
                    jnp.minimum(n_acc + 1, stop_at + 1),
                    jnp.minimum(state.budget, max_seq - 1 - state.pos))
                n_emit = jnp.where(state.active, n_emit, 0)
                emit = idx < n_emit[:, None]                      # (S, L)
                # roll back the rejected rows (window indices >= n_emit)
                rej = jnp.where(emit | ~state.active[:, None], max_seq, win)
                if paged_mode:
                    caches = pg.rollback(caches, pool_flags, pv, rej)
                else:
                    caches = spc.rollback_dense(caches, kv_flags, rej,
                                                state.active, max_seq)
                last = jnp.take_along_axis(
                    out, jnp.clip(n_emit - 1, 0, L - 1)[:, None],
                    axis=1)[:, 0]
                tok = jnp.where(state.active, last, state.last_tok)
                rng = jnp.where(state.active[:, None], keys, state.rng)
                pos = state.pos + n_emit
                budget = state.budget - n_emit
                stopped = jnp.any(is_stop & emit, axis=1)
                active = state.active & ~stopped & (budget > 0) \
                    & (pos < max_seq - 1)
                # the drafter learns only VERIFIED emissions, in order
                ds = drafter.observe(state.draft, out, emit)
                new = state._replace(
                    last_tok=tok, pos=pos, budget=budget, active=active,
                    rng=rng, draft=ds,
                    n_drafted=state.n_drafted
                    + jnp.where(state.active, d, 0),
                    n_accepted=state.n_accepted + jnp.maximum(n_emit - 1, 0))
                return (new, caches), (out, emit)

            pre_active = state.active
            (state, caches), (toks, emitted) = jax.lax.scan(
                body, (state, caches), None, length=steps)
            if paged_mode:
                dead = pre_active & ~state.active
                with jax.named_scope("pages"):
                    state = state._replace(pages=pg.release(state.pages,
                                                            dead))
            return state, caches, toks, emitted

        return tick

    def _make_import(self):
        """The import half of a page transfer (I7): scatter exported
        tiles into this pool's granted pages, adopt them into the slot's
        block table (refcount 1, owned) and install the slot scalars.
        `dst_ids` is (mp,) i32 with entries past `n` ignored; `n` and
        `slot` are traced scalars — one compile serves every transfer."""
        ns = self.num_slots
        pool_flags = self._pool_flags

        def imp(state, caches, tiles, scalars, dst_ids, n, slot):
            mp = state.pages.tables.shape[1]
            live = jnp.arange(mp, dtype=jnp.int32) < n
            caches = pg.import_pages(caches, pool_flags, tiles, dst_ids,
                                     live)
            pool = pg.adopt(state.pages, slot, dst_ids, n)
            onehot = jnp.arange(ns) == slot
            last_tok, pos, budget, rng_row, stop_row = scalars
            state = state._replace(
                last_tok=jnp.where(onehot, last_tok, state.last_tok),
                pos=jnp.where(onehot, pos, state.pos),
                budget=jnp.where(onehot, budget, state.budget),
                active=onehot | state.active,
                rng=jnp.where(onehot[:, None], rng_row[None, :], state.rng),
                stop=jnp.where(onehot[:, None], stop_row[None, :],
                               state.stop),
                pages=pool)
            return state, caches

        return imp

    def import_request(self, state, caches, tiles, scalars, dst_ids,
                       n: int, slot: int):
        return self._import(state, caches, tiles, scalars, dst_ids, n, slot)


class PrefillWorker:
    """Compiles and runs the chunked admission path (and, disaggregated,
    the export half of the page transfer) against ONE pool's state."""

    def __init__(self, *, cfg, num_slots: int, max_seq: int,
                 prefill_chunk: int, stop_cap: int, sampling, base_key,
                 kv_layout: str, pool_flags, draft_len: int, drafter):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.page_size = cfg.page_size
        self.pages_per_slot = -(-max_seq // cfg.page_size)
        self.prefill_chunk = prefill_chunk
        self.stop_cap = stop_cap
        self.sampling = sampling
        self.base_key = base_key
        self.paged = kv_layout == "paged"
        self._pool_flags = pool_flags
        self.draft_len = draft_len
        self.drafter = drafter
        self._admit_chunk = jax.jit(self._make_admit_chunk(),
                                    donate_argnums=_donate())
        self._export = jax.jit(
            self._make_export(),
            donate_argnums=() if jax.default_backend() == "cpu" else (0,)) \
            if self.paged else None

    def _make_admit_chunk(self):
        """One prefill chunk for every admitting slot, in one call.

        tokens (S, C) holds each admitting slot's chunk (garbage rows for
        slots mid-decode are masked out of the cache merge); offsets are
        the per-slot chunk starts — a warm-prefix slot's first chunk
        starts at its matched length, not 0.  Rows whose chunk completes
        the prompt (`final`) sample their first token on device and
        commit the slot state; the sampled tokens come back so the host
        can append them.

        Under the paged layout the first chunk of a round also carries
        the round's whole pool transaction, applied via
        `pages.admit_update` in the fixed evict -> share -> grant ->
        register order the HostPool mirror replays, followed by the
        copy-on-write split (`pages.cow_copy`) for slots whose cached
        prefix ends mid-page.  Later chunks pass an all-False `admitting`
        mask and zero deltas — the allocator is a no-op there."""
        cfg, sc = self.cfg, self.sampling
        max_seq, ns = self.max_seq, self.num_slots
        page_size = self.page_size
        base_key = self.base_key
        paged_mode = self.paged
        pool_flags = self._pool_flags
        draft_len, drafter = self.draft_len, self.drafter

        def admit(params, state, caches, tokens, valid, first, offsets,
                  true_lens, seeds, budgets0, stops, admitting, shared,
                  n_shared, new_pages, cow_src, evict_delta, register_delta):
            C = tokens.shape[1]
            if paged_mode:
                with jax.named_scope("pages"):
                    pool = pg.admit_update(state.pages, admitting, shared,
                                           n_shared, new_pages, evict_delta,
                                           register_delta)
                state = state._replace(pages=pool)
                # copy-on-write split: a cached prefix that ends mid-page
                # lands as a private copy in the slot's first FRESH page
                # (table entry n_shared — a fresh grant always exists:
                # the matched prefix is capped at prompt_len - 1, so at
                # least the final prompt row needs a writable page).  The
                # copy is traced before any forward write, so it reads
                # the source page's pre-call contents even if its chain
                # was evicted and the page re-granted this same round.
                mp = pool.tables.shape[1]
                with jax.named_scope("pages"):
                    dst = jnp.take_along_axis(
                        pool.tables, jnp.clip(n_shared, 0, mp - 1)[:, None],
                        axis=1)[:, 0]
                    caches = pg.cow_copy(caches, pool_flags, cow_src, dst)
            # a slot's FIRST chunk starts from pristine state: recurrent
            # mixers accumulate (h/conv/C/n/m carry the previous occupant
            # forward — the seed engine's whole-prompt *_sequence prefill
            # implicitly started from zeros), and KV rows revert to their
            # init values rather than stale garbage (XLA folds the init
            # tree into constants; no second cache is held).  Shared page
            # pools are exempt: co-resident requests own live rows there,
            # and stale rows only ever surface masked to exact zeros.
            # `first` is an explicit host-built mask — warm-prefix slots
            # start their chunk offsets at the matched length, so
            # `offsets == 0` would miss them.

            def reset(cur, ini):
                m = first.reshape((1, ns) + (1,) * (cur.ndim - 2))
                return jnp.where(m, ini.astype(cur.dtype), cur)

            if paged_mode:
                init_tree = M.init_cache(cfg, ns, max_seq,
                                         num_pages=pool.refs.shape[0])
                caches = jax.tree_util.tree_map(
                    lambda cur, ini, pf: cur if pf else reset(cur, ini),
                    caches, init_tree, pool_flags)
            else:
                caches = jax.tree_util.tree_map(
                    reset, caches, M.init_cache(cfg, ns, max_seq))
            # unembed only each slot's true last prompt row (the one whose
            # logits can be sampled), not all C chunk positions
            idx = jnp.clip(true_lens - 1 - offsets, 0, C - 1)
            pv = _paged_bundle(state.pages, max_seq, page_size)(valid) \
                if paged_mode else None
            logits, _, new_caches = M.forward(
                params, {"tokens": tokens}, cfg, caches=caches,
                cache_pos=offsets, gather_pos=idx, paged=pv)

            def merge(old, new):
                m = valid.reshape((1, ns) + (1,) * (old.ndim - 2))
                return jnp.where(m, new.astype(old.dtype), old)

            if paged_mode:
                # pool leaves already masked their writes at scatter time;
                # per-slot leaves (recurrent state, xattn) merge as before
                caches = jax.tree_util.tree_map(
                    lambda old, new, pf: new if pf else merge(old, new),
                    caches, new_caches, pool_flags)
            else:
                caches = jax.tree_util.tree_map(merge, caches, new_caches)
            last = logits[:, 0]                                 # (S, V)
            final = valid & (offsets + C >= true_lens)
            keys0 = smp.request_keys(base_key, seeds)
            toks, keys = smp.sample(last, keys0, sc)
            # per-request stop set; -1 padding matches no real token id
            hit_stop = final & jnp.any(toks[:, None] == stops, axis=1)
            act = final & (budgets0 > 0) & ~hit_stop \
                & (true_lens < max_seq - 1)
            state = state._replace(
                last_tok=jnp.where(final, toks, state.last_tok),
                pos=jnp.where(final, true_lens, state.pos),
                budget=jnp.where(final, budgets0, state.budget),
                active=jnp.where(final, act, state.active),
                rng=jnp.where(final[:, None], keys, state.rng),
                stop=jnp.where(final[:, None], stops, state.stop))
            if draft_len:
                # seed the drafter from the prompt: clear the slot on its
                # first chunk, then observe this chunk's real tokens in
                # order, plus the sampled first token on the final chunk —
                # so tick-time proposals can draft from prompt n-grams
                # (prompt-lookup decoding)
                ds = drafter.reset(state.draft, first)
                cmask = valid[:, None] \
                    & (offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
                       < true_lens[:, None])
                ds = drafter.observe(ds, tokens, cmask)
                ds = drafter.observe(ds, toks[:, None], final[:, None])
                state = state._replace(
                    draft=ds,
                    n_drafted=jnp.where(first, 0, state.n_drafted),
                    n_accepted=jnp.where(first, 0, state.n_accepted))
            if paged_mode:
                # a request that terminates AT admission (first token EOS,
                # or no decode room) must drop its references right here
                dead = final & ~act
                with jax.named_scope("pages"):
                    state = state._replace(pages=pg.release(state.pages,
                                                            dead))
            return state, caches, toks

        return admit

    def _make_export(self):
        """The export half of a page transfer (I7): gather the departing
        slot's page tiles and scalar state, then release its references
        and deactivate it — all in ONE traced call, so the source pool
        can never be observed holding refs for rows already copied out.
        The caches come back untouched (they are not an output), so the
        released pages' stale rows are simply overwritten by the next
        grant's prefill."""
        ns = self.num_slots
        pool_flags = self._pool_flags

        def export(state, caches, src_ids, slot):
            tiles = pg.export_pages(caches, pool_flags, src_ids)
            take = lambda a: jnp.take(a, slot, axis=0)  # noqa: E731
            scalars = (take(state.last_tok), take(state.pos),
                       take(state.budget), take(state.rng),
                       take(state.stop))
            onehot = jnp.arange(ns) == slot
            state = state._replace(
                active=state.active & ~onehot,
                pages=pg.release(state.pages, onehot))
            return state, tiles, scalars

        return export

    def export_request(self, state, caches, src_ids, slot: int):
        return self._export(state, caches, src_ids, slot)

    def run_round(self, params, state, caches, rnd):
        """Execute a Scheduler AdmissionRound: build the per-chunk host
        arrays and drive the compiled admit over every chunk.  Returns
        the updated (state, caches), the per-slot final-chunk token
        arrays (device-resident — the caller owns the sync), the number
        of compiled calls made and the prompt rows they computed.  Each
        call is a `prefill.call` span whose args are those rows and the
        rows it pads to (slots x chunk)."""
        ns, C = self.num_slots, self.prefill_chunk
        paged = self.paged
        admitted, plan = rnd.admitted, rnd.plan
        starts, n_chunks = rnd.starts, rnd.n_chunks
        finals: dict[int, Any] = {}          # slot -> final-chunk tokens
        P = state.pages.refs.shape[0]
        n_calls = n_rows = 0
        for ci in range(max(n_chunks.values())):
            rows = sum(min(C, len(req.prompt) - starts[slot] - ci * C)
                       for slot, req in admitted if ci < n_chunks[slot])
            with TraceAnnotation("prefill.call", rows=rows, padded=ns * C):
                tokens = np.zeros((ns, C), np.int32)
                valid = np.zeros((ns,), bool)
                offsets = np.zeros((ns,), np.int32)
                true_lens = np.ones((ns,), np.int32)
                seeds = np.zeros((ns,), np.int32)
                budgets0 = np.zeros((ns,), np.int32)
                stops = np.full((ns, self.stop_cap), -1, np.int32)
                admitting = np.zeros((ns,), bool)
                shared = np.zeros((ns, self.pages_per_slot), np.int32)
                n_shared = np.zeros((ns,), np.int32)
                new_pages = np.zeros((ns,), np.int32)
                cow_src = np.full((ns,), -1, np.int32)
                ev_arr = np.zeros((P,), np.int32)
                rg_arr = np.zeros((P,), np.int32)
                if paged and ci == 0:
                    for p, d in rnd.evict_delta.items():
                        ev_arr[p] = d
                    for p, d in rnd.reg_delta.items():
                        rg_arr[p] = d
                for slot, req in admitted:
                    if ci >= n_chunks[slot]:
                        continue
                    off = starts[slot] + ci * C
                    if paged and ci == 0:
                        m_len, full, cow, n_fresh = plan[slot]
                        admitting[slot] = True
                        shared[slot, :len(full)] = full
                        n_shared[slot] = len(full)
                        new_pages[slot] = n_fresh
                        cow_src[slot] = cow
                    if ci == n_chunks[slot] - 1 and not paged:
                        # dense only: a final chunk whose padded end would
                        # cross max_seq slides back inside the cache
                        # (dynamic_update_slice would clamp the write start
                        # and scramble rows); the re-covered rows recompute
                        # to identical values.  The paged scatter drops
                        # out-of-range rows instead, so no slide is needed.
                        off = min(off, max(0, self.max_seq - C))
                    piece = req.prompt[off:off + C]
                    tokens[slot, :len(piece)] = piece
                    valid[slot] = True
                    offsets[slot] = off
                    true_lens[slot] = len(req.prompt)
                    seeds[slot] = req.seed
                    budgets0[slot] = req.max_new_tokens - 1
                    stops[slot, :len(req.stop_tokens)] = req.stop_tokens
                first = valid if ci == 0 else np.zeros((ns,), bool)
                state, caches, toks = self._admit_chunk(
                    params, state, caches, jnp.asarray(tokens),
                    jnp.asarray(valid), jnp.asarray(first),
                    jnp.asarray(offsets), jnp.asarray(true_lens),
                    jnp.asarray(seeds), jnp.asarray(budgets0),
                    jnp.asarray(stops), jnp.asarray(admitting),
                    jnp.asarray(shared), jnp.asarray(n_shared),
                    jnp.asarray(new_pages), jnp.asarray(cow_src),
                    jnp.asarray(ev_arr), jnp.asarray(rg_arr))
            n_calls += 1
            n_rows += rows
            for slot, req in admitted:
                if ci == n_chunks[slot] - 1:
                    finals[slot] = toks
            del toks
        return state, caches, finals, n_calls, n_rows
