"""On-device token sampling for the serving engine.

Sampling runs *inside* the jit'd engine tick (runtime/serve.py) so the
host never sees logits: each slot carries its own PRNG key chain in the
device-resident `SlotState`, and a tick emits tokens directly.  The key
chain is derived from the per-request seed alone (not the slot index), so
a request's stream is reproducible regardless of which slot it lands in
or what else is batched alongside it.

Methods:
  greedy      — argmax; consumes no randomness (keys pass through).
  temperature — softmax sample of logits / temperature.
  top_k       — temperature sample restricted to the k highest logits.
  top_p       — temperature sample restricted to the smallest prefix of
                the sorted distribution with cumulative mass >= top_p
                (the best token is always kept).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

METHODS = ("greedy", "temperature", "top_k", "top_p")


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    method: str = "greedy"
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"sampling method must be one of {METHODS}, "
                             f"got {self.method!r}")
        if self.temperature <= 0.0:
            raise ValueError(f"temperature must be > 0, "
                             f"got {self.temperature}")
        if self.method == "top_k" and self.top_k < 1:
            raise ValueError(f"top_k sampling needs top_k >= 1, "
                             f"got {self.top_k}")
        if self.method == "top_p" and not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def request_keys(base_key, seeds):
    """Per-request starting keys: (B,) i32 seeds -> (B, 2) u32 keys.

    Derived from the request seed only, never the slot index."""
    return jax.vmap(lambda s: jax.random.fold_in(base_key, s))(seeds)


def _filter_logits(logits, sc: SamplingConfig):
    """Temperature scaling + top_k / top_p restriction of (B, V) rows;
    the f32 result is what the stochastic methods sample from (and what
    the speculative accept rule scores drafts against)."""
    l = logits.astype(jnp.float32) / sc.temperature
    if sc.method == "top_k":
        k = min(sc.top_k, l.shape[-1])
        # rank-based mask: a value threshold (`l >= kth`) would keep EVERY
        # logit tied with the k-th largest, growing the nucleus past k.
        # lax.top_k breaks ties by lowest index, so scattering its indices
        # keeps exactly k tokens
        idx = jax.lax.top_k(l, k)[1]                    # (B, k)
        keep = jax.vmap(lambda m, i: m.at[i].set(True))(
            jnp.zeros(l.shape, bool), idx)
        l = jnp.where(keep, l, -jnp.inf)
    elif sc.method == "top_p":
        srt = jnp.sort(l, axis=-1)[:, ::-1]             # descending
        probs = jax.nn.softmax(srt, axis=-1)
        before = jnp.cumsum(probs, axis=-1) - probs     # mass strictly above
        keep = before < sc.top_p                        # best always kept
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
        l = jnp.where(l >= thresh[:, None], l, -jnp.inf)
    return l


@jax.named_scope("sample")
def sample(logits, keys, sc: SamplingConfig):
    """logits (B, V), keys (B, 2) u32 -> (tokens (B,) i32, new_keys).

    Stochastic methods split each row's key once per emitted token;
    greedy returns the keys untouched."""
    if sc.method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
    pairs = jax.vmap(jax.random.split)(keys)            # (B, 2, 2)
    new_keys, subs = pairs[:, 0], pairs[:, 1]
    l = _filter_logits(logits, sc)
    toks = jax.vmap(jax.random.categorical)(subs, l)
    return toks.astype(jnp.int32), new_keys


@jax.named_scope("sample")
def spec_verify(logits, drafts, keys, sc: SamplingConfig):
    """Vectorized accept/replace for one speculative draft window.

    logits (B, L, V) scored over the chunk [last_tok, g_1 .. g_d]
    (L = d + 1, so logits[:, i] conditions on the first i drafts);
    drafts (B, d) the proposed tokens; keys (B, 2) u32 per-slot chains.

    Returns (out (B, L) i32, n_acc (B,) i32, new_keys) where out[:, i]
    is the token the stream emits at window index i if it reaches that
    far: accepted drafts verbatim for i < n_acc, the model's own
    replacement at i == n_acc (the rejection correction for i < d, the
    bonus token at i == d).  The caller clamps how many of these are
    actually emitted (stop tokens / budget / max_seq).

    Greedy: a draft is accepted iff it equals the argmax of the previous
    position's logits — so every emitted token is exactly the token
    non-speculative greedy decoding would have produced (the parity
    invariant speculate.py documents).  Consumes no randomness.

    Stochastic: per-position rejection sampling against the drafter's
    point-mass proposal — draft g at position i is accepted with
    probability p_i(g) under the filtered/temperature distribution, and
    a rejection resamples from p_i with g masked out (the renormalized
    residual), so each emitted token is marginally distributed exactly
    as p_i, same as non-speculative sampling.  One split per slot per
    window, then per-position fold_in — acceptance at one position
    cannot perturb the draw at another."""
    B, L, _ = logits.shape
    d = L - 1
    if sc.method == "greedy":
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B, L)
        ok = (drafts == t[:, :d]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(ok, axis=1), axis=1).astype(jnp.int32)
        return t, n_acc, keys
    pairs = jax.vmap(jax.random.split)(keys)                    # (B, 2, 2)
    new_keys, subs = pairs[:, 0], pairs[:, 1]
    l = _filter_logits(logits.reshape(B * L, -1), sc).reshape(
        B, L, logits.shape[-1])
    probs = jax.nn.softmax(l, axis=-1)
    pkeys = jax.vmap(lambda k: jax.vmap(
        lambda i: jax.random.fold_in(k, i))(jnp.arange(L)))(subs)
    halves = jax.vmap(jax.vmap(jax.random.split))(pkeys)        # (B, L, 2, 2)
    k_u, k_c = halves[:, :, 0], halves[:, :, 1]
    u = jax.vmap(jax.vmap(jax.random.uniform))(k_u)             # (B, L)
    p_draft = jnp.take_along_axis(probs[:, :d], drafts[..., None],
                                  axis=-1)[..., 0]              # (B, d)
    acc = (u[:, :d] < p_draft).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(acc, axis=1), axis=1).astype(jnp.int32)
    # replacement draw at every position: the rejected draft is masked out
    # of its own row (the bonus row at i == d has no draft: -1 matches no
    # vocabulary id, so its draw is the plain filtered categorical)
    pad = jnp.concatenate(
        [drafts, jnp.full((B, 1), -1, jnp.int32)], axis=1)      # (B, L)
    lm = jnp.where(jnp.arange(l.shape[-1])[None, None, :] == pad[..., None],
                   -jnp.inf, l)
    repl = jax.vmap(jax.vmap(jax.random.categorical))(
        k_c, lm).astype(jnp.int32)                              # (B, L)
    out = jnp.where(jnp.arange(L)[None, :] < n_acc[:, None], pad, repl)
    return out, n_acc, new_keys
