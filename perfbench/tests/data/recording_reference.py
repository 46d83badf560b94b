"""A reference module for the tests: it records each call to `logits_at`
and answers with equal logits everywhere (every served token reads a gap
of 0), and its `KINDS` draw the state-space leaves of `hybrid.json`."""
import jax
import jax.numpy as jnp
import numpy as np

Q_CHUNK = 32
CALLS: list = []

KINDS = {
    "small": lambda key, shape, dtype: jax.random.uniform(
        key, shape, jnp.float32, -0.125, 0.125).astype(dtype),
    "ones": lambda key, shape, dtype: jnp.ones(shape, dtype),
    "dt_bias": lambda key, shape, dtype: jnp.full(shape, -4.5, dtype),
    "s4d_real": lambda key, shape, dtype: jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape)).astype(
            dtype),
}


def logits_at(cfg, seed, tokens, rows, quant=None, group=4):
    CALLS.append({"seed": seed, "tokens": np.asarray(tokens).shape,
                  "rows": np.asarray(rows).shape, "quant": quant,
                  "group": group})
    n, r = np.asarray(rows).shape
    return [jnp.zeros((min(group, n - i), r, cfg["vocab_size"]), jnp.float32)
            for i in range(0, n, group)]
