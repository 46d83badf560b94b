"""Device time of the `admit` program's ops in the `attention` named
scope (q/k/v, RoPE, the gather of the cache and the attention over it,
the output projection), per admit execution in the traced window."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None:
        return None
    t = sp["scopes"].get("admit", {}).get("attention")
    rec = ctx["trace"]["programs"].get("admit")
    if t is None or not rec or not rec["count"]:
        return None
    return 1e3 * t / rec["count"]
