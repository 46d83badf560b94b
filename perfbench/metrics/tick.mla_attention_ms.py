"""Device time of the `tick` program's ops in the `attention` named
scope (every layer's MLA: projections, RoPE, the gather of the latent
cache and its up-projections, the attention over it, the output
projection), per tick execution in the traced window."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None:
        return None
    t = sp["scopes"].get("tick", {}).get("attention")
    rec = ctx["trace"]["programs"].get("tick")
    if t is None or not rec or not rec["count"]:
        return None
    return 1e3 * t / rec["count"]
