"""Device time of the `tick` program's ops in the `kv_write` named scope
(the new K/V rows scattered into the page pool), per tick execution in
the traced window."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None:
        return None
    t = sp["scopes"].get("tick", {}).get("kv_write")
    rec = ctx["trace"]["programs"].get("tick")
    if t is None or not rec or not rec["count"]:
        return None
    return 1e3 * t / rec["count"]
