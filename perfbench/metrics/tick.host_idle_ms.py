"""Device-idle time outside the program's `engine.admit` spans (tick
dispatch and sync, the token loop, the harness, collections), per
`engine.tick` span in the traced window."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None:
        return None
    ticks = sp["spans"].get("engine.tick", {}).get("count")
    if not ticks:
        return None
    return 1e3 * (sp["idle"]["seconds"] - sp["idle"]["in_admit"]) / ticks
