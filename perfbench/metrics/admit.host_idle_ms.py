"""Device-idle time inside the program's `engine.admit` spans (planning,
host arrays, transfers, dispatch and the round's sync), per admission
round in the traced window.  Times the rounds, plus `tick.host_idle_ms`
times the ticks, is the window's idle time."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None or not sp["rounds"]:
        return None
    return 1e3 * sp["idle"]["in_admit"] / sp["rounds"]
