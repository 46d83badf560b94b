"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(ctx):
    tr = ctx["trace"]
    return 100 * (1 - tr["busy_s"] / tr["window_s"])
