"""Share of the HBM roofline the decode tick reaches: the least bytes a
step moves (every weight once at its stored width, the live K/V pages of
every layer, one new K/V row per sequence; `roofline.tick_bytes`),
averaged over the window's ticks, over peak HBM bandwidth, over the mean
device time of a `tick` execution."""
from perfbench import roofline


def read(ctx):
    rec = ctx["trace"]["programs"].get("tick")
    ticks = ctx["window"].ticks
    if not rec or not rec["count"] or not ticks:
        return None
    cfg, eng = ctx["cfg"], ctx["cfg"]["engine"]
    mean_bytes = sum(roofline.tick_bytes(cfg, t, eng["page_size"],
                                         eng["slots"])
                     for t in ticks) / len(ticks)
    t_tick = rec["seconds"] / rec["count"]
    return 100 * mean_bytes / ctx["peaks"]["hbm_bytes_s"] / t_tick
