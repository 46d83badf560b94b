"""Share of the HBM roofline the Pallas paged-decode kernel reaches: the
live K/V pages it must read plus q and the output for every slot
(`roofline.paged_decode_bytes`), per call averaged over the window's
ticks, over peak HBM bandwidth, over the kernel's mean device time per
call.  The kernel runs once per layer per tick."""
from perfbench import roofline

KERNEL = "paged_decode"


def read(ctx):
    rec = ctx["trace"]["kernels"].get(KERNEL)
    ticks = ctx["window"].ticks
    if not rec or not rec["count"] or not ticks:
        return None
    cfg, eng = ctx["cfg"], ctx["cfg"]["engine"]
    per_tick = sum(roofline.paged_decode_bytes(cfg, t, eng["page_size"],
                                               eng["slots"])
                   for t in ticks) / len(ticks)
    per_call = per_tick / cfg["num_hidden_layers"]
    t_call = rec["seconds"] / rec["count"]
    return 100 * per_call / ctx["peaks"]["hbm_bytes_s"] / t_call
