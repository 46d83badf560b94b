"""Model FLOPs of every token the window processed (prompts admitted in
it, prefilled from position 0, and every decoded token, attention over
its context counted once; `flops_mla_moe`) over the window's seconds,
the chips and the bf16 peak."""
from perfbench import flops_mla_moe as F


def read(ctx):
    win, cfg = ctx["window"], ctx["cfg"]
    flops = sum(F.prefill_flops(cfg, len(t.req.prompt))
                for t in win.tracked if t.stamps
                and win.t_open < t.stamps[0] <= win.t_end)
    flops += sum(F.token_flops(cfg, n, True)
                 for tick in win.ticks for n in tick)
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"]
    return 100 * flops / ((win.t_end - win.t_open) * ctx["chips"] * peak)
