"""Model FLOPs of every token the window processed (prompts admitted in
it, prefilled from position 0, and every decoded token, attention over
its context counted once) over the window's seconds, the chips and the
peak of the linears' operand type (int8 for w8a8, else bf16)."""
from perfbench import roofline


def read(ctx):
    win, cfg = ctx["window"], ctx["cfg"]
    flops = sum(roofline.prefill_flops(cfg, len(t.req.prompt))
                for t in win.tracked if t.stamps
                and win.t_open < t.stamps[0] <= win.t_end)
    flops += sum(roofline.token_flops(cfg, n, True)
                 for tick in win.ticks for n in tick)
    if not flops:
        return None
    peak = ctx["peaks"]["int8_ops" if cfg["weights"] == "int8"
                        else "bf16_flops"]
    return 100 * flops / ((win.t_end - win.t_open) * ctx["chips"] * peak)
