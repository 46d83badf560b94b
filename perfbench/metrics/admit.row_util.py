"""Share of the rows the admit calls of the traced window pad to that
hold a prompt row: the `rows` over the `padded` args of the program's
`prefill.call` spans (each call pads to slots x chunk rows)."""
from perfbench import spans


def read(ctx):
    sp = spans.of(ctx)
    if sp is None:
        return None
    call = sp["spans"].get("prefill.call")
    if not call or not call["args"].get("padded"):
        return None
    return 100 * call["args"]["rows"] / call["args"]["padded"]
