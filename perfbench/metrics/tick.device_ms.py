"""Mean device time of one `tick` program execution (one decode step
for every slot) in the traced window."""


def read(ctx):
    rec = ctx["trace"]["programs"].get("tick")
    if not rec or not rec["count"]:
        return None
    return 1e3 * rec["seconds"] / rec["count"]
