"""Mean device time of one `admit` program execution in the traced
window."""


def read(ctx):
    rec = ctx["trace"]["programs"].get("admit")
    if not rec or not rec["count"]:
        return None
    return 1e3 * rec["seconds"] / rec["count"]
