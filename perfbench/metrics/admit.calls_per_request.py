"""Compiled admit calls the engine made in the window (`n_admit_calls`)
per request admitted in it: ceil(prompt / chunk) when rounds hold one
prompt, fewer when prompts share a round's calls."""


def read(ctx):
    win = ctx["window"]
    if not win.admitted:
        return None
    return win.admit_calls / win.admitted
