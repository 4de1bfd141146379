"""Seconds per solve: the whole window over the solves completed in it
(the window ends when the last solve it started finishes)."""


def read(ctx: dict):
    solves = ctx.get("solves")
    if not solves:
        return None
    return ctx["window_s"] / len(solves)
