"""Requests answered correctly inside the window, over the window."""


def read(ctx: dict):
    answered = ctx.get("answered_s")
    if answered is None:
        return None
    n = sum(1 for t in answered if t is not None and t <= ctx["seconds"])
    return n / ctx["seconds"]
