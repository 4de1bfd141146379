"""Solver phases per solve (``MatchState.phases``), mean over the window."""


def read(ctx: dict):
    solves = ctx.get("solves")
    if not solves:
        return None
    return sum(s["phases"] for s in solves) / len(solves)
