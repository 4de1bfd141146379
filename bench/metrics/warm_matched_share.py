"""Share of the final matching that the warm start alone finds:
cardinality after ``Matcher.init`` over the solve's, summed over the
cell's graphs."""


def read(ctx: dict):
    solves, warm = ctx.get("solves"), ctx.get("warm_sizes")
    if not solves or warm is None:
        return None
    final = {s["graph"]: s["size"] for s in solves}
    if len(final) != len(warm) or not sum(final.values()):
        return None
    return 100.0 * sum(warm) / sum(final.values())
