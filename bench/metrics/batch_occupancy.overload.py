"""Real requests per padded batch lane over all flushes
(``ServiceMetrics.occupancy``, the program's own counter)."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or not snap["batch_padded"]:
        return None
    return 100.0 * snap["occupancy"]
