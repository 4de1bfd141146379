"""Milliseconds per batched flush in ``repro.serve.solve`` (the ``vmap``
solve, ``run_many`` through ``block_until_ready`` and the copy of the
lanes' BFS levels), ``ServiceMetrics`` ``batch_solve_s`` over
``batch_flushes``."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or "batch_solve_s" not in snap or not snap["batch_flushes"]:
        return None
    return 1e3 * snap["batch_solve_s"] / snap["batch_flushes"]
