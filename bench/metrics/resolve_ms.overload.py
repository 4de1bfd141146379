"""Host milliseconds per batched flush in ``repro.serve.resolve`` (each
request's result sliced out of the batch, ``MatchStats.of``, and
``set_result`` with the callbacks it runs), ``ServiceMetrics``
``resolve_s`` over ``batch_flushes``."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or "resolve_s" not in snap or not snap["batch_flushes"]:
        return None
    return 1e3 * snap["resolve_s"] / snap["batch_flushes"]
