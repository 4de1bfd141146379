"""Host milliseconds per batched flush in ``repro.serve.stack`` (claim,
inert-lane padding, ``DeviceCSR.stack``), ``ServiceMetrics`` ``stack_s``
over ``batch_flushes``."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or "stack_s" not in snap or not snap["batch_flushes"]:
        return None
    return 1e3 * snap["stack_s"] / snap["batch_flushes"]
