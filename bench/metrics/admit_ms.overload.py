"""Host milliseconds per request in ``MatchingService.submit``'s admission
(``repro.serve.admit``: the bucketizer's validation and padding through the
enqueue), ``ServiceMetrics`` ``admit_s`` over ``submitted``."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or "admit_s" not in snap or not snap["submitted"]:
        return None
    return 1e3 * snap["admit_s"] / snap["submitted"]
