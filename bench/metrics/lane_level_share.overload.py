"""BFS levels the batches' real lanes needed over the levels the lock-step
batches ran them for: ``ServiceMetrics`` ``lane_levels`` (each lane's own
levels, summed) over ``lane_level_slots`` (per flush, real lanes times the
deepest real lane's levels).

An upper bound on lock-step efficiency: within each phase a batch runs its
deepest lane's levels, and whole-solve totals per lane cannot see that."""


def read(ctx: dict):
    snap = ctx.get("service")
    if not snap or not snap.get("lane_level_slots"):
        return None
    return 100.0 * snap["lane_levels"] / snap["lane_level_slots"]
