"""Device time in gather operations over device busy time, from the
trace (the BFS sweep's per-edge reads of the O(n) state, and ALTERNATE's)."""

import trace_reduce


def read(ctx: dict):
    return trace_reduce.class_share(ctx, "gather")
