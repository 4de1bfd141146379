"""Device time in scatter operations over device busy time, from the
trace (the XLA scatter-min of the BFS sweep and of ALTERNATE)."""

import trace_reduce


def read(ctx: dict):
    return trace_reduce.class_share(ctx, "scatter")
