"""Share of the traced window in which no operation ran on the device."""

import trace_reduce


def read(ctx: dict):
    return trace_reduce.idle_share(ctx)
