"""Device time in the merge of the edge shards (the instructions under the
scope ``merge_shards``: one all-reduce of the winner vector per BFS level)
over device busy time, from the trace."""

import trace_reduce


def read(ctx: dict):
    return trace_reduce.class_share(ctx, "merge")
