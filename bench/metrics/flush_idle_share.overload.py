"""Share of the traced window in which the device is idle while the flush
thread is inside ``repro.serve.stack`` or ``repro.serve.resolve``: the part
of ``device_idle_share.overload`` that the batch's host work before and
after its solve holds the device back by."""

import program_spans

FLUSH_HOST = ("repro.serve.stack", "repro.serve.resolve")


def read(ctx: dict):
    if not ctx.get("trace"):
        return None
    loaded = program_spans.load()
    if loaded is None:
        return None
    return program_spans.idle_under(*loaded, FLUSH_HOST)
