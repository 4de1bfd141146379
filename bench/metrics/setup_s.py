"""Set-up: process start to window start, host clock."""


def read(ctx: dict):
    return ctx["setup_s"]
