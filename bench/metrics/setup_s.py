"""Set-up time: process start to window start (host clock)."""


def read(ctx):
    return ctx["setup_s"]
