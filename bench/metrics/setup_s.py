"""Set-up time: process start to the window's first request (loading,
weight generation, adapter pages, warm-up compiles or cache loads)."""


def read(ctx):
    return ctx.setup_s
