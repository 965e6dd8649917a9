"""Backend compiles JAX ran inside the window (a shape set-up missed)."""


def read(ctx):
    return int(ctx.compiles)
