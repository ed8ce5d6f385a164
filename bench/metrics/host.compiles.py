"""Backend compiles and compile-cache loads inside the window (should be 0)."""


def read(ctx):
    return ctx.compiles
