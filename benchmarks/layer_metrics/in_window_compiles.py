"""``in_window_compiles``: compile-cache entries after the window minus
before it. Must read 0."""


def read(ctx):
    return ctx.in_window_compiles
