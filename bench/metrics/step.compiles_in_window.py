"""Model step: XLA backend compiles during the window's steps. Moves
``itl_p99_ms``."""


def read(ctx):
    return sum(s.compiles for s in ctx.steps)
