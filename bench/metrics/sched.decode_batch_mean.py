"""Scheduler: requests decoded per engine step, over the window's steps.
Moves ``output_tok_per_s``."""


def read(ctx):
    if not ctx.steps:
        return None
    return sum(len(s.decode_ctx) for s in ctx.steps) / len(ctx.steps)
