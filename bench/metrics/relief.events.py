"""Relief ladder: the ladder's actions during the window's steps
(``StepRec.events``): swap-level changes and KV pool resizes, and int8 KV
batches where the configuration turns that tier on. Moves
``ttft_p95_s``."""


def read(ctx):
    if not ctx.steps:
        return None
    return sum(s.events for s in ctx.steps)
