"""Model step: ``step.mfu`` over the window's steps that carried a prefill
piece, the steps a request waits through for its first token; beside the
chunk kernel's roofline. Moves ``ttft_p95_s``."""


def read(ctx):
    steps = [s for s in ctx.steps if s.chunks or s.wholes]
    return ctx.flops.step_mfu(ctx.cfg, steps, ctx.peaks)
