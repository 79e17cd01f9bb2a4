"""Model step: FLOPs the window's steps required over (their summed wall
time x the chip's bf16 peak), in %. FLOPs are 2 x parameters x tokens (int4
layers at the model's size), attention over each token's context, and the
head for the rows whose logits are used. Moves ``output_tok_per_s``."""


def read(ctx):
    return ctx.flops.step_mfu(ctx.cfg, ctx.steps, ctx.peaks)
