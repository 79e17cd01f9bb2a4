"""Kernels: the least time the FLOPs and bytes ``paged_decode_attention``
needs in the traced steps (each live block read once at its stored
precision) over the kernel's device time in the trace, in %. Moves
``output_tok_per_s``: most of a step is decode, and the kernel runs in
every step."""
KERNEL = "paged_decode_attention"


def read(ctx):
    f = ctx.flops
    need = sum(f.roofline_s(*f.decode_attn_cost(ctx.cfg, s.decode_ctx),
                            ctx.peaks) for s in ctx.traced_steps
               if s.decode_ctx)
    from bench.lib.trace import kernel_seconds
    took = kernel_seconds(ctx.trace["ops"], KERNEL)
    if need <= 0 or took <= 0:
        return None
    return 100.0 * need / took
