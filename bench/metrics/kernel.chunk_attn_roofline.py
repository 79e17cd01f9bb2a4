"""Kernels: the least time the FLOPs and bytes ``paged_chunk_attention``
needs for the traced steps' prefill chunks (context blocks read once at
their stored precision) over the kernel's device time in the trace, in %.
Moves ``ttft_p95_s``."""
KERNEL = "paged_chunk_attention"


def read(ctx):
    f = ctx.flops
    need = sum(f.roofline_s(*f.chunk_attn_cost(ctx.cfg, s.chunks), ctx.peaks)
               for s in ctx.traced_steps if s.chunks)
    from bench.lib.trace import kernel_seconds
    took = kernel_seconds(ctx.trace["ops"], KERNEL)
    if need <= 0 or took <= 0:
        return None
    return 100.0 * need / took
