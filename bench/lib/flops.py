"""Operations and bytes the served model needs, from its shapes, as its
architecture module (``cfg["arch"]``) counts them.

These are what the algorithm requires, not what a kernel happens to do:
padding rows, idle decode slots and re-reads are not counted, so a kernel
that wastes work reads lower against its roofline.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def matmul_params(cfg: dict) -> int:
    """Parameters one token multiplies through: those its layers touch
    (int4 layers count at the model's size) plus the output head."""
    arch = cfg["arch"]
    return arch.touched_params(cfg) + arch.head_params(cfg)


def step_flops(cfg: dict, decode_ctx: Iterable[int],
               chunks: Iterable[Tuple[int, int]], logits_rows: int) -> int:
    """FLOPs one engine step requires: 2 per parameter a token touches
    through the layers, attention over each token's context, and the head
    only for the rows whose logits are used (one per decode row and per
    prompt end).

    ``decode_ctx``: context length before each decoded token;
    ``chunks``: (start position, tokens) of each prefill piece."""
    arch = cfg["arch"]
    decode_ctx = list(decode_ctx)
    chunks = list(chunks)
    tokens = len(decode_ctx) + sum(n for _, n in chunks)
    f = 2 * arch.touched_params(cfg) * tokens \
        + 2 * arch.head_params(cfg) * logits_rows
    f += sum(arch.attn_flops(cfg, 1, c) for c in decode_ctx)
    f += sum(arch.attn_flops(cfg, n, p0) for p0, n in chunks)
    return f


def _blocks_bytes(cfg: dict, ctx: int, nq: int) -> int:
    """The stored blocks of ``ctx`` positions, ``nq`` of them quantized,
    each read once at its stored precision."""
    arch = cfg["arch"]
    blocks = -(-ctx // cfg["serving"]["kv_block_size"])
    return (blocks - nq) * arch.kv_read_bytes(cfg, False) \
        + nq * arch.kv_read_bytes(cfg, True)


def decode_attn_cost(cfg: dict, rows: Iterable[Tuple[int, int]]
                     ) -> Tuple[int, int]:
    """(flops, bytes) ``paged_decode_attention`` needs over all layers for
    one step. ``rows``: (context length before the new token, blocks of it
    held quantized) per live row. Each live block is read once at its stored
    precision; the query, the new token's K/V and the output once each."""
    arch = cfg["arch"]
    io = arch.decode_io_bytes(cfg)
    flops = nbytes = 0
    for ctx, nq in rows:
        flops += arch.attn_flops(cfg, 1, ctx)
        nbytes += _blocks_bytes(cfg, ctx, nq) + io
    return flops, nbytes


def chunk_attn_cost(cfg: dict, chunks: Iterable[Tuple[int, int, int]]
                    ) -> Tuple[int, int]:
    """(flops, bytes) ``paged_chunk_attention`` needs for prefill pieces
    ``(start, tokens, quantized context blocks)``: causal attention of the
    piece over its context and itself; the context's blocks read once at
    their stored precision, the piece's q, k, v and output once each."""
    arch = cfg["arch"]
    io = arch.chunk_io_bytes(cfg)
    flops = nbytes = 0
    for p0, n, nq in chunks:
        flops += arch.attn_flops(cfg, n, p0)
        nbytes += _blocks_bytes(cfg, p0, nq) + n * io
    return flops, nbytes


def roofline_s(flops: int, nbytes: int, peaks: dict) -> float:
    """The least time the chip can take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def step_mfu(cfg: dict, steps, peaks: dict):
    """FLOPs ``steps`` (the window's ``StepRec``s) required over their
    summed wall time x the chip's bf16 peak, in %; None with no work."""
    work = sum(step_flops(cfg, [c for c, _ in s.decode_ctx],
                          [(p, n) for p, n, _ in s.chunks] + s.wholes,
                          s.logits_rows) for s in steps)
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0 or work <= 0:
        return None
    return 100.0 * work / (wall * peaks["bf16_flops"])
