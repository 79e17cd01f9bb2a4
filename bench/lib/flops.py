"""Operations and bytes the served model needs, from its shapes.

These are what the algorithm requires, not what a kernel happens to do:
padding rows, idle decode slots and re-reads are not counted, so a kernel
that wastes work reads lower against its roofline.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench.lib import weights as W


def matmul_params(cfg: dict) -> int:
    """Parameters one token multiplies through: every layer's projections
    (int4 layers count at the model's size) plus the output head."""
    z = W.dims(cfg)
    D, H, KVH, Dh, F, V, L = (z[k] for k in ("D", "H", "KVH", "Dh", "F",
                                             "V", "L"))
    per_layer = D * (H + 2 * KVH) * Dh + H * Dh * D + 3 * D * F
    return L * per_layer + D * V


def attn_flops(cfg: dict, n_query: int, ctx_before: int) -> int:
    """QK^T and PV of ``n_query`` causal queries starting at position
    ``ctx_before``, over all layers: 4 flops per (query, key, head, dim)."""
    z = W.dims(cfg)
    pairs = n_query * ctx_before + n_query * (n_query + 1) // 2
    return 4 * z["L"] * z["H"] * z["Dh"] * pairs


def step_flops(cfg: dict, decode_ctx: Iterable[int],
               chunks: Iterable[Tuple[int, int]], logits_rows: int) -> int:
    """FLOPs one engine step requires: 2 per parameter per token through the
    layers, attention over each token's context, and the head only for the
    rows whose logits are used (one per decode row and per prompt end).

    ``decode_ctx``: context length before each decoded token;
    ``chunks``: (start position, tokens) of each prefill piece."""
    z = W.dims(cfg)
    layer_params = matmul_params(cfg) - z["D"] * z["V"]
    decode_ctx = list(decode_ctx)
    chunks = list(chunks)
    tokens = len(decode_ctx) + sum(n for _, n in chunks)
    f = 2 * layer_params * tokens + 2 * z["D"] * z["V"] * logits_rows
    f += sum(attn_flops(cfg, 1, c) for c in decode_ctx)
    f += sum(attn_flops(cfg, n, p0) for p0, n in chunks)
    return f


def _block_bytes(cfg: dict, quantized: bool) -> int:
    """One stored KV block of one layer, K and V: bf16 values, or int8
    payload plus the four f32 scale/zero numbers of the block."""
    z = W.dims(cfg)
    n = cfg["serving"]["kv_block_size"] * z["KVH"] * z["Dh"]
    return 2 * n + 16 if quantized else 2 * 2 * n


def decode_attn_cost(cfg: dict, rows: Iterable[Tuple[int, int]]
                     ) -> Tuple[int, int]:
    """(flops, bytes) ``paged_decode_attention`` needs over all layers for
    one step. ``rows``: (context length before the new token, blocks of it
    held quantized) per live row. Each live block is read once at its stored
    precision; the query, the new token's K/V and the output once each."""
    z = W.dims(cfg)
    bs = cfg["serving"]["kv_block_size"]
    L, H, KVH, Dh = z["L"], z["H"], z["KVH"], z["Dh"]
    flops = nbytes = 0
    for ctx, nq in rows:
        flops += attn_flops(cfg, 1, ctx)
        blocks = -(-ctx // bs)
        kv = (blocks - nq) * _block_bytes(cfg, False) \
            + nq * _block_bytes(cfg, True)
        nbytes += L * (kv + 2 * (2 * H * Dh) + 2 * (2 * KVH * Dh))
    return flops, nbytes


def chunk_attn_cost(cfg: dict, chunks: Iterable[Tuple[int, int, int]]
                    ) -> Tuple[int, int]:
    """(flops, bytes) ``paged_chunk_attention`` needs for prefill pieces
    ``(start, tokens, quantized context blocks)``: causal attention of the
    piece over its context and itself; the context's blocks read once at
    their stored precision, the piece's q, k, v and output once each."""
    z = W.dims(cfg)
    bs = cfg["serving"]["kv_block_size"]
    L, H, KVH, Dh = z["L"], z["H"], z["KVH"], z["Dh"]
    flops = nbytes = 0
    for p0, n, nq in chunks:
        flops += attn_flops(cfg, n, p0)
        blocks = -(-p0 // bs)
        kv = (blocks - nq) * _block_bytes(cfg, False) \
            + nq * _block_bytes(cfg, True)
        nbytes += L * (kv + n * 2 * (2 * H * Dh + 2 * KVH * Dh))
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
