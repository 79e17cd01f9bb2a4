"""Jitted public wrappers around the Pallas kernels.

One :class:`AttentionSpec` describes everything the attention kernels need
beyond the tensors themselves — sliding window, logit softcap, softmax
scale, head layout, MLA latent dims — so the engine builds the spec once
per layer (at :class:`~repro.engine.model_exec.ModelExec` construction)
instead of threading six kwargs through every call site.

``paged_decode_attention`` is the engine's decode attention hot path and
``paged_prefill_attention`` the chunked-prefill one. Both dispatch through
the shared :mod:`repro.kernels.dispatch` resolver (``REPRO_QUANT_KERNEL``
env var or :func:`set_quant_kernel_mode`), the same four modes as the
wNa16 GEMM:

  * ``auto``             — compiled Pallas on TPU, XLA fallback elsewhere
  * ``pallas``           — compiled Pallas (Mosaic) unconditionally
  * ``pallas_interpret`` — Pallas interpret mode (kernel-body validation on
                           CPU; used by the parity/token-identity tests)
  * ``xla``              — the bucketed jnp gather (attention) / the
                           packed-dequant fused matmul (wNa16): the
                           numerically pinned fallback + parity oracle

The mode is read at trace time — set it before building jitted callables
(the engine's per-instance jit caches make this safe per engine).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels import paged_attention as pa
from repro.kernels.wna16_gemm import wna16_gemm as _gemm

_QUANT_KERNEL_MODES = dispatch.MODES


def set_quant_kernel_mode(mode: str) -> str:
    """Set the kernel dispatch mode; returns the previous mode."""
    return dispatch.set_mode(mode)


def quant_kernel_mode() -> str:
    """Resolved dispatch mode (``auto`` resolves by backend)."""
    return dispatch.resolve()


# ---------------------------------------------------------------------------
# AttentionSpec: the one attention-parameter bundle of the data plane
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Static attention configuration shared by decode / prefill / chunk.

    Frozen + hashable so it can be baked into jitted callables as a static
    argument. ``scale=None`` means the kernel default ``head_dim ** -0.5``.
    ``latent_dv`` enables the MLA latent mode: keys/values live in one
    latent pool of width ``kv_lora_rank + rope`` (``kv_heads == 1``),
    scores span the full latent width, and the value accumulation keeps
    only the first ``latent_dv`` (= ``kv_lora_rank``) lanes — the paged
    form of the DeepSeek weight-absorption identity. ``q_heads`` /
    ``kv_heads`` are the head layout (GQA group = q_heads // kv_heads);
    they are informational for shape checks and may be omitted.
    """
    window: int = 0
    softcap: float = 0.0
    scale: Optional[float] = None
    q_heads: Optional[int] = None
    kv_heads: Optional[int] = None
    latent_dv: Optional[int] = None
    # kv-dtype lane: when True the layer's pool may hold quantized blocks
    # and attention calls thread the (qk, qv, k_scale, v_scale, k_zero,
    # v_zero) sidecar bundle for in-kernel (or explicit, on the xla
    # oracle) dequantization of quantized blocks during the walk.
    kv_quant: bool = False

    def validate(self, q, k_pool) -> None:
        if self.q_heads is not None:
            assert q.shape[-2] == self.q_heads, (q.shape, self)
        if self.kv_heads is not None:
            assert k_pool.shape[2] == self.kv_heads, (k_pool.shape, self)


def _spec_of(spec, window, softcap):
    """The pre-spec ``window=``/``softcap=`` kwargs are gone (PR 9 kept a
    shim; all callers have moved): passing them is now a hard error."""
    if window or softcap:
        raise TypeError(
            "the deprecated window=/softcap= kwargs were removed from "
            "kernels.ops — build an AttentionSpec(window=..., softcap=...) "
            "once and pass it as `spec` (ModelExec builds per-layer specs "
            "at construction; see ops.AttentionSpec)")
    return AttentionSpec() if spec is None else spec


# ---------------------------------------------------------------------------
# wNa16 quantized matmul
# ---------------------------------------------------------------------------
def _xla_packed_matmul(x2, qt, bias):
    """Packed-dequant fallback, one traced graph so XLA fuses the epilogue.
    Numerically identical to the default jnp QTensor path."""
    if qt.inv_act is not None:
        x2 = x2 * qt.inv_act.astype(x2.dtype)
    y = jnp.matmul(x2, qt.dequantize(x2.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def wna16_matmul(x2, qt, *, bias=None):
    """x2: (M, K) × QTensor (K, N) → (M, N) in ``x2.dtype``.

    Fused epilogue: AWQ ``inv_act`` equalization, optional ``bias`` (N,),
    cast to the activation dtype — no fp32 round-trips through HBM.
    """
    assert qt.bits in (4, 8), "Pallas path supports int4/int8 (DESIGN.md §2)"
    mode = dispatch.resolve()
    if mode == "xla":
        return _xla_packed_matmul(x2, qt, bias)
    return _gemm(x2, qt.packed, qt.scales, qt.zeros, qt.inv_act, bias,
                 bits=qt.bits, group=qt.group, out_dtype=jnp.dtype(x2.dtype),
                 interpret=(mode == "pallas_interpret"))


# ---------------------------------------------------------------------------
# paged attention (decode + chunked prefill), AttentionSpec-driven
# ---------------------------------------------------------------------------
def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    spec: AttentionSpec = None, *,
                    kv_quant=None, window: int = 0, softcap: float = 0.0):
    """Context-only decode read (no append): the block walk under the
    Pallas modes, the bucketed jnp gather under ``xla``. All static
    parameters come from ``spec``; ``kv_quant`` is the optional
    quantized-KV sidecar bundle for this layer's pool."""
    spec = _spec_of(spec, window, softcap)
    mode = dispatch.resolve()
    if mode == "xla":
        # the query is the newest pool token, at context_lens - 1
        return pa.paged_gather_attention(q, k_pool, v_pool, block_tables,
                                         context_lens - 1,
                                         window=spec.window,
                                         softcap=spec.softcap,
                                         scale=spec.scale, kv_quant=kv_quant)
    return pa.paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                              window=spec.window, softcap=spec.softcap,
                              scale=spec.scale,
                              interpret=(mode == "pallas_interpret"),
                              kv_quant=kv_quant)


def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, block_tables,
                           pos, spec: AttentionSpec = None, *,
                           kv_quant=None, window: int = 0,
                           softcap: float = 0.0):
    """Decode attention over pool KV + the current token (B, KVH, Dh).

    Contract: the caller has already scattered (k_new, v_new) into the pool
    at position ``pos[b]`` (the scatter and this read are independent — the
    Pallas kernel only reads positions < pos and takes the new token as a
    VMEM operand). ``block_tables`` may be truncated to any width covering
    ``pos // block_size``; cost scales with that width on the gather path.

    Dispatch: ``pallas``/``pallas_interpret`` run the fused block-walk
    kernel; ``xla`` the bucketed jnp gather; ``auto`` picks by backend.
    """
    spec = _spec_of(spec, window, softcap)
    mode = dispatch.resolve()
    if mode == "xla":
        return pa.paged_gather_attention(q, k_pool, v_pool, block_tables,
                                         pos, window=spec.window,
                                         softcap=spec.softcap,
                                         scale=spec.scale, kv_quant=kv_quant)
    return pa.paged_attention_fused(q, k_new, v_new, k_pool, v_pool,
                                    block_tables, pos, window=spec.window,
                                    softcap=spec.softcap, scale=spec.scale,
                                    interpret=(mode == "pallas_interpret"),
                                    kv_quant=kv_quant)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, pos0,
                            spec: AttentionSpec = None, *,
                            k_new=None, v_new=None, kv_quant=None,
                            window: int = 0, softcap: float = 0.0):
    """Chunked-prefill attention: C queries at positions ``pos0 + i`` over
    paged context, mirroring ``paged_decode_attention`` for the decode hot
    path. The caller has already scattered the chunk's own KV into the pool
    at the request's block-table offset.

    Dispatch: under ``pallas``/``pallas_interpret`` this is the fused
    chunk block-walk kernel — when the chunk's (k_new, v_new), shape
    (B, C, KVH, Dh), are passed, the multi-token batched-append variant
    folds them into the softmax as VMEM operands and the walk never
    re-reads the just-appended chunk from the HBM pool; without them the
    pool-read variant re-gathers the chunk from the pool. Under ``xla``
    (and ``auto`` off-TPU) it is the bucketed jnp gather — the numerically
    pinned reference the kernel must match, whose cost already tracks the
    caller-bucketed table width, not ``max_blocks_per_seq``.

    MLA latent pools go through ``spec.latent_dv``/``spec.scale`` with the
    absorbed query (see ``model_exec._chunk_mla_attention``).
    """
    spec = _spec_of(spec, window, softcap)
    mode = dispatch.resolve()
    if mode == "xla":
        return pa.paged_chunk_gather_attention(
            q, k_pool, v_pool, block_tables, pos0, window=spec.window,
            softcap=spec.softcap, scale=spec.scale, dv=spec.latent_dv,
            kv_quant=kv_quant)
    interpret = mode == "pallas_interpret"
    if k_new is not None:
        return pa.paged_chunk_attention_fused(
            q, k_new, v_new, k_pool, v_pool, block_tables, pos0,
            window=spec.window, softcap=spec.softcap, scale=spec.scale,
            dv=spec.latent_dv, interpret=interpret, kv_quant=kv_quant)
    return pa.paged_chunk_attention(
        q, k_pool, v_pool, block_tables, pos0, window=spec.window,
        softcap=spec.softcap, scale=spec.scale, dv=spec.latent_dv,
        interpret=interpret, kv_quant=kv_quant)
