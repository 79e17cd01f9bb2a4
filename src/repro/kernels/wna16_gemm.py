"""Pallas TPU kernel: fused dequant + matmul for weight-only INT4/INT8 GEMM.

The compute hot-spot of MorphServe's quantized layer variants (paper §3.3:
AWQ INT4 inference kernels). TPU adaptation: dequantization happens in VMEM
right before the MXU matmul, so HBM traffic is the *packed* weight bytes —
4x (int4) / 2x (int8) less than bf16. Decode is weight-bandwidth-bound, which
is exactly why swapped layers speed up TPOT (paper Fig. 7).

The whole epilogue is fused so the serving data plane never round-trips an
fp32 weight or activation through HBM:

  out = cast((x * inv_act) @ dequant(packed) + bias, out_dtype)

``inv_act`` is the AWQ activation-equalization reciprocal (QTensor.inv_act),
``bias`` the layer bias, and the accumulator stays fp32 in VMEM scratch
regardless of ``out_dtype``.

Grid: (M/bm, N/bn, K/bk), K innermost; the (bm, bn) fp32 accumulator stays
resident in VMEM scratch across the K sweep. ``bm`` auto-selects from
{8, 16, 32, 64, 128} — decode GEMMs (M = batch slots) get skinny 8/16-row
blocks instead of padding to 128.

Weight layout (matches quant/pack.py):
  int4: (K/2, N) uint8, low nibble = even k, high nibble = odd k
  int8: (K, N) uint8
  scales/zeros: (K/group, N) float32.

The K block size is always a multiple of ``group`` (and even, for int4):
scales/zeros are built at the caller's group size, so shrinking the group to
fit a block — what this file did before — silently misindexes them. The TPU
tiling rule adds that a block's second-minor dim is a multiple of 8 or the
whole axis, so the (bk // group, bn) scale block needs ``bk // group`` to be
a multiple of 8 — or ``bk == K``, one K block covering every group row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

_BM_CANDIDATES = (8, 16, 32, 64, 128)


def _pick_bm(M: int) -> int:
    """Decode-skinny M blocking: smallest aligned block covering M."""
    for c in _BM_CANDIDATES:
        if M <= c:
            return c
    return _BM_CANDIDATES[-1]


def _pick_bk(K: int, group: int, bits: int, bk: int) -> int:
    """Largest K block <= ``bk`` (at least one quantum) that divides K,
    where the quantum is 8 quantization groups rounded up to a multiple of
    128 lanes: the (bm, bk) activation block then tiles, the (bk // group,
    bn) scale/zero blocks hold a multiple of 8 rows, and the int4 packed
    block (bk // 2, bn) does too. When no quantum divides K the block is
    all of K (every block dim then equals its array dim)."""
    assert K % group == 0 and (bits != 4 or K % 2 == 0), (K, group, bits)
    quantum = math.lcm(8 * group, 128)
    if K % quantum:
        return K
    m = K // quantum
    d = max(1, min(bk // quantum, m))
    while m % d:
        d -= 1
    return quantum * d


def _dequant_block(w_ref, s_ref, z_ref, bits: int, bk: int, group: int):
    """Unpack + dequantize one (bk, bn) weight block in VMEM."""
    # Mosaic has no uint8 -> f32 cast; the codes go through int32 (exact)
    if bits == 4:
        packed = w_ref[...].astype(jnp.int32)      # (bk//2, bn)
        lo = (packed & 0xF).astype(jnp.float32)
        hi = ((packed >> 4) & 0xF).astype(jnp.float32)
        q = jnp.stack([lo, hi], axis=1).reshape(bk, packed.shape[-1])
    else:                                          # int8
        q = w_ref[...].astype(jnp.int32).astype(jnp.float32)   # (bk, bn)
    s = jnp.repeat(s_ref[...], group, axis=0)      # (bk, bn)
    z = jnp.repeat(z_ref[...], group, axis=0)
    return (q - z) * s


def _wna16_kernel(*refs, bits: int, bk: int, group: int, n_k: int,
                  has_inv: bool, has_bias: bool):
    """refs: x, w, s, z, [inv_act], [bias], out, acc_scratch."""
    it = iter(refs)
    x_ref, w_ref, s_ref, z_ref = next(it), next(it), next(it), next(it)
    inv_ref = next(it) if has_inv else None
    b_ref = next(it) if has_bias else None
    o_ref, acc_ref = next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_block(w_ref, s_ref, z_ref, bits, bk, group)
    x = x_ref[...].astype(jnp.float32)             # (bm, bk)
    if has_inv:
        x = x * inv_ref[...].astype(jnp.float32)   # (1, bk) broadcast
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def wna16_gemm(x, packed, scales, zeros, inv_act=None, bias=None, *,
               bits: int, group: int, out_dtype=None,
               bm: int = 0, bn: int = 128, bk: int = 2048,
               interpret: bool = None):
    """x: (M, K) × packed int{4,8} (K-packed, N) → (M, N) ``out_dtype``.

    ``inv_act`` (K,) and ``bias`` (N,) are optional fused-epilogue operands;
    ``out_dtype`` defaults to ``x.dtype``. M is padded to the auto-selected
    skinny block; K is blocked by :func:`_pick_bk`; N is blocked at the
    largest power-of-two divisor <= ``bn``. ``interpret=None`` resolves
    through :func:`dispatch.kernel_interpret` (compiled under ``pallas``,
    interpreted under ``pallas_interpret``; the XLA path is
    ``ops.wna16_matmul``'s).
    """
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    return _wna16_pallas(x, packed, scales, zeros, inv_act, bias, bits=bits,
                         group=group, out_dtype=out_dtype, bm=bm, bn=bn,
                         bk=bk, interpret=dispatch.kernel_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bits", "group", "out_dtype",
                                             "bm", "bn", "bk", "interpret"))
def _wna16_pallas(x, packed, scales, zeros, inv_act, bias, *, bits, group,
                  out_dtype, bm, bn, bk, interpret):
    M, K = x.shape
    N = scales.shape[-1]
    bm = bm or _pick_bm(M)
    bk = _pick_bk(K, group, bits, bk)
    bn = min(bn, N)
    while N % bn:
        bn //= 2
    assert K % bk == 0 and N % bn == 0 and bk % group == 0, (K, N, bk, group)
    pad_m = (-M) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    n_k = K // bk
    grid = (Mp // bm, N // bn, n_k)

    kdiv = 2 if bits == 4 else 1
    has_inv = inv_act is not None
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk // kdiv, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
    ]
    operands = [x, packed, scales, zeros]
    if has_inv:
        in_specs.append(pl.BlockSpec((1, bk), lambda i, j, k: (0, k)))
        operands.append(inv_act.reshape(1, K))
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(bias.reshape(1, N))
    out = pl.pallas_call(
        functools.partial(_wna16_kernel, bits=bits, bk=bk, group=group,
                          n_k=n_k, has_inv=has_inv, has_bias=has_bias),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="wna16_gemm",
    )(*operands)
    return out[:M]
