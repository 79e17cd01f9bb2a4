"""Pallas TPU kernel: paged-attention decode (block-table KV indirection).

The attention hot-spot of the serving engine. KV lives in a paged pool
(num_blocks, block_size, kv_heads, head_dim); each sequence owns a list of
physical block ids (its block table). The kernel walks a sequence's blocks
with **scalar-prefetched** block tables — the index_map reads the table to
pick which physical pool block to DMA into VMEM next, which is the TPU-native
equivalent of PagedAttention's pointer indirection (vLLM) and what KVResizer's
elastic pool relies on.

Grid: (batch, max_blocks_per_seq), innermost = block walk with an
online-softmax accumulator per kv head in VMEM scratch. Each grid step DMAs
one whole pool block, ``(1, block_size, KVH, Dh)`` — every kv head of the
block at once — and loops over the heads inside the kernel. The block's last
two dims equal the pool's ``(KVH, Dh)``, which is what the TPU tiling rule
accepts for any head count; a per-head ``(1, block_size, 1, Dh)`` block puts
a size-1 block on the second-minor axis and is refused by the v5e compiler.
For a bf16 pool with KVH=2, XLA tiles the last two dims ``T(2,128)``, so
the layout costs no padding in HBM and reaches the kernel without a copy.
GQA: the G = H/KVH query heads of a kv head are processed together as the
(G, Dh) q block.

Feature parity with ``layers.naive_attention`` for the decode case:
sliding-window masking (``window``), logit softcapping (``softcap``), and a
**fused single-token append** — the current step's (k_new, v_new) enter the
online softmax as VMEM operands at the finish step, so attention never
re-reads the just-appended token from the HBM pool and the pool scatter can
be scheduled independently of the block walk.

``paged_decode_attention`` is the engine-facing fused op: one call appends
the token to the pool and returns the attention output. On TPU it runs the
Pallas kernel; elsewhere it lowers to a jnp gather whose cost tracks the
*caller-truncated* block-table width (the engine buckets tables to the
power-of-two of the live max, so decode HBM traffic follows live context).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch


def _paged_attn_kernel(tables_ref, lens_ref, *refs,
                       block_size: int, max_nb: int, kv_heads: int,
                       scale: float, window: int, softcap: float,
                       fused_new: bool, kv_quant: bool = False):
    # the quantized-KV sidecar (see _walk_call) adds the int8 pool mirrors
    # and the row's per-table-entry (k_scale, v_scale, k_zero, v_zero) in
    # SMEM after the fused-new operands. Dequant is a branch-free FMA
    # (wNa16-epilogue style): a quantized block has its fp slot zeroed, an
    # fp block has scale=0/zero=0, so
    #   k = fp_slot + q * scale + zero
    # is exact for both without a per-block flag operand.
    (q_ref, k_ref, v_ref, kn_ref, vn_ref, qk_ref, qv_ref, qp_ref, o_ref,
     m_scr, l_scr, acc_scr) = _split_refs(refs, kv_quant)
    b = pl.program_id(0)
    nb = pl.program_id(1)
    if kv_quant:
        ks, vs, kz, vz = (qp_ref[0, i, nb] for i in range(4))

    @pl.when(nb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx_len = lens_ref[b]            # pool tokens (excludes the fused new one)
    # query position: the fused new token sits *at* ctx_len; otherwise the
    # newest pool token (decode semantics) anchors the sliding window.
    qpos = ctx_len if fused_new else ctx_len - 1
    base = nb * block_size
    valid = base < ctx_len
    if window > 0:
        # the block can be skipped entirely when even its last position
        # (base + bs - 1) falls outside the window (kpos > qpos - window).
        valid &= (base + block_size - 1) > (qpos - window)

    @pl.when(valid)
    def _step():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        msk = pos < ctx_len                      # (1, bs)
        if window > 0:
            msk &= pos > (qpos - window)
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)         # (G, Dh)
            k = k_ref[0, :, h, :].astype(jnp.float32)   # (bs, Dh)
            v = v_ref[0, :, h, :].astype(jnp.float32)   # (bs, Dh)
            if kv_quant:
                k = k + qk_ref[0, :, h, :].astype(jnp.float32) * ks + kz
                v = v + qv_ref[0, :, h, :].astype(jnp.float32) * vs + vz
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(msk, s, -1e30)             # (G, bs)
            m_prev = m_scr[h]                        # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(nb == max_nb - 1)
    def _finish():
        for h in range(kv_heads):
            if fused_new:
                # fold the current token in (always visible: kpos == qpos).
                q = q_ref[0, h].astype(jnp.float32)       # (G, Dh)
                kn = kn_ref[0, h].astype(jnp.float32)     # (1, Dh)
                vn = vn_ref[0, h].astype(jnp.float32)     # (1, Dh)
                s = jnp.dot(q, kn.T, preferred_element_type=jnp.float32) \
                    * scale
                if softcap:
                    s = jnp.tanh(s / softcap) * softcap   # (G, 1)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, s)
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * corr + p
                m_scr[h] = m_new
                acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                    p, vn, preferred_element_type=jnp.float32)
            o_ref[0, h] = (acc_scr[h] /
                           jnp.maximum(l_scr[h], 1e-30)).astype(o_ref.dtype)


def _split_refs(refs, kv_quant):
    """Name a kernel's refs after (tables, lens/pos0): with ``kv_quant`` the
    sidecar adds the two int8 mirrors and the scale/zero rows after the
    fused-new operands; without it those three are None."""
    if kv_quant:
        q, k, v, kn, vn, qk, qv, qp, *rest = refs
    else:
        qk = qv = qp = None
        q, k, v, kn, vn, *rest = refs
    return (q, k, v, kn, vn, qk, qv, qp, *rest)


def _pool_spec(bs, kvh, dh):
    """One whole pool block, every kv head: ``tables[b, nb]`` picks it."""
    return pl.BlockSpec((1, bs, kvh, dh),
                        lambda b, nb, tables, *_: (tables[b, nb], 0, 0, 0))


def _row_spec(shape):
    """A per-batch-row operand (q, new KV, output): fetched once per row and
    held across the block walk."""
    return pl.BlockSpec((1,) + shape, lambda b, nb, *_: (b, 0, 0, 0))


def _walk_call(kernel, *, grid, prefetch, in_specs, operands, kv_quant,
               out_shape, scratch_shapes, interpret, name,
               vmem_limit_bytes=None):
    """The block walk's ``pallas_call``: tables and lens/pos0 are
    scalar-prefetched. The quantized-KV sidecar, when given, adds its int8
    pool mirrors as two more walked inputs and each batch row's
    (k_scale, v_scale, k_zero, v_zero) for every table entry as a
    ``(4, max_nb)`` SMEM row block, gathered through the tables outside the
    kernel: a (1, 1) VMEM block per pool block is refused by the TPU tiling
    rule, and a pool-wide scalar-prefetch row outgrows the 1 MiB of SMEM
    at 65536 blocks, where this row grows with the table only."""
    bs, kvh, dh = operands[1].shape[1:]
    if kv_quant is not None:
        qk, qv, ks, vs, kz, vz = kv_quant
        tables = prefetch[0]
        rows = jnp.stack([ks, vs, kz, vz]).astype(jnp.float32)[:, tables]
        in_specs = in_specs + [_pool_spec(bs, kvh, dh)] * 2 + [
            pl.BlockSpec((1, 4, tables.shape[1]), lambda b, nb, *_: (b, 0, 0),
                         memory_space=pltpu.SMEM)]
        operands = list(operands) + [qk, qv, rows.transpose(1, 0, 2)]
    return pl.pallas_call(
        functools.partial(kernel, kv_quant=kv_quant is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
            out_specs=_row_spec(out_shape.shape[1:]),
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=dispatch.kernel_interpret(interpret),
        name=name,
    )(*prefetch, *operands)


def _paged_call(qg, k_pool, v_pool, k_new, v_new, block_tables, context_lens,
                *, window, softcap, fused_new, interpret, scale=None,
                kv_quant=None):
    B, KVH, G, Dh = qg.shape
    bs = k_pool.shape[1]
    max_nb = block_tables.shape[1]
    scale = Dh ** -0.5 if scale is None else scale

    return _walk_call(
        functools.partial(_paged_attn_kernel, block_size=bs, max_nb=max_nb,
                          kv_heads=KVH, scale=scale, window=window,
                          softcap=softcap, fused_new=fused_new),
        grid=(B, max_nb), prefetch=(block_tables, context_lens),
        in_specs=[_row_spec((KVH, G, Dh)), _pool_spec(bs, KVH, Dh),
                  _pool_spec(bs, KVH, Dh), _row_spec((KVH, 1, Dh)),
                  _row_spec((KVH, 1, Dh))],
        operands=[qg, k_pool, v_pool, k_new, v_new], kv_quant=kv_quant,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, Dh), qg.dtype),
        scratch_shapes=[pltpu.VMEM((KVH, G, 1), jnp.float32),
                        pltpu.VMEM((KVH, G, 1), jnp.float32),
                        pltpu.VMEM((KVH, G, Dh), jnp.float32)],
        interpret=interpret, name="paged_decode_attention")


def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    window: int = 0, softcap: float = 0.0,
                    scale: float = None, interpret: bool = None,
                    kv_quant=None):
    """q: (B, H, Dh); pools: (num_blocks, bs, KVH, Dh);
    block_tables: (B, max_nb) int32; context_lens: (B,) int32 → (B, H, Dh).

    All ``context_lens[b]`` tokens live in the pool; the query is the token at
    position ``context_lens[b] - 1`` (decode). ``window`` > 0 applies sliding-
    window masking anchored at that position; ``softcap`` > 0 tanh-caps the
    logits. Unused table entries may hold any valid block id (length-masked).

    ``kv_quant`` is the optional quantized-KV sidecar bundle
    ``(qk, qv, k_scale, v_scale, k_zero, v_zero)`` — int8 pool mirrors plus
    per-block f32 scale/zero vectors — enabling in-kernel dequant of
    quantized blocks during the walk (fp blocks carry zeroed params).

    ``interpret=None`` resolves through :func:`dispatch.kernel_interpret`
    (compiled under ``pallas``, interpreted under ``pallas_interpret``).
    """
    B, H, Dh = q.shape
    KVH = k_pool.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, Dh)
    zero = jnp.zeros((B, KVH, 1, Dh), q.dtype)
    out = _paged_call(qg, k_pool, v_pool, zero, zero, block_tables,
                      context_lens, window=window, softcap=softcap,
                      scale=scale, fused_new=False, interpret=interpret,
                      kv_quant=kv_quant)
    return out.reshape(B, H, Dh)


def paged_attention_fused(q, k_new, v_new, k_pool, v_pool, block_tables,
                          pos, *, window: int = 0, softcap: float = 0.0,
                          scale: float = None, interpret: bool = None,
                          kv_quant=None):
    """Fused decode step: ``pos[b]`` tokens are in the pool and the current
    token's (k_new, v_new) — shape (B, KVH, Dh) — enters the softmax as an
    operand at position ``pos[b]`` without a pool read. Returns (B, H, Dh).

    The caller owns the pool scatter (the append itself); this kernel only
    *reads* positions < pos, so append and attention have no data dependence.
    ``kv_quant``/``interpret``: see :func:`paged_attention`.
    """
    B, H, Dh = q.shape
    KVH = k_pool.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, Dh)
    kn = k_new.reshape(B, KVH, 1, Dh).astype(k_pool.dtype)
    vn = v_new.reshape(B, KVH, 1, Dh).astype(v_pool.dtype)
    out = _paged_call(qg, k_pool, v_pool, kn, vn, block_tables, pos,
                      window=window, softcap=softcap, scale=scale,
                      fused_new=True, interpret=interpret, kv_quant=kv_quant)
    return out.reshape(B, H, Dh)


# ---------------------------------------------------------------------------
# chunked-prefill attention: a chunk of queries over partially-paged context
# ---------------------------------------------------------------------------
def _chunk_attn_kernel(tables_ref, pos0_ref, *refs,
                       block_size: int, max_nb: int, kv_heads: int,
                       chunk: int, groups: int, scale: float, window: int,
                       softcap: float, dv: int, fused_new: bool,
                       kv_quant: bool = False):
    """Flash-style causal chunk attention over block-table-paged KV.

    One batch-row program walks the sequence's block table with
    scalar-prefetched indirection (innermost grid dim), one whole pool
    block — every kv head — per step, and keeps an online-softmax
    accumulator per kv head for all ``chunk * groups`` query rows in VMEM
    scratch. Query row ``r`` is chunk token ``r // groups`` at absolute
    position ``pos0 + r // groups``.

    ``fused_new=True`` is the multi-token batched-append variant: the block
    walk reads only pool positions ``< pos0`` (the already-paged context at
    the block-table offset) and the chunk's own C-token KV enters the
    softmax as VMEM operands at the finish step under an intra-chunk causal
    mask — attention never re-reads the just-appended chunk from the HBM
    pool, so the caller's pool scatter has no data dependence on the walk.
    With ``fused_new=False`` the chunk's KV is read back from the pool
    (positions ``<= pos0 + i`` per query, as the gather reference does).

    ``dv < Dh`` is the MLA latent mode: scores use the full latent width
    (c_kv + rope) while the value accumulation keeps only the first ``dv``
    (kv_lora_rank) lanes — the weight-absorption identity's paged form.
    Masked probabilities are zeroed explicitly (not just -1e30 logits): a
    query row whose visible span misses an entire visited block must not
    pick up exp(0) garbage weight while its running max is still empty.

    ``kv_quant=True`` threads the quantized-KV sidecar refs (int8 pool
    mirrors + per-block scale/zero) and dequantizes visited blocks with the
    same branch-free FMA as the decode kernel — the chunk's own fused KV is
    always fp, so only the walked context needs the epilogue.
    """
    (q_ref, k_ref, v_ref, kn_ref, vn_ref, qk_ref, qv_ref, qp_ref, o_ref,
     m_scr, l_scr, acc_scr) = _split_refs(refs, kv_quant)
    b = pl.program_id(0)
    nb = pl.program_id(1)
    CG = chunk * groups
    if kv_quant:
        ks, vs, kz, vz = (qp_ref[0, i, nb] for i in range(4))

    @pl.when(nb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos0 = pos0_ref[b]
    base = nb * block_size
    # frontier: fused variant reads only pre-chunk context from the pool;
    # the pool-read variant also covers the chunk's own scattered KV.
    frontier = pos0 if fused_new else pos0 + chunk
    valid = base < frontier
    if window > 0:
        # skippable when even the block's last position falls below the
        # window of the chunk's FIRST query (pos0) — the one whose window
        # reaches furthest back; later queries only see higher positions.
        valid &= (base + block_size - 1) > (pos0 - window)

    @pl.when(valid)
    def _step():
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (CG, block_size), 1)
        qi = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (CG, block_size), 0) // groups
        msk = kpos < pos0 if fused_new else kpos <= qi
        if window > 0:
            msk &= kpos > qi - window
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)         # (CG, Dh)
            k = k_ref[0, :, h, :].astype(jnp.float32)   # (bs, Dh)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if kv_quant:
                k = k + qk_ref[0, :, h, :].astype(jnp.float32) * ks + kz
                v = v + qv_ref[0, :, h, :].astype(jnp.float32) * vs + vz
            v = v[:, :dv]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(msk, s, -1e30)
            m_prev = m_scr[h]                          # (CG, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(nb == max_nb - 1)
    def _finish():
        if fused_new:
            # fold the chunk's own KV in: key j visible to query i iff
            # j <= i (both at pos0 + ·), then the sliding window.
            qi = jax.lax.broadcasted_iota(jnp.int32, (CG, chunk), 0) // groups
            kj = jax.lax.broadcasted_iota(jnp.int32, (CG, chunk), 1)
            msk = kj <= qi
            if window > 0:
                msk &= kj > qi - window
        for h in range(kv_heads):
            if fused_new:
                q = q_ref[0, h].astype(jnp.float32)         # (CG, Dh)
                kn = kn_ref[0, h].astype(jnp.float32)       # (C, Dh)
                vn = vn_ref[0, h].astype(jnp.float32)[:, :dv]
                s = jnp.dot(q, kn.T, preferred_element_type=jnp.float32) \
                    * scale
                if softcap:
                    s = jnp.tanh(s / softcap) * softcap     # (CG, C)
                s = jnp.where(msk, s, -1e30)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
                m_scr[h] = m_new
                acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                    p, vn, preferred_element_type=jnp.float32)
            o_ref[0, h] = (acc_scr[h] /
                           jnp.maximum(l_scr[h], 1e-30)).astype(o_ref.dtype)


def _chunk_call(qg, k_pool, v_pool, kn, vn, block_tables, pos0, *, chunk,
                groups, scale, window, softcap, dv, fused_new, interpret,
                kv_quant=None):
    B, KVH, CG, Dh = qg.shape
    bs = k_pool.shape[1]
    max_nb = block_tables.shape[1]

    return _walk_call(
        functools.partial(_chunk_attn_kernel, block_size=bs, max_nb=max_nb,
                          kv_heads=KVH, chunk=chunk, groups=groups,
                          scale=scale, window=window, softcap=softcap, dv=dv,
                          fused_new=fused_new),
        grid=(B, max_nb), prefetch=(block_tables, pos0),
        in_specs=[_row_spec((KVH, CG, Dh)), _pool_spec(bs, KVH, Dh),
                  _pool_spec(bs, KVH, Dh), _row_spec((KVH,) + kn.shape[2:]),
                  _row_spec((KVH,) + vn.shape[2:])],
        operands=[qg, k_pool, v_pool, kn, vn], kv_quant=kv_quant,
        out_shape=jax.ShapeDtypeStruct((B, KVH, CG, dv), qg.dtype),
        scratch_shapes=[pltpu.VMEM((KVH, CG, 1), jnp.float32),
                        pltpu.VMEM((KVH, CG, 1), jnp.float32),
                        pltpu.VMEM((KVH, CG, dv), jnp.float32)],
        interpret=interpret, name="paged_chunk_attention",
        vmem_limit_bytes=48 * 2**20)


def _chunk_io(q, k_pool):
    """(B, C, H, Dh) queries → (B, KVH, C*G, Dh) kernel layout + dims."""
    B, C, H, Dh = q.shape
    KVH = k_pool.shape[2]
    G = H // KVH
    qg = q.reshape(B, C, KVH, G, Dh).transpose(0, 2, 1, 3, 4)
    return qg.reshape(B, KVH, C * G, Dh), (B, C, H, KVH, G, Dh)


def _pos0_vec(pos0, B):
    return jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1), (B,))


def paged_chunk_attention(q, k_pool, v_pool, block_tables, pos0, *,
                          window: int = 0, softcap: float = 0.0,
                          scale: float = None, dv: int = None,
                          interpret: bool = None, kv_quant=None):
    """Pool-read chunk block walk: q (B, C, H, Dh), chunk KV already
    scattered into the pool at the block-table offset. Query i at absolute
    position ``pos0 + i`` sees every pool position ``<= pos0 + i``
    (pool garbage beyond the chunk frontier is causally masked), matching
    :func:`paged_chunk_gather_attention` exactly. Returns (B, C, H, dv).

    ``scale`` overrides the default ``Dh ** -0.5``; ``dv`` < Dh enables the
    MLA latent mode (values = first ``dv`` lanes of the latent pool).
    ``kv_quant``/``interpret``: see :func:`paged_attention`.
    """
    qg, (B, C, H, KVH, G, Dh) = _chunk_io(q, k_pool)
    dv = dv or Dh
    scale = Dh ** -0.5 if scale is None else scale
    zero = jnp.zeros((B, KVH, 1, Dh), k_pool.dtype)
    out = _chunk_call(qg, k_pool, v_pool, zero, zero, block_tables,
                      _pos0_vec(pos0, B), chunk=C, groups=G, scale=scale,
                      window=window, softcap=softcap, dv=dv, fused_new=False,
                      interpret=interpret, kv_quant=kv_quant)
    return out.reshape(B, KVH, C, G, dv).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, dv)


def paged_chunk_attention_fused(q, k_new, v_new, k_pool, v_pool,
                                block_tables, pos0, *, window: int = 0,
                                softcap: float = 0.0, scale: float = None,
                                dv: int = None, interpret: bool = None,
                                kv_quant=None):
    """Multi-token batched-append chunk walk: the block walk covers only the
    already-paged context (< ``pos0``) and the chunk's own (k_new, v_new) —
    shape (B, C, KVH, Dh) — enter the online softmax as VMEM operands under
    an intra-chunk causal mask, the C-token generalization of the decode
    kernel's fused single-token append. The caller still owns the pool
    scatter of the chunk's KV (for later chunks/decode); this kernel never
    reads pool positions ``>= pos0``, so scatter and walk are independent.
    Returns (B, C, H, dv)."""
    qg, (B, C, H, KVH, G, Dh) = _chunk_io(q, k_pool)
    dv = dv or Dh
    scale = Dh ** -0.5 if scale is None else scale
    kn = k_new.transpose(0, 2, 1, 3).astype(k_pool.dtype)   # (B, KVH, C, Dh)
    vn = v_new.transpose(0, 2, 1, 3).astype(v_pool.dtype)
    out = _chunk_call(qg, k_pool, v_pool, kn, vn, block_tables,
                      _pos0_vec(pos0, B), chunk=C, groups=G, scale=scale,
                      window=window, softcap=softcap, dv=dv, fused_new=True,
                      interpret=interpret, kv_quant=kv_quant)
    return out.reshape(B, KVH, C, G, dv).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, dv)


def _dequant_pools(k_pool, v_pool, kv_quant):
    """Explicit-dequant reference path: materialize fp pools with the same
    branch-free FMA the kernels apply per visited block (fp slot +
    q * scale + zero — exact for fp blocks because their params are 0)."""
    qk, qv, ks, vs, kz, vz = kv_quant
    exp = (slice(None),) + (None,) * (k_pool.ndim - 1)
    k = k_pool.astype(jnp.float32) + qk.astype(jnp.float32) * ks[exp] + kz[exp]
    expv = (slice(None),) + (None,) * (v_pool.ndim - 1)
    v = v_pool.astype(jnp.float32) + qv.astype(jnp.float32) * vs[expv] + vz[expv]
    return k, v


def paged_chunk_gather_attention(q, k_pool, v_pool, block_tables, pos0, *,
                                 window: int = 0, softcap: float = 0.0,
                                 scale: float = None, dv: int = None,
                                 kv_quant=None):
    """Causal chunk attention against paged KV (gather path / parity oracle).

    q: (B, C, H, Dh) — C consecutive queries at absolute positions
    ``pos0 .. pos0 + C - 1``; the pool already holds the chunk's own KV
    (appended by the caller at the block-table offset), so query i of the
    chunk sees every pool position ``<= pos0 + i`` through the causal mask
    — garbage beyond the chunk frontier sits at positions ``> pos0 + C - 1``
    and is always masked. Cost is linear in ``block_tables.shape[1]``, which
    the engine buckets to the power of two covering the chunk's end, so
    prefill HBM traffic follows the *paged* context. The Pallas block walk
    above is the TPU fast path; this gather is the numerically-pinned
    reference it must match (and the ``xla`` dispatch-mode fallback).

    ``scale``/``dv`` mirror the kernel's MLA latent mode: explicit softmax
    scale and value truncation to the first ``dv`` lanes. ``kv_quant``
    takes the same sidecar bundle as the Pallas path but dequantizes
    *explicitly* (whole-pool FMA before the gather) — the numerics oracle
    the in-kernel epilogue is parity-tested against.
    """
    from repro.models.layers import naive_attention
    if kv_quant is not None:
        k_pool, v_pool = _dequant_pools(k_pool, v_pool, kv_quant)
    B = q.shape[0]
    nb, bs = block_tables.shape[1], k_pool.shape[1]
    gk = k_pool[block_tables].reshape(B, nb * bs, *k_pool.shape[2:])
    gv = v_pool[block_tables].reshape(B, nb * bs, *v_pool.shape[2:])
    if dv is not None:
        gv = gv[..., :dv]
    return naive_attention(q, gk, gv, causal=True, q_offset=pos0,
                           window=window, softcap=softcap, scale=scale)


# ---------------------------------------------------------------------------
# jnp fallback (CPU/GPU): gather over the *given* table width
# ---------------------------------------------------------------------------
def paged_gather_attention(q, k_pool, v_pool, block_tables, pos, *,
                           window: int = 0, softcap: float = 0.0,
                           scale: float = None, kv_quant=None):
    """Decode attention via gather + dense masked softmax (non-TPU path).

    Contract: the pool already holds ``pos[b] + 1`` tokens for row b (the
    current token was appended at position ``pos[b]`` before the call). Cost
    is linear in ``block_tables.shape[1]`` — the engine truncates tables to
    the power-of-two bucket of the live max, so HBM/memory traffic follows
    the *live* context, not ``max_blocks_per_seq``. ``kv_quant`` applies
    the explicit-dequant FMA over the pools before gathering (oracle path).
    """
    # lazy import: qlinear -> kernels.ops -> this module at import time.
    from repro.models.layers import naive_attention
    if kv_quant is not None:
        k_pool, v_pool = _dequant_pools(k_pool, v_pool, kv_quant)
    B = q.shape[0]
    nb, bs = block_tables.shape[1], k_pool.shape[1]
    gk = k_pool[block_tables].reshape(B, nb * bs, *k_pool.shape[2:])
    gv = v_pool[block_tables].reshape(B, nb * bs, *v_pool.shape[2:])
    out = naive_attention(q[:, None], gk, gv, causal=True, q_offset=pos,
                          window=window, softcap=softcap, scale=scale)
    return out[:, 0]
