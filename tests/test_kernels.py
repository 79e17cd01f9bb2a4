"""Pallas kernel validation vs pure-jnp oracles (interpret mode).

Per the assignment: shape/dtype sweeps with assert_allclose against ref.py.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, hst

from repro.kernels import ops, ref
from repro.kernels.wna16_gemm import wna16_gemm
from repro.quant import qlinear, quantize_tensor


@contextlib.contextmanager
def quant_kernel_mode(mode):
    prev = ops.set_quant_kernel_mode(mode)
    try:
        yield
    finally:
        ops.set_quant_kernel_mode(prev)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("M,K,N,G", [
    (1, 256, 128, 64),        # decode (tiny M)
    (8, 256, 128, 128),
    (33, 512, 256, 128),      # M not multiple of block
    (128, 1024, 512, 128),
    (16, 128, 384, 32),       # small K = single k-block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wna16_gemm_sweep(bits, M, K, N, G, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(M * K + bits))
    x = jax.random.normal(k1, (M, K), dtype=jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (K, N)) * 0.05
    qt = quantize_tensor(w, bits=bits, group=G)
    with quant_kernel_mode("pallas_interpret"):
        out = ops.wna16_matmul(x.astype(jnp.float32), qt)
    want = ref.wna16_gemm_ref(x.astype(jnp.float32), qt.packed, qt.scales,
                              qt.zeros, bits=bits, group=qt.group, K=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the off-TPU XLA packed-dequant fallback must agree too
    with quant_kernel_mode("xla"):
        out2 = ops.wna16_matmul(x.astype(jnp.float32), qt)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("K,N,G", [
    (768, 128, 384),          # group divides K but not the default bk=512
    (640, 128, 160),          # ... and not any power-of-two shrink of it
    (768, 256, 192),
])
def test_wna16_gemm_group_not_dividing_default_bk(bits, K, N, G):
    """Regression: the kernel must reslice the K block to a group multiple.

    The seed halved ``group`` until it divided bk, silently misindexing the
    scales/zeros built at the caller's group size (K=512-with-group-384-style
    shapes gave wrong results without any shape error)."""
    M = 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(K + G + bits))
    x = jax.random.normal(k1, (M, K))
    w = jax.random.normal(k2, (K, N)) * 0.05
    qt = quantize_tensor(w, bits=bits, group=G)
    assert qt.group == G                  # shape really uses the odd group
    out = wna16_gemm(x, qt.packed, qt.scales, qt.zeros, bits=bits, group=G,
                     interpret=True)
    want = ref.wna16_gemm_ref(x, qt.packed, qt.scales, qt.zeros, bits=bits,
                              group=G, K=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("awq", [False, True])
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("M,K,N,G,dtype", [
    (1, 256, 128, 128, jnp.float32),      # decode skinny
    (5, 256, 96, 64, jnp.float32),        # N not a lane multiple
    (8, 384, 192, 96, jnp.float32),       # non-pow2 everything
    (16, 256, 128, 128, jnp.bfloat16),    # low-precision activations
])
def test_wna16_fused_epilogue_parity(bits, awq, bias, M, K, N, G, dtype):
    """Fused path (inv_act + bias + out-dtype cast in the kernel epilogue)
    vs the jnp dequant path, across bits x group x AWQ x bias x shapes."""
    ks = jax.random.split(jax.random.PRNGKey(M + K + N + bits), 4)
    x = jax.random.normal(ks[0], (M, K), dtype=jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    s = (jnp.exp(jax.random.normal(ks[2], (K,)) * 0.3) if awq else None)
    b = (jax.random.normal(ks[3], (N,)).astype(dtype) if bias else None)
    qt = quantize_tensor(w, bits=bits, group=G, act_scale=s)
    want = qlinear.matmul(x, qt, bias=b)                 # jnp dequant path
    with quant_kernel_mode("pallas_interpret"):
        out = qlinear.matmul(x, qt.with_use_kernel(), bias=b)
    assert out.dtype == want.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_wna16_moe_expert_matmul_fused_parity():
    """Stacked-expert QTensor matmul: fused per-expert GEMMs == dequant
    einsum (the MoE hot path under ``use_quant_kernel``)."""
    from repro.models.moe import _expert_matmul
    from repro.quant import quantize_tree
    E, C, D, F = 3, 4, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    xg = jax.random.normal(ks[0], (E, C, D))
    w = jax.random.normal(ks[1], (E, D, F)) * 0.05
    qt = quantize_tree({"w": w}, bits=4, group=64)["w"]
    want = _expert_matmul(xg, qt)
    with quant_kernel_mode("pallas_interpret"):
        out = _expert_matmul(xg, qt.with_use_kernel())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("blocks", [(8, 128, 512), (128, 128, 128)])
def test_wna16_gemm_block_shapes(blocks):
    bm, bn, bk = blocks
    M, K, N, G = 64, 1024, 256, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (M, K))
    w = jax.random.normal(k2, (K, N)) * 0.05
    qt = quantize_tensor(w, bits=4, group=G)
    out = wna16_gemm(x, qt.packed, qt.scales, qt.zeros, bits=4, group=G,
                     bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.wna16_gemm_ref(x, qt.packed, qt.scales, qt.zeros, bits=4,
                              group=G, K=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("B,H,KVH,Dh,nblocks,bs,maxnb", [
    (2, 8, 2, 64, 16, 16, 4),
    (3, 4, 4, 32, 8, 8, 3),
    (1, 16, 1, 128, 32, 16, 8),   # MQA, long table
    (4, 4, 2, 64, 8, 32, 2),
])
def test_paged_attention_sweep(B, H, KVH, Dh, nblocks, bs, maxnb):
    ks = jax.random.split(jax.random.PRNGKey(B * H + Dh), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = jax.random.randint(ks[3], (B, maxnb), 0, nblocks)
    lens = jax.random.randint(ks[4], (B,), 1, maxnb * bs + 1)
    out = ops.paged_attention(q, kp, vp, tables, lens)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@given(seed=hst.integers(0, 2**16), bs=hst.sampled_from([8, 16]),
       maxnb=hst.integers(1, 6))
@settings(max_examples=15, deadline=None)
def test_paged_attention_table_permutation_invariance(seed, bs, maxnb):
    """Property: physical block placement must not matter — permuting the
    pool and remapping tables gives identical output (KVResizer invariant)."""
    rng = np.random.default_rng(seed)
    B, H, KVH, Dh, nblocks = 2, 4, 2, 32, 12
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = rng.integers(0, nblocks, size=(B, maxnb)).astype(np.int32)
    lens = rng.integers(1, maxnb * bs + 1, size=(B,)).astype(np.int32)
    out1 = ref.paged_attention_ref(q, kp, vp, jnp.array(tables),
                                   jnp.array(lens))
    perm = rng.permutation(nblocks)
    inv = np.argsort(perm)
    out2 = ref.paged_attention_ref(q, kp[inv], vp[inv],
                                   jnp.array(perm[tables]), jnp.array(lens))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [0, 5, 23])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_attention_window_softcap(window, softcap):
    """Extended-kernel parity: sliding window + logit softcap vs oracle."""
    B, H, KVH, Dh, nblocks, bs, maxnb = 3, 8, 2, 32, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(window * 7 + 1), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = jax.random.randint(ks[3], (B, maxnb), 0, nblocks)
    lens = jax.random.randint(ks[4], (B,), 1, maxnb * bs + 1)
    spec = ops.AttentionSpec(window=window, softcap=softcap)
    out = ops.paged_attention(q, kp, vp, tables, lens, spec)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens,
                                   window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ops_window_softcap_kwargs_are_hard_errors():
    """The pre-AttentionSpec ``window=``/``softcap=`` kwargs are fully
    removed from kernels.ops — passing a non-default value is a TypeError
    naming the replacement, not a silently-ignored knob."""
    B, H, KVH, Dh, nblocks, bs, maxnb = 1, 4, 2, 32, 4, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = jnp.zeros((B, maxnb), jnp.int32)
    lens = jnp.array([4], jnp.int32)
    with pytest.raises(TypeError, match="AttentionSpec"):
        ops.paged_attention(q, kp, vp, tables, lens, window=5)
    with pytest.raises(TypeError, match="AttentionSpec"):
        ops.paged_decode_attention(q, kp[0, 0], vp[0, 0], kp, vp, tables,
                                   lens - 1, softcap=20.0)
    with pytest.raises(TypeError, match="AttentionSpec"):
        ops.paged_prefill_attention(q[:, None], kp, vp, tables, 0, window=3)


def _fused_case(seed, B, H, KVH, Dh, bs, maxnb):
    """Random decode-step case honouring the engine's block-ownership
    contract: live table entries are globally distinct (the append must not
    alias another row's context)."""
    nblocks = B * maxnb + 1
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = jnp.array(1 + rng.permutation(B * maxnb).reshape(B, maxnb),
                       jnp.int32)
    pos = jnp.array(rng.integers(0, maxnb * bs, size=B), jnp.int32)  # ragged
    kn = jax.random.normal(ks[3], (B, KVH, Dh))
    vn = jax.random.normal(ks[4], (B, KVH, Dh))
    return q, kp, vp, tables, pos, kn, vn


@pytest.mark.parametrize("B,H,KVH,Dh,bs,maxnb", [
    (2, 8, 2, 64, 16, 4),     # GQA G=4
    (3, 4, 4, 32, 8, 3),      # MHA G=1
    (1, 16, 1, 128, 16, 6),   # MQA G=16
])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (7, 0.0), (0, 25.0),
                                            (11, 25.0)])
def test_paged_attention_fused_decode(B, H, KVH, Dh, bs, maxnb, window,
                                      softcap):
    """Fused single-token append: Pallas-interpret AND the jnp gather
    fallback must both match the oracle across GQA group sizes, sliding
    window, softcap, and ragged per-row context lengths."""
    from repro.kernels import paged_attention as pa
    q, kp, vp, tables, pos, kn, vn = _fused_case(
        B * H + Dh + window, B, H, KVH, Dh, bs, maxnb)
    want = ref.paged_attention_ref(q, kp, vp, tables, pos, window=window,
                                   softcap=softcap, k_new=kn, v_new=vn)
    out = pa.paged_attention_fused(q, kn, vn, kp, vp, tables, pos,
                                   window=window, softcap=softcap,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # jnp fallback contract: pool already holds the appended token
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    kp1 = kp.at[blk, pos % bs].set(kn)
    vp1 = vp.at[blk, pos % bs].set(vn)
    out2 = pa.paged_gather_attention(q, kp1, vp1, tables, pos, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_bucketed_tables():
    """ops.paged_decode_attention must be invariant to truncating the block
    table to any width that still covers the live context (the engine's
    bucketed-gather optimization)."""
    B, H, KVH, Dh, bs, maxnb = 2, 8, 4, 32, 8, 8
    q, kp, vp, tables, _, kn, vn = _fused_case(5, B, H, KVH, Dh, bs, maxnb)
    pos = jnp.array([11, 4], jnp.int32)          # live blocks: 2 and 1
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    kp1 = kp.at[blk, pos % bs].set(kn)
    vp1 = vp.at[blk, pos % bs].set(vn)
    full = ops.paged_decode_attention(q, kn, vn, kp1, vp1, tables, pos)
    for nb_t in (2, 4):                          # pow2 buckets >= live max
        out = ops.paged_decode_attention(q, kn, vn, kp1, vp1,
                                         tables[:, :nb_t], pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# chunk-prefill block-walk kernel (pool-read + fused batched-append variants)
# ---------------------------------------------------------------------------
def _chunk_case(seed, B, C, H, KVH, Dh, bs, maxnb, pos0):
    """Random chunk-prefill case: globally-distinct live blocks (ownership
    contract), chunk KV scattered into the pool at the table offset, plus
    the raw (k_new, v_new) operands for the fused batched-append variant."""
    nblocks = B * maxnb + 1
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, C, H, Dh))
    kp = jax.random.normal(ks[1], (nblocks, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nblocks, bs, KVH, Dh))
    tables = jnp.array(1 + rng.permutation(B * maxnb).reshape(B, maxnb),
                       jnp.int32)
    kn = jax.random.normal(ks[3], (B, C, KVH, Dh))
    vn = jax.random.normal(ks[4], (B, C, KVH, Dh))
    assert pos0 + C <= maxnb * bs
    idx = pos0 + np.arange(C)
    blk = np.take_along_axis(np.asarray(tables), idx[None, :] // bs, axis=1)
    kp = kp.at[blk, idx[None, :] % bs].set(kn)
    vp = vp.at[blk, idx[None, :] % bs].set(vn)
    return q, kp, vp, tables, kn, vn


@pytest.mark.parametrize("B,C,H,KVH,Dh,bs,maxnb,pos0", [
    (2, 8, 8, 2, 64, 16, 4, 16),    # GQA G=4, block-aligned offset
    (1, 16, 4, 4, 32, 8, 6, 13),    # MHA, unaligned nonzero offset
    (2, 4, 16, 1, 64, 8, 4, 0),     # MQA, chunk at the very start
])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (9, 0.0), (0, 25.0),
                                            (9, 25.0)])
def test_paged_chunk_attention_sweep(B, C, H, KVH, Dh, bs, maxnb, pos0,
                                     window, softcap):
    """Both chunk-kernel variants vs the gather reference across GQA group
    sizes, window, softcap, and (un)aligned block-table offsets. The fused
    batched-append variant must also be independent of the pool scatter —
    it may never read pool positions >= pos0."""
    from repro.kernels import paged_attention as pa
    q, kp, vp, tables, kn, vn = _chunk_case(C * H + pos0 + window, B, C, H,
                                            KVH, Dh, bs, maxnb, pos0)
    want = pa.paged_chunk_gather_attention(q, kp, vp, tables, pos0,
                                           window=window, softcap=softcap)
    out_pool = pa.paged_chunk_attention(q, kp, vp, tables, pos0,
                                        window=window, softcap=softcap,
                                        interpret=True)
    out_fused = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, tables,
                                               pos0, window=window,
                                               softcap=softcap,
                                               interpret=True)
    # scrub the chunk span from the pool with garbage: fused output must not
    # change (large-finite, not NaN — a partially-owned block is still read
    # whole and masked, and 0 * NaN would poison the masked accumulate)
    idx = pos0 + np.arange(C)
    blk = np.take_along_axis(np.asarray(tables), idx[None, :] // bs, axis=1)
    kp0 = kp.at[blk, idx[None, :] % bs].set(1e8)
    vp0 = vp.at[blk, idx[None, :] % bs].set(1e8)
    out_noscatter = pa.paged_chunk_attention_fused(
        q, kn, vn, kp0, vp0, tables, pos0, window=window, softcap=softcap,
        interpret=True)
    for out in (out_pool, out_fused, out_noscatter):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_paged_chunk_attention_pow2_chunk_buckets(C):
    """The fused batched-append variant across the engine's pow2 chunk
    buckets, each at a nonzero block-table offset (context from earlier
    chunks already paged)."""
    from repro.kernels import paged_attention as pa
    B, H, KVH, Dh, bs = 2, 8, 2, 32, 16
    maxnb = (64 + C) // bs + 1
    q, kp, vp, tables, kn, vn = _chunk_case(C, B, C, H, KVH, Dh, bs, maxnb,
                                            pos0=64)
    want = pa.paged_chunk_gather_attention(q, kp, vp, tables, 64)
    out = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, tables, 64,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_chunk_attention_mla_latent():
    """MLA latent mode: KVH=1 latent pool of width r+rope, scores over the
    full latent width, values = first ``dv`` lanes, explicit softmax scale
    — kernel vs the gather reference with the same (scale, dv)."""
    from repro.kernels import paged_attention as pa
    B, C, H, r, rope, bs, maxnb, pos0 = 2, 8, 8, 32, 16, 8, 6, 21
    Dh = r + rope
    scale = 48.0 ** -0.5                 # qk head-dim scale, != Dh**-0.5
    q, kp, vp, tables, kn, vn = _chunk_case(3, B, C, H, 1, Dh, bs, maxnb,
                                            pos0)
    want = pa.paged_chunk_gather_attention(q, kp, kp, tables, pos0,
                                           scale=scale, dv=r)
    out_pool = pa.paged_chunk_attention(q, kp, kp, tables, pos0, scale=scale,
                                        dv=r, interpret=True)
    out_fused = pa.paged_chunk_attention_fused(q, kn, kn, kp, kp, tables,
                                               pos0, scale=scale, dv=r,
                                               interpret=True)
    assert out_pool.shape == (B, C, H, r)
    for out in (out_pool, out_fused):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_paged_chunk_attention_table_width_invariance():
    """Like decode: truncating the table to any pow2 width covering
    ``pos0 + C`` must not change the chunk output (bucketed-table
    contract)."""
    from repro.kernels import paged_attention as pa
    B, C, H, KVH, Dh, bs, maxnb = 2, 8, 8, 2, 32, 8, 8
    pos0 = 9                             # live span 9..16 → 3 blocks
    q, kp, vp, tables, kn, vn = _chunk_case(11, B, C, H, KVH, Dh, bs, maxnb,
                                            pos0)
    full = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, tables, pos0,
                                          interpret=True)
    for nb_t in (4, 8):
        out = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp,
                                             tables[:, :nb_t], pos0,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)


def test_ops_paged_prefill_attention_dispatch():
    """The unified ops wrapper: xla mode == gather reference bit-for-bit;
    pallas_interpret mode (fused when k_new/v_new given, pool-read
    otherwise) matches it numerically; AttentionSpec carries window/softcap."""
    from repro.kernels import paged_attention as pa
    B, C, H, KVH, Dh, bs, maxnb, pos0 = 2, 8, 8, 2, 32, 8, 5, 11
    spec = ops.AttentionSpec(window=13, softcap=20.0)
    q, kp, vp, tables, kn, vn = _chunk_case(17, B, C, H, KVH, Dh, bs, maxnb,
                                            pos0)
    want = pa.paged_chunk_gather_attention(q, kp, vp, tables, pos0,
                                           window=13, softcap=20.0)
    with quant_kernel_mode("xla"):
        out_x = ops.paged_prefill_attention(q, kp, vp, tables, pos0, spec,
                                            k_new=kn, v_new=vn)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(want))
    with quant_kernel_mode("pallas_interpret"):
        out_f = ops.paged_prefill_attention(q, kp, vp, tables, pos0, spec,
                                            k_new=kn, v_new=vn)
        out_p = ops.paged_prefill_attention(q, kp, vp, tables, pos0, spec)
    for out in (out_f, out_p):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# stacked (L, blocks, bs, KVH, Dh) pools read in place at a layer index
# ---------------------------------------------------------------------------
def _stack_at(pool, layer, seed, n_layers=3):
    """An ``n_layers`` stacked pool holding ``pool`` at ``layer`` and noise
    in every other layer, so a walk that reads the wrong layer shows."""
    noise = jax.random.normal(jax.random.PRNGKey(seed),
                              (n_layers,) + pool.shape, pool.dtype)
    return noise.at[layer].set(pool)


@pytest.mark.parametrize("layer", [1, 2])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (7, 25.0)])
@pytest.mark.parametrize("walk", ["decode", "chunk"])
def test_stacked_pool_walk_reads_layer_in_place(walk, window, softcap,
                                                layer):
    """The fused decode and chunk walks over a stacked 3-layer pool at
    ``layer`` (through ``ops`` under ``pallas_interpret``) equal the 4-D
    call on ``pool[layer]`` bit for bit, and the ``xla`` gather through
    ``ops`` at the same layer."""
    from repro.kernels import paged_attention as pa
    spec = ops.AttentionSpec(window=window, softcap=softcap)
    if walk == "decode":
        B, H, KVH, Dh, bs, maxnb = 2, 8, 2, 64, 16, 4
        q, kp, vp, tables, pos, kn, vn = _fused_case(layer, B, H, KVH, Dh,
                                                     bs, maxnb)
        # the pool holds the appended token (the xla gather's contract)
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        kp = kp.at[blk, pos % bs].set(kn)
        vp = vp.at[blk, pos % bs].set(vn)
        kps, vps = _stack_at(kp, layer, 100), _stack_at(vp, layer, 101)
        want = pa.paged_attention_fused(q, kn, vn, kp, vp, tables, pos,
                                        window=window, softcap=softcap,
                                        interpret=True)

        def call(kpool, vpool):
            return ops.paged_decode_attention(q, kn, vn, kpool, vpool, tables,
                                              pos, spec, layer=layer)
    else:
        B, C, H, KVH, Dh, bs, maxnb, pos0 = 2, 8, 8, 2, 64, 16, 4, 13
        q, kp, vp, tables, kn, vn = _chunk_case(layer, B, C, H, KVH, Dh, bs,
                                                maxnb, pos0)
        kps, vps = _stack_at(kp, layer, 100), _stack_at(vp, layer, 101)
        want = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, tables,
                                              pos0, window=window,
                                              softcap=softcap, interpret=True)

        def call(kpool, vpool):
            return ops.paged_prefill_attention(q, kpool, vpool, tables, pos0,
                                               spec, layer=layer, k_new=kn,
                                               v_new=vn)
    with quant_kernel_mode("pallas_interpret"):
        out = call(kps, vps)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    with quant_kernel_mode("xla"):
        out_x = call(kps, vps)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [1, 2])
def test_stacked_pool_mla_latent_chunk(layer):
    """MLA latent mode over a stacked latent pool (passed as both K and V)
    equals its 4-D call on ``pool[layer]``, for both chunk variants."""
    from repro.kernels import paged_attention as pa
    B, C, H, r, rope, bs, maxnb, pos0 = 2, 8, 8, 32, 16, 8, 6, 21
    kw = dict(scale=48.0 ** -0.5, dv=r, interpret=True)
    q, kp, _, tables, kn, _ = _chunk_case(3, B, C, H, 1, r + rope, bs, maxnb,
                                          pos0)
    kps = _stack_at(kp, layer, 102)
    pairs = [
        (pa.paged_chunk_attention(q, kps, kps, tables, pos0, layer=layer,
                                  **kw),
         pa.paged_chunk_attention(q, kp, kp, tables, pos0, **kw)),
        (pa.paged_chunk_attention_fused(q, kn, kn, kps, kps, tables, pos0,
                                        layer=layer, **kw),
         pa.paged_chunk_attention_fused(q, kn, kn, kp, kp, tables, pos0,
                                        **kw)),
    ]
    for got, want in pairs:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_matches_dense_attention():
    """Paged oracle == dense causal attention when the table is contiguous."""
    from repro.models.layers import naive_attention
    B, H, KVH, Dh, bs, maxnb = 2, 8, 4, 32, 16, 4
    T = bs * maxnb
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    k = jax.random.normal(ks[0], (B, T, KVH, Dh))
    v = jax.random.normal(ks[1], (B, T, KVH, Dh))
    q = jax.random.normal(ks[2], (B, 1, H, Dh))
    lens = jnp.array([T, T // 2], jnp.int32)
    # pack into pool: block b of seq s at pool id s*maxnb+b
    kp = k.reshape(B * maxnb, bs, KVH, Dh)
    vp = v.reshape(B * maxnb, bs, KVH, Dh)
    tables = jnp.arange(B * maxnb, dtype=jnp.int32).reshape(B, maxnb)
    out_p = ops.paged_attention(q[:, 0], kp, vp, tables, lens)
    out_d = naive_attention(q, k, v, causal=False, kv_len=lens)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d[:, 0]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quantized-KV in-kernel dequant (the quantize-cold relief tier's read path)
# ---------------------------------------------------------------------------
def _quantize_pool_subset(kp, vp, qids, bits=8):
    """Quantize pool blocks ``qids`` the way PagedKVPool.quantize_blocks
    does: codec codes in an int8 twin pool, one (scale, zero) pair per
    block in the sidecar, fp slots zeroed. Unlisted blocks keep zeroed
    sidecar rows, so the kernels' branch-free FMA (fp + q*scale + zero)
    reproduces fp blocks exactly and dequantizes quantized ones."""
    from repro.core import kv_codec

    def one(pool):
        arr = np.asarray(pool)
        q, s, z = kv_codec.affine_quantize_np(arr[None], bits=bits)
        nblocks = arr.shape[0]
        qs = np.zeros(arr.shape, np.int8)
        sc = np.zeros((nblocks,), np.float32)
        zp = np.zeros((nblocks,), np.float32)
        fp = arr.copy()
        for b in qids:
            qs[b], sc[b], zp[b] = q[0, b], s[0, b], z[0, b]
            fp[b] = 0.0
        return (jnp.asarray(fp), jnp.asarray(qs), jnp.asarray(sc),
                jnp.asarray(zp))

    k_fp, qk, ks_, kz = one(kp)
    v_fp, qv, vs_, vz = one(vp)
    return k_fp, v_fp, (qk, qv, ks_, vs_, kz, vz)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("mode", ["pallas_interpret", "xla"])
def test_paged_decode_attention_kv_quant_parity(bits, mode):
    """Decode over a mixed fp/quantized pool: the in-kernel dequant walk
    (pallas leg) and the explicit-dequant gather (xla leg) must both match
    the oracle built by dequantizing the pools up front — for int8 and the
    int4 knob, in both REPRO_QUANT_KERNEL matrix legs."""
    from repro.kernels import paged_attention as pa
    B, H, KVH, Dh, bs, maxnb = 2, 8, 2, 32, 8, 4
    q, kp, vp, tables, pos, kn, vn = _fused_case(
        41 + bits, B, H, KVH, Dh, bs, maxnb)
    # append the current token (contract of the gather path)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    kp = kp.at[blk, pos % bs].set(kn)
    vp = vp.at[blk, pos % bs].set(vn)
    # quantize every block except the append blocks (the engine's
    # keep-tail rule: the block being written never quantizes)
    qids = sorted(set(range(kp.shape[0])) - set(np.asarray(blk).tolist()))
    assert qids, "case degenerated: nothing left to quantize"
    k_fp, v_fp, kvq = _quantize_pool_subset(kp, vp, qids, bits=bits)
    dq_k, dq_v = pa._dequant_pools(k_fp, v_fp, kvq)
    want = pa.paged_gather_attention(q, dq_k, dq_v, tables, pos)
    with quant_kernel_mode(mode):
        out = ops.paged_decode_attention(q, kn, vn, k_fp, v_fp, tables,
                                         pos, kv_quant=kvq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # sanity: quantization really altered the visited pool content
    assert not np.array_equal(np.asarray(dq_k), np.asarray(kp))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("mode", ["pallas_interpret", "xla"])
def test_paged_chunk_attention_kv_quant_parity(bits, mode):
    """Chunk-prefill over a mixed fp/quantized pool: fused batched-append
    and pool-read kernel variants (pallas leg) and the explicit-dequant
    gather (xla leg) all match the dequantized-up-front oracle."""
    from repro.kernels import paged_attention as pa
    B, C, H, KVH, Dh, bs, maxnb, pos0 = 2, 8, 8, 2, 32, 8, 4, 16
    q, kp, vp, tables, kn, vn = _chunk_case(
        53 + bits, B, C, H, KVH, Dh, bs, maxnb, pos0)
    # quantize only fully-paged context blocks below the chunk span
    idx = pos0 + np.arange(C)
    span = np.take_along_axis(np.asarray(tables), idx[None, :] // bs, axis=1)
    qids = sorted(set(range(kp.shape[0])) - set(span.ravel().tolist()))
    assert qids
    k_fp, v_fp, kvq = _quantize_pool_subset(kp, vp, qids, bits=bits)
    dq_k, dq_v = pa._dequant_pools(k_fp, v_fp, kvq)
    want = pa.paged_chunk_gather_attention(q, dq_k, dq_v, tables, pos0)
    with quant_kernel_mode(mode):
        out_fused = ops.paged_prefill_attention(q, k_fp, v_fp, tables, pos0,
                                                k_new=kn, v_new=vn,
                                                kv_quant=kvq)
        out_pool = ops.paged_prefill_attention(q, k_fp, v_fp, tables, pos0,
                                               kv_quant=kvq)
    for out in (out_fused, out_pool):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_kv_quant_noop_bundle_is_exact():
    """An all-fp pool with a zeroed sidecar must be bit-identical to the
    no-bundle path: the FMA epilogue is exact when scale == zero == 0."""
    from repro.kernels import paged_attention as pa
    B, H, KVH, Dh, bs, maxnb = 2, 4, 2, 32, 8, 3
    q, kp, vp, tables, pos, kn, vn = _fused_case(7, B, H, KVH, Dh, bs, maxnb)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    kp = kp.at[blk, pos % bs].set(kn)
    vp = vp.at[blk, pos % bs].set(vn)
    k_fp, v_fp, kvq = _quantize_pool_subset(kp, vp, qids=[], bits=8)
    base = pa.paged_gather_attention(q, k_fp, v_fp, tables, pos)
    quant = pa.paged_gather_attention(q, k_fp, v_fp, tables, pos,
                                      kv_quant=kvq)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(quant))
