"""Jitted model execution over the paged KV pool + mixed-precision layers.

This is the worker's data plane. Functions are jitted per
(layer-list pytree structure, pool shape, padded prompt bucket) — the bounded
recompile set that replaces CUDA kernel-precompilation (DESIGN.md §2):
swap levels are bucketed, pool sizes are bucketed, prompt lengths are padded
to buckets.

Supports the dense/GQA family (the paper's eval models), MLA (latent pool),
and SSM/hybrid (state slots) — MoE FFNs work in all of them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.engine.kv_cache import write_blocks
from repro.kernels import dispatch, ops
from repro.models import layers as L
from repro.models import lm
from repro.models import mamba as M
from repro.models import moe as MO
from repro.quant import qlinear


def pad_bucket(n: int, quantum: int = 64) -> int:
    """Round up to a small set of buckets (powers of two of `quantum`)."""
    b = quantum
    while b < n:
        b *= 2
    return b


def build_attention_specs(cfg: ModelConfig, kinds,
                          kv_quant: bool = False) -> tuple:
    """One :class:`~repro.kernels.ops.AttentionSpec` per layer, built once at
    :class:`ModelExec` construction and baked statically into the jitted
    steps — window, softcap, softmax scale, head layout, and (for MLA) the
    latent value width all live here instead of being threaded as kwargs
    through every attention call site. ``kv_quant=True`` marks the layers'
    pools as eligible to hold quantized blocks (GQA family only — the MLA
    latent pool keeps fp blocks)."""
    if cfg.mla is not None:
        m = cfg.mla
        spec = ops.AttentionSpec(
            scale=(m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5,
            q_heads=cfg.n_heads, kv_heads=1, latent_dv=m.kv_lora_rank)
        return tuple(spec for _ in kinds)
    return tuple(
        ops.AttentionSpec(window=lm.layer_window(cfg, i),
                          softcap=cfg.logit_softcap,
                          q_heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                          kv_quant=kv_quant)
        for i, _ in enumerate(kinds))


# ---------------------------------------------------------------------------
# Paged attention append + read (jnp path; the Pallas kernel is the TPU path)
# ---------------------------------------------------------------------------
def _append_kv(pool_k, pool_v, li, k_new, v_new, blk, off):
    """Write one new token's KV per slot into layer li of the pool.
    k_new: (slots, KVH, Dh); blk/off: (slots,) int32 (scratch 0 for idle)."""
    pk = pool_k.at[li, blk, off].set(k_new)
    pv = pool_v.at[li, blk, off].set(v_new)
    return pk, pv


def _gather_kv(pool, li, tables):
    """(slots, maxnb) tables → (slots, maxnb*bs, KVH, Dh)."""
    g = pool[li][tables]                       # (slots, maxnb, bs, KVH, Dh)
    s, nb, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(s, nb * bs, *g.shape[3:])


def _paged_gqa_decode(p, cfg, x, pool_k, pool_v, li, tables, pos, spec,
                      kvq=None):
    """x: (slots, 1, D); pos: (slots,) absolute position of the new token.

    The attention read goes through ``kernels/ops.paged_decode_attention``
    (Pallas block-walk on TPU; bucketed jnp gather elsewhere) — cost follows
    the caller-truncated width of ``tables``, not max_blocks_per_seq.
    ``spec`` is the layer's static :class:`~repro.kernels.ops.AttentionSpec`;
    ``kvq`` the layer's quantized-KV sidecar slice (or None when the pool
    holds no quantized blocks this step).
    """
    slots = x.shape[0]
    bs = pool_k.shape[2]
    q, k, v = L.gqa_project_qkv(p, cfg, x, pos[:, None])
    blk_idx = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    pool_k, pool_v = _append_kv(pool_k, pool_v, li, k[:, 0], v[:, 0],
                                blk_idx, pos % bs)
    out = ops.paged_decode_attention(
        q[:, 0], k[:, 0], v[:, 0], pool_k[li], pool_v[li], tables, pos, spec,
        kv_quant=kvq)
    y = qlinear.matmul(out.reshape(slots, 1, -1), p["wo"], bias=p.get("bo"))
    return y, pool_k, pool_v


def _paged_mla_decode(p, cfg, x, pool_k, li, tables, pos):
    """MLA with the latent pool (KVH=1, Dh=r+rope). Absorbed-weight scoring.

    Expects the decode-prepared attn params (``absorb_mla_decode_weights``):
    ``wk_abs``/``wv_abs`` replace ``w_ukv``, so the dequant + reshape of the
    absorbed projection happens once per swap level, not once per token
    inside the jitted step.
    """
    m = cfg.mla
    slots = x.shape[0]
    bs = pool_k.shape[2]
    q_nope, q_rope, c_kv_new, k_rope_new = L._mla_qkv(p, cfg, x, pos[:, None])
    latent_new = jnp.concatenate([c_kv_new[:, 0], k_rope_new[:, 0, 0]], -1)
    blk_idx = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    pool_k = pool_k.at[li, blk_idx, pos % bs, 0].set(latent_new)
    lat = _gather_kv(pool_k, li, tables)[..., 0, :]      # (slots, T, r+rope)
    c_kv, k_rope = jnp.split(lat, [m.kv_lora_rank], axis=-1)
    T = c_kv.shape[1]
    kv_len = pos + 1
    wk, wv = p["wk_abs"], p["wv_abs"]                    # (r, H, dk), (r, H, dv)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32), wk)
    s = (jnp.einsum("bshr,btr->bhst", q_abs, c_kv.astype(jnp.float32))
         + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                      k_rope.astype(jnp.float32)))
    s = s * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    msk = jnp.arange(T)[None, None, None, :] < kv_len[:, None, None, None]
    s = jnp.where(msk, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhst,btr->bshr", pr, c_kv.astype(jnp.float32))
    out = jnp.einsum("bshr,rhd->bshd", ctx_lat, wv).astype(x.dtype)
    y = qlinear.matmul(out.reshape(slots, 1, -1), p["wo"])
    return y, pool_k


# ---------------------------------------------------------------------------
# Decode step over the full stack
# ---------------------------------------------------------------------------
def paged_decode_step(cfg: ModelConfig, kinds, specs, misc, layer_params,
                      tokens, pos, pool_k, pool_v, tables, ssm_conv, ssm_ssm,
                      kvq=None):
    """tokens: (slots, 1); pos: (slots,) absolute index of the token being
    decoded (= context length *before* it, i.e. context_len - 1 once the
    token is counted in generated). RoPE position and KV append slot.
    ``kvq`` is the pool's quantized-KV sidecar bundle (L-leading arrays,
    sliced per layer here) or None while nothing is quantized.
    Returns (logits (slots, V), pool_k, pool_v, ssm_conv, ssm_ssm)."""
    x = jnp.take(misc["embed"], tokens, axis=0)
    ssm_li = 0
    for i, (kind, p) in enumerate(zip(kinds, layer_params)):
        spec = specs[i]
        kvq_i = None if kvq is None else tuple(a[i] for a in kvq)
        if kind == "mamba":
            h = L.apply_norm(cfg.norm, p["norm"], x)
            st = {"conv": ssm_conv[ssm_li], "ssm": ssm_ssm[ssm_li]}
            y, st = M.mamba_decode(p["mixer"], cfg, h, st)
            ssm_conv = ssm_conv.at[ssm_li].set(st["conv"])
            ssm_ssm = ssm_ssm.at[ssm_li].set(st["ssm"])
            ssm_li += 1
            x = x + y
            continue
        if kind == "hybrid":
            h = L.apply_norm(cfg.norm, p["ln1"], x)
            a, pool_k, pool_v = _paged_gqa_decode(
                p["attn"], cfg, h, pool_k, pool_v, i, tables, pos, spec,
                kvq_i)
            st = {"conv": ssm_conv[ssm_li], "ssm": ssm_ssm[ssm_li]}
            s, st = M.mamba_decode(p["ssm"], cfg, h, st)
            ssm_conv = ssm_conv.at[ssm_li].set(st["conv"])
            ssm_ssm = ssm_ssm.at[ssm_li].set(st["ssm"])
            ssm_li += 1
            mixed = 0.5 * (p["beta_a"] * L.apply_norm("rmsnorm", p["norm_a"], a)
                           + p["beta_s"] * L.apply_norm("rmsnorm", p["norm_s"], s))
            x = x + mixed.astype(x.dtype)
            h2 = L.apply_norm(cfg.norm, p["ln2"], x)
            x = x + L.mlp_apply(p["mlp"], cfg, h2)
            continue
        h = L.apply_norm(cfg.norm, p["ln1"], x)
        if cfg.mla is not None:
            attn_out, pool_k = _paged_mla_decode(p["attn"], cfg, h, pool_k,
                                                 i, tables, pos)
        else:
            attn_out, pool_k, pool_v = _paged_gqa_decode(
                p["attn"], cfg, h, pool_k, pool_v, i, tables, pos, spec,
                kvq_i)
        if cfg.parallel_block:
            x = x + attn_out + L.mlp_apply(p["mlp"], cfg, h)
            continue
        x = x + attn_out
        h2 = L.apply_norm(cfg.norm, p["ln2"], x)
        if kind in ("moe", "mla_moe"):
            y, _ = MO.moe_apply(p["moe"], cfg, h2, capacity_factor=-1.0)
            x = x + y
        else:
            x = x + L.mlp_apply(p["mlp"], cfg, h2)
    logits = lm.unembed(cfg, misc, x)
    return logits[:, 0], pool_k, pool_v, ssm_conv, ssm_ssm


def paged_prefill(cfg: ModelConfig, kinds, misc, layer_params, tokens,
                  pool_k, pool_v, block_ids, ssm_conv, ssm_ssm, slot):
    """Prefill ONE request (batch 1, padded length Sp = len(block_ids)*bs).

    tokens: (1, Sp); block_ids: (nb,) — scratch 0 where padded. Returns
    (full logits (Sp, V), pools, ssm states)."""
    layer_list = list(zip(kinds, layer_params))
    logits, payloads = lm.prefill_collect(cfg, misc, layer_list, tokens)
    bs = pool_k.shape[2]
    nb = block_ids.shape[0]
    Sp = tokens.shape[1]
    pad = nb * bs - Sp

    def _block_pad(x):                     # (Sp, ...) -> (nb, bs, ...)
        if pad > 0:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape(nb, bs, *x.shape[1:])

    ssm_li = 0
    for i, payload in enumerate(payloads):
        if "k" in payload and nb > 0:
            k = _block_pad(payload["k"][0])
            v = _block_pad(payload["v"][0])
            pool_k = write_blocks(pool_k, block_ids, k, layer=i)
            pool_v = write_blocks(pool_v, block_ids, v, layer=i)
        elif "latent" in payload and nb > 0:
            lat = _block_pad(payload["latent"][0])[:, :, None, :]
            pool_k = write_blocks(pool_k, block_ids, lat, layer=i)
        if "ssm_conv" in payload:
            ssm_conv = ssm_conv.at[ssm_li, slot].set(payload["ssm_conv"][0])
            ssm_ssm = ssm_ssm.at[ssm_li, slot].set(payload["ssm_ssm"][0])
            ssm_li += 1
    return logits[0], pool_k, pool_v, ssm_conv, ssm_ssm


def paged_prefill_batch(cfg: ModelConfig, kinds, misc, layer_params, tokens,
                        pool_k, pool_v, tables, lens):
    """Prefill up to P requests in ONE jitted call at a shared padded length.

    tokens: (P, Sp) with Sp = tables.shape[1] * block_size (a shared bucket);
    tables: (P, nb) physical block ids, scratch 0 where padded; lens: (P,)
    true prompt lengths. Rows are independent (causal masking + dropless MoE),
    so batching is bit-transparent per row. Attention/MLA families only —
    SSM/hybrid state is position-exact and keeps the per-request path.

    Returns (last-token logits (P, V), pool_k, pool_v)."""
    layer_list = list(zip(kinds, layer_params))
    logits, payloads = lm.prefill_collect(cfg, misc, layer_list, tokens)
    bs = pool_k.shape[2]
    P, Sp = tokens.shape
    nb = tables.shape[1]
    for i, payload in enumerate(payloads):
        if "k" in payload and nb > 0:
            ids = tables.reshape(-1)
            k = payload["k"].reshape(P * nb, bs, *payload["k"].shape[2:])
            v = payload["v"].reshape(P * nb, bs, *payload["v"].shape[2:])
            pool_k = write_blocks(pool_k, ids, k, layer=i)
            pool_v = write_blocks(pool_v, ids, v, layer=i)
        elif "latent" in payload and nb > 0:
            lat = payload["latent"].reshape(
                P * nb, bs, *payload["latent"].shape[2:])[:, :, None, :]
            pool_k = write_blocks(pool_k, tables.reshape(-1), lat, layer=i)
    last = logits[jnp.arange(P), lens - 1]
    return last, pool_k, pool_v


def _chunk_gqa_attention(p, cfg, x, positions, pool_k, pool_v, li, tables,
                         blk, off, pos0, spec, kvq=None):
    """Causal chunk attention against already-paged context (batch 1).

    x: (1, Cp, D) chunk activations at absolute positions ``positions``;
    the chunk's KV is scattered into layer ``li`` of the pool first (pad
    positions land in blocks the next chunk overwrites, or in scratch 0),
    then the chunk attends through ``ops.paged_prefill_attention``: under
    the Pallas modes that is the fused block-walk kernel — the chunk's own
    (k, v) ride along as VMEM operands (batched append) and the walk covers
    only the already-paged context ``< pos0`` — under ``xla`` the bucketed
    table gather, where position ``pos0 + i`` sees every pool token
    ``<= pos0 + i``. Both are bit-equal to whole-prompt prefill because
    per-token projections are row-independent and the pool round-trip is
    value-preserving *as long as the pool dtype holds the KV exactly* (the
    default float32 pool does, for bf16 or f32 activations; the kernel
    casts its VMEM chunk operands to the pool dtype so both paths see the
    same rounding). A lossy pool (fp8/bf16) makes chunk 2+ attend over
    rounded KV — the same divergence the pool-backed decode path already
    has vs dense."""
    B, Cp, _ = x.shape
    q, k, v = L.gqa_project_qkv(p, cfg, x, positions)
    pool_k = pool_k.at[li, blk, off].set(k[0].astype(pool_k.dtype))
    pool_v = pool_v.at[li, blk, off].set(v[0].astype(pool_v.dtype))
    out = ops.paged_prefill_attention(q, pool_k[li], pool_v[li],
                                      tables[None], pos0, spec,
                                      k_new=k, v_new=v, kv_quant=kvq)
    y = qlinear.matmul(out.reshape(B, Cp, -1), p["wo"], bias=p.get("bo"))
    return y, pool_k, pool_v


def _chunk_mla_attention(p, cfg, x, positions, pool_k, li, tables, blk, off,
                         pos0, spec):
    """MLA chunk attention over the latent pool (KVH=1, Dh=r+rope).

    Two numerics, mirroring decode: with absorbed decode params (``wk_abs``
    present — the Pallas dispatch modes) the chunk scores directly against
    the latent pool through the fused chunk kernel (``spec.latent_dv``
    keeps the first ``kv_lora_rank`` value lanes, ``spec.scale`` is the qk
    head-dim scale) and expands the latent context through ``wv_abs``
    afterwards; with raw params (``w_ukv`` — the xla fallback) the latent
    context is expanded to per-head K/V first, as whole-prompt prefill
    does. Both are the same attention by the weight-absorption identity."""
    m = cfg.mla
    B, Cp, _ = x.shape
    q_nope, q_rope, c_kv_new, k_rope_new = L._mla_qkv(p, cfg, x, positions)
    latent_new = jnp.concatenate([c_kv_new[0], k_rope_new[0, :, 0]], -1)
    pool_k = pool_k.at[li, blk, off, 0].set(latent_new.astype(pool_k.dtype))
    if "wk_abs" in p:
        q_abs = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                           p["wk_abs"])
        q_lat = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], -1)
        ctx_lat = ops.paged_prefill_attention(
            q_lat, pool_k[li], pool_k[li], tables[None], pos0, spec,
            k_new=latent_new[None, :, None, :],
            v_new=latent_new[None, :, None, :])
        out = jnp.einsum("bshr,rhd->bshd", ctx_lat.astype(jnp.float32),
                         p["wv_abs"]).astype(x.dtype)
        return qlinear.matmul(out.reshape(B, Cp, -1), p["wo"]), pool_k
    lat = _gather_kv(pool_k, li, tables[None])[..., 0, :]  # (1, T, r+rope)
    c_kv, k_rope = jnp.split(lat, [m.kv_lora_rank], axis=-1)
    k_nope, v = L._mla_expand_kv(p, cfg, c_kv.astype(x.dtype))
    T = c_kv.shape[1]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :].astype(x.dtype),
                                  (B, T, cfg.n_heads, m.qk_rope_head_dim))],
        axis=-1)
    out = L.naive_attention(q, k, v, causal=True, q_offset=pos0)
    y = qlinear.matmul(out.reshape(B, Cp, -1), p["wo"])
    return y, pool_k


def paged_prefill_chunk(cfg: ModelConfig, kinds, specs, misc, layer_params,
                        tokens, pos0, pool_k, pool_v, tables, kvq=None):
    """Prefill ONE chunk of ONE request against partially-paged context.

    tokens: (1, Cp) — the chunk, end-padded to a bucketed length; pos0:
    scalar int32 absolute position of tokens[0] (= the request's
    ``prefill_pos``); tables: (nb,) block table whose span ``nb * bs`` covers
    at least ``pos0 + Cp`` token positions (scratch 0 where the request owns
    fewer blocks). Each layer appends the chunk's KV into the pool and runs
    causal attention of the chunk against everything paged so far, so a long
    prompt streams through the pool chunk by chunk while decode batches keep
    stepping between chunks (Sarathi-style chunked prefill).

    Attention/MLA families only — SSM/hybrid recurrent state is
    position-exact and keeps the whole-prompt path. Returns
    (chunk logits (Cp, V), pool_k, pool_v)."""
    bs = pool_k.shape[2]
    Cp = tokens.shape[1]
    positions = pos0 + jnp.arange(Cp)[None, :]       # (1, Cp)
    abs_pos = positions[0]
    blk = tables[abs_pos // bs]                       # (Cp,)
    off = abs_pos % bs
    x = jnp.take(misc["embed"], tokens, axis=0)
    for i, (kind, p) in enumerate(zip(kinds, layer_params)):
        h = L.apply_norm(cfg.norm, p["ln1"], x)
        kvq_i = None if kvq is None else tuple(a[i] for a in kvq)
        if cfg.mla is not None:
            attn_out, pool_k = _chunk_mla_attention(
                p["attn"], cfg, h, positions, pool_k, i, tables, blk, off,
                pos0, specs[i])
        else:
            attn_out, pool_k, pool_v = _chunk_gqa_attention(
                p["attn"], cfg, h, positions, pool_k, pool_v, i, tables,
                blk, off, pos0, specs[i], kvq_i)
        if cfg.parallel_block:
            x = x + attn_out + L.mlp_apply(p["mlp"], cfg, h)
            continue
        x = x + attn_out
        h2 = L.apply_norm(cfg.norm, p["ln2"], x)
        if kind in ("moe", "mla_moe"):
            y, _ = MO.moe_apply(p["moe"], cfg, h2, capacity_factor=-1.0)
            x = x + y
        else:
            x = x + L.mlp_apply(p["mlp"], cfg, h2)
    logits = lm.unembed(cfg, misc, x)
    return logits[0], pool_k, pool_v


def absorb_mla_decode_weights(cfg: ModelConfig, layer_params):
    """Precompute the absorbed MLA projection for the decode path.

    ``w_ukv`` (possibly a QTensor) is dequantized + reshaped ONCE here —
    outside the jitted step — into ``wk_abs`` (r, H, dk) / ``wv_abs``
    (r, H, dv); the per-token decode previously redid that dequant every
    step. Cached per swap level by :class:`ModelExec`.
    """
    m = cfg.mla
    H = cfg.n_heads
    out = []
    for p in layer_params:
        attn = p.get("attn") if isinstance(p, dict) else None
        if attn is None or "w_ukv" not in attn:
            out.append(p)
            continue
        w = attn["w_ukv"]
        wd = (w.dequantize(jnp.float32) if qlinear.is_quantized(w)
              else w.astype(jnp.float32))
        wd = wd.reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
        attn = {k: v for k, v in attn.items() if k != "w_ukv"}
        attn["wk_abs"] = wd[..., :m.qk_nope_head_dim]
        attn["wv_abs"] = wd[..., m.qk_nope_head_dim:]
        out.append(dict(p, attn=attn))
    return tuple(out)


class ModelExec:
    """Owns the jit caches for prefill/decode at each (level, pool, bucket).

    Layer *kinds* never change with swapping, so they're baked statically;
    only the per-layer param pytrees (dense vs QTensor) vary by level — jit
    re-specializes per pytree structure, which is exactly the bounded
    per-level executable cache. For MLA archs the decode path additionally
    caches the absorbed ``w_ukv`` projection per layer list (i.e. per swap
    level — the actuator hands out one stable list per level)."""

    def __init__(self, cfg: ModelConfig, params, kinds, kv_quant=False):
        self.cfg = cfg
        self.kinds = tuple(kinds)
        self.misc = {k: v for k, v in params.items() if k != "segments"}
        self._absorb_cache: Dict[int, Tuple[Any, Any]] = {}
        # per-layer static attention config, bound into the partials (not a
        # traced arg) so donate_argnums keep pointing at the pools below
        self.specs = build_attention_specs(cfg, self.kinds, kv_quant)
        self._decode_jit = jax.jit(
            functools.partial(paged_decode_step, cfg, self.kinds, self.specs),
            donate_argnums=(4, 5, 7, 8))
        self._prefill_jit = jax.jit(
            functools.partial(paged_prefill, cfg, self.kinds),
            donate_argnums=(3, 4, 6, 7))
        self._prefill_batch_jit = jax.jit(
            functools.partial(paged_prefill_batch, cfg, self.kinds),
            donate_argnums=(3, 4))
        # chunked prefill specializes per (chunk bucket, table width bucket,
        # level pytree) — both dims power-of-two bucketed by the engine, so
        # the recompile set stays log-bounded like prompt/pool buckets.
        self._prefill_chunk_jit = jax.jit(
            functools.partial(paged_prefill_chunk, cfg, self.kinds,
                              self.specs),
            donate_argnums=(4, 5))

    def _decode_params(self, layer_list):
        """Per-layer decode params; MLA absorbed weights hoisted + cached."""
        lp = tuple(p for _, p in layer_list)
        if self.cfg.mla is None:
            return lp
        hit = self._absorb_cache.get(id(layer_list))
        if hit is None or hit[0] is not layer_list:
            # keep a reference to the source list so its id stays valid
            hit = (layer_list, absorb_mla_decode_weights(self.cfg, lp))
            self._absorb_cache[id(layer_list)] = hit
        return hit[1]

    def decode(self, layer_list, tokens, pos, pool_k, pool_v, tables,
               ssm_conv, ssm_ssm, kvq=None):
        lp = self._decode_params(layer_list)
        return self._decode_jit(self.misc, lp, tokens, pos,
                                pool_k, pool_v, tables, ssm_conv, ssm_ssm,
                                kvq)

    def prefill(self, layer_list, tokens, pool_k, pool_v, block_ids,
                ssm_conv, ssm_ssm, slot):
        lp = tuple(p for _, p in layer_list)
        return self._prefill_jit(self.misc, lp, tokens,
                                 pool_k, pool_v, block_ids, ssm_conv,
                                 ssm_ssm, slot)

    def prefill_batch(self, layer_list, tokens, pool_k, pool_v, tables, lens):
        lp = tuple(p for _, p in layer_list)
        return self._prefill_batch_jit(self.misc, lp, tokens,
                                       pool_k, pool_v, tables, lens)

    def prefill_chunk(self, layer_list, tokens, pos0, pool_k, pool_v, table,
                      kvq=None):
        # MLA under the Pallas modes scores against the latent pool with the
        # absorbed decode weights (same per-level cache as decode); the xla
        # fallback keeps the raw params + expanded-KV reference numerics.
        if self.cfg.mla is not None and dispatch.uses_pallas():
            lp = self._decode_params(layer_list)
        else:
            lp = tuple(p for _, p in layer_list)
        return self._prefill_chunk_jit(self.misc, lp, tokens, pos0,
                                       pool_k, pool_v, table, kvq)
