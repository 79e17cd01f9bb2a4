"""The dense decoder block: pre-norm attention over grouped-query heads (MHA
where the kv heads equal the heads) with RoPE, a SwiGLU MLP, optional QKV
biases, RMSNorm or non-parametric LayerNorm, a tied or untied head.

The benchmark's layout stacks each per-layer tensor on a leading layer axis.
The interface is described in :mod:`bench.lib.arch`.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench.lib.reference import f8_rows, kv_affine

GLOBAL = ("embed", "lm_head", "final_norm")
MAT = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "D": d, "H": h,
            "KVH": cfg["num_key_value_heads"],
            "Dh": cfg.get("head_dim") or d // h,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def _rms(cfg: dict) -> bool:
    return cfg["architecture"]["norm"] == "rmsnorm"


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def shapes(cfg: dict) -> Dict[str, tuple]:
    z = dims(cfg)
    L, D, H, KVH, Dh, F, V = (z[k] for k in ("L", "D", "H", "KVH", "Dh",
                                             "F", "V"))
    out = {"embed": (V, D),
           "wq": (L, D, H * Dh), "wk": (L, D, KVH * Dh),
           "wv": (L, D, KVH * Dh), "wo": (L, H * Dh, D),
           "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    if _rms(cfg):
        out.update(ln1=(L, D), ln2=(L, D), final_norm=(D,))
    if cfg["architecture"]["qkv_bias"]:
        out.update(bq=(L, H * Dh), bk=(L, KVH * Dh), bv=(L, KVH * Dh))
    return out


def init(name: str, shape: tuple, key, dtype):
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                                   # (.., fan_in, fan_out) matrices
        x = shape[-2] ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    z = dims(cfg)
    arch = cfg["architecture"]
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=z["L"], d_model=z["D"],
        n_heads=z["H"], n_kv_heads=z["KVH"], d_ff=z["F"], vocab=z["V"],
        head_dim=z["Dh"], norm=arch["norm"], act=cfg["hidden_act"],
        gated_mlp=True, qkv_bias=arch["qkv_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["serving"]["dtype"], source=cfg["source"])


def program_params(cfg: dict, w: Dict[str, jax.Array]) -> dict:
    """One segment of stacked dense layers (the same device arrays)."""
    rms = _rms(cfg)
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    for b in ("bq", "bk", "bv"):
        if b in w:
            attn[b] = w[b]
    layer = {"ln1": {"scale": w["ln1"]} if rms else {}, "attn": attn,
             "ln2": {"scale": w["ln2"]} if rms else {},
             "mlp": {k: w[k] for k in ("w_up", "w_down", "w_gate")}}
    params = {"embed": w["embed"],
              "final_norm": {"scale": w["final_norm"]} if rms else {},
              "segments": [(layer,)]}
    if "lm_head" in w:
        params["lm_head"] = w["lm_head"]
    return params


# ---------------------------------------------------------------------------
# bytes held
# ---------------------------------------------------------------------------
def layer_bytes(cfg: dict) -> list:
    """Per layer: bf16 weights; with its matrices int4 (packed nibbles plus
    f32 scale and zero per group of ``swap_group`` along K, the rest bf16);
    its share of one KV block (K and V, bf16)."""
    z = dims(cfg)
    group = cfg["serving"]["swap_group"]
    fp = q = 0
    for name, shape in shapes(cfg).items():
        if name in GLOBAL:
            continue
        n = 1
        for s in shape[1:]:
            n *= s
        fp += 2 * n
        if len(shape) == 3 and n >= 1 << 14:
            k, m = shape[1], shape[2]
            q += k // 2 * m + 2 * (k // group) * m * 4
        else:
            q += 2 * n
    kv = cfg["serving"]["kv_block_size"] * 2 * z["KVH"] * z["Dh"] * 2
    return [{"fp": fp, "q": q, "kv": kv}] * z["L"]


def misc_bytes(cfg: dict) -> int:
    z = dims(cfg)
    misc = 2 * z["V"] * z["D"] * (1 if cfg["tie_word_embeddings"] else 2)
    if _rms(cfg):
        misc += 2 * z["D"]
    return misc


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def n_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def layer_weights(w: dict, i: int) -> dict:
    return {k: v[i] for k, v in w.items() if k not in GLOBAL}


def matrices(lw: dict) -> tuple:
    return MAT


def ref_globals(w: dict):
    embed = w["embed"]
    return embed, (w["lm_head"] if "lm_head" in w else embed.T), \
        w.get("final_norm")


def ref_static(cfg: dict) -> tuple:
    z = dims(cfg)
    arch = cfg["architecture"]
    eps = cfg["rms_norm_eps" if arch["norm"] == "rmsnorm"
              else "layer_norm_eps"]
    return (arch["norm"], float(eps), float(cfg["rope_theta"]), z["H"],
            z["KVH"], z["Dh"], cfg["serving"]["kv_block_size"])


def ref_store(static: tuple, T: int):
    """K and V of one layer, (T, KVH, Dh) each."""
    KVH, Dh = static[4], static[5]
    return (jnp.zeros((T, KVH, Dh), jnp.float32),
            jnp.zeros((T, KVH, Dh), jnp.float32))


def _norm(kind: str, eps: float, x, scale=None):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta: float):
    """Rotate-half RoPE. x: (S, heads, Dh); pos: (S,)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                           / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("static", "control"),
                   donate_argnums=(2,))
def ref_layer(lw, x, store, qmask, start, lo, hi, *, static, control):
    """One block for queries at positions ``start .. start + S - 1`` over
    the stored keys and values ``store`` (each (T, KVH, Dh)); only rows
    ``lo <= i < hi`` are new and written to the store (the others are
    recomputed context). Blocks marked in ``qmask`` are read back through
    the int8 tier's affine code (int4 for the control)."""
    norm, eps, theta, H, KVH, Dh, bs = static
    kst, vst = store
    S, T = x.shape[0], kst.shape[0]
    f = lambda a: a.astype(jnp.float32)            # noqa: E731
    act = f8_rows if control else (lambda a: a)
    h = act(_norm(norm, eps, x, f(lw["ln1"]) if "ln1" in lw else None))
    pos = start + jnp.arange(S)
    q = h @ f(lw["wq"]) + (f(lw["bq"]) if "bq" in lw else 0.0)
    k = h @ f(lw["wk"]) + (f(lw["bk"]) if "bk" in lw else 0.0)
    v = h @ f(lw["wv"]) + (f(lw["bv"]) if "bv" in lw else 0.0)
    q = _rope(q.reshape(S, H, Dh), pos, theta)
    k = _rope(k.reshape(S, KVH, Dh), pos, theta)
    v = v.reshape(S, KVH, Dh)
    if control:
        k, v = f8_rows(k), f8_rows(v)
    r = jnp.arange(S)
    valid = ((r >= lo) & (r < hi))[:, None, None]
    old_k = jax.lax.dynamic_slice(kst, (start, 0, 0), (S, KVH, Dh))
    old_v = jax.lax.dynamic_slice(vst, (start, 0, 0), (S, KVH, Dh))
    kst = jax.lax.dynamic_update_slice(kst, jnp.where(valid, k, old_k),
                                       (start, 0, 0))
    vst = jax.lax.dynamic_update_slice(vst, jnp.where(valid, v, old_v),
                                       (start, 0, 0))
    qmax = 7 if control else 127
    kb = kst.reshape(T // bs, bs, KVH, Dh)
    vb = vst.reshape(T // bs, bs, KVH, Dh)
    m = qmask[:, None, None, None]
    kr = jnp.where(m, kv_affine(kb, qmax), kb).reshape(T, KVH, Dh)
    vr = jnp.where(m, kv_affine(vb, qmax), vb).reshape(T, KVH, Dh)
    g = H // KVH
    qg = q.reshape(S, KVH, g, Dh)
    s = jnp.einsum("skgd,tkd->kgst", qg, kr) * Dh ** -0.5
    causal = jnp.arange(T)[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, vr).reshape(S, H * Dh)
    x = x + act(o) @ f(lw["wo"])
    h2 = act(_norm(norm, eps, x, f(lw["ln2"]) if "ln2" in lw else None))
    a = h2 @ f(lw["w_gate"])
    x = x + act(jax.nn.silu(a) * (h2 @ f(lw["w_up"]))) @ f(lw["w_down"])
    return x, (kst, vst)


@functools.partial(jax.jit, static_argnames=("static", "control"))
def ref_read(x, final, head, tokens, *, static, control):
    norm, eps = static[0], static[1]
    h = _norm(norm, eps, x, None if final is None
              else final.astype(jnp.float32))
    if control:
        h = f8_rows(h)
    logits = h @ head.astype(jnp.float32)
    at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
    return logits.max(-1), jnp.argmax(logits, -1), at


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
def touched_params(cfg: dict) -> int:
    """Every layer's projections: a dense layer's token touches them all."""
    z = dims(cfg)
    D, H, KVH, Dh, F = (z[k] for k in ("D", "H", "KVH", "Dh", "F"))
    return z["L"] * (D * (H + 2 * KVH) * Dh + H * Dh * D + 3 * D * F)


def head_params(cfg: dict) -> int:
    z = dims(cfg)
    return z["D"] * z["V"]


def attn_flops(cfg: dict, n_query: int, ctx_before: int) -> int:
    """QK^T and PV: 4 flops per (query, key, head, dim)."""
    z = dims(cfg)
    pairs = n_query * ctx_before + n_query * (n_query + 1) // 2
    return 4 * z["L"] * z["H"] * z["Dh"] * pairs


def kv_read_bytes(cfg: dict, quantized: bool) -> int:
    """K and V of one block: bf16 values, or int8 payload plus the four f32
    scale/zero numbers of the block; all layers."""
    z = dims(cfg)
    n = cfg["serving"]["kv_block_size"] * z["KVH"] * z["Dh"]
    return z["L"] * (2 * n + 16 if quantized else 2 * 2 * n)


def decode_io_bytes(cfg: dict) -> int:
    """The query and the output, the new token's K and V, bf16."""
    z = dims(cfg)
    H, KVH, Dh = z["H"], z["KVH"], z["Dh"]
    return z["L"] * (2 * (2 * H * Dh) + 2 * (2 * KVH * Dh))


def chunk_io_bytes(cfg: dict) -> int:
    """Per prefill token: its q, k, v and output, bf16."""
    z = dims(cfg)
    H, KVH, Dh = z["H"], z["KVH"], z["Dh"]
    return z["L"] * 2 * (2 * H * Dh + 2 * KVH * Dh)
