"""Plain float32 reference of a dense decoder, replaying what the timed path
served.

Nothing here imports the program. The reference follows the published
architecture (pre-norm blocks, RoPE, grouped-query attention, SwiGLU MLP,
tied or untied head) in ``jax.numpy`` at ``highest`` matmul precision, on
the benchmark's own weights. It computes each request's positions in the
order and under the precision the configuration states for the step that
served them:

- a step at swap level ``k`` runs the first ``k`` layers with int4 weights
  (every matrix of at least 2^14 entries): asymmetric round-to-nearest per
  group of 128 along the input dimension
  (``scale = (max - min) / 15``, ``zero = round(-min / scale)``),
  quantized here from the same bf16 weights;
- a KV block the tier held in int8 is read back as ``q * scale + zero``
  with one affine pair per (layer, block): ``zero`` the mid-range,
  ``scale`` the half-range over 127, values rounded to the nearest step.

The control (``precision="control"``) is the same computation one step
lower than the configuration states, in float8 e4m3 where it states bf16:
every weight matrix (one scale per output column), every matmul input and
stored K/V (one scale per row, per token and head), and int8 KV blocks at
int4.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W

MAT = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# a swap quantizes the layer's matrices of at least this many entries; the
# rest (biases, norms, any small matrix) stay bf16
INT4_MIN_SIZE = 1 << 14
F8_MAX = 448.0


def _pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# precision steps
# ---------------------------------------------------------------------------
def int4_groupwise(w, group: int = 128):
    """(K, N) weight -> its int4 asymmetric RTN value, per group along K."""
    k, n = w.shape
    g = w.astype(jnp.float32).reshape(k // group, group, n)
    lo, hi = g.min(axis=1), g.max(axis=1)
    scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
    zero = jnp.clip(jnp.round(-lo / scale), 0, 15)
    q = jnp.clip(jnp.round(g / scale[:, None] + zero[:, None]), 0, 15)
    return ((q - zero[:, None]) * scale[:, None]).reshape(k, n)


def kv_affine(x, qmax: int = 127):
    """Round-trip (..., blocks, bs, KVH, Dh) through the per-block affine
    integer code: one (scale, zero) per block."""
    red = (-3, -2, -1)
    mx, mn = x.max(axis=red, keepdims=True), x.min(axis=red, keepdims=True)
    zero = (mx + mn) * 0.5
    scale = jnp.maximum((mx - mn) / (2.0 * qmax), 1e-8)
    return jnp.clip(jnp.round((x - zero) / scale), -qmax, qmax) * scale + zero


def _pow2_scale(amax):
    return jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax / F8_MAX, 1e-30))))


def round_e4m3(x):
    """Round f32 ``x`` (already scaled into +-448) to the nearest float8 e4m3
    value: 3 mantissa bits, normals down to 2^-6, subnormal steps of 2^-9.
    Written out in arithmetic, so no compiler can drop a round trip through
    the 8-bit type."""
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -F8_MAX, F8_MAX)


def f8_columns(w):
    """(K, N) -> rounded to float8 e4m3 with one power-of-two scale per
    output column (so the result is exact in bf16)."""
    wf = w.astype(jnp.float32)
    s = _pow2_scale(jnp.abs(wf).max(axis=0, keepdims=True))
    return (round_e4m3(wf / s) * s).astype(w.dtype)


def f8_rows(x):
    """(..., Dh) -> rounded to float8 e4m3 with one scale per last-axis row."""
    s = _pow2_scale(jnp.abs(x).max(axis=-1, keepdims=True))
    return round_e4m3(x / s) * s


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
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


@functools.partial(jax.jit, static_argnames=("arch", "control"),
                   donate_argnums=(2, 3))
def _layer(lw, x, kst, vst, qmask, start, lo, hi, *, arch, control):
    """One block for queries at positions ``start .. start + S - 1`` over
    stored keys ``kst``/``vst`` (T, KVH, Dh); only rows ``lo <= i < hi``
    are new and written to the store (the others are recomputed context).
    Writes the block's K/V into the store and returns (x, kst, vst)."""
    norm, eps, theta, H, KVH, Dh, bs = arch
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
    # blocks the tier held in int8 read back through the affine code
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
    return x, kst, vst


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _read(x, final_scale, head, tokens, *, arch, control):
    """Per row of ``x``: the best logit, its token, and the logit of
    ``tokens``."""
    norm, eps = arch[0], arch[1]
    h = _norm(norm, eps, x, None if final_scale is None
              else final_scale.astype(jnp.float32))
    if control:
        h = f8_rows(h)
    logits = h @ head.astype(jnp.float32)
    at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
    return logits.max(-1), jnp.argmax(logits, -1), at


class Reference:
    """The reference for one configuration and one set of weights."""

    def __init__(self, cfg: dict, w: Dict[str, jax.Array],
                 precision: str = "exact", store_len: int = 256):
        assert precision in ("exact", "control")
        self.cfg = cfg
        self.control = precision == "control"
        self.store_len = store_len
        z = W.dims(cfg)
        self.z = z
        arch = cfg["architecture"]
        eps = cfg["rms_norm_eps" if arch["norm"] == "rmsnorm"
                  else "layer_norm_eps"]
        self.arch = (arch["norm"], float(eps),
                     float(cfg["rope_theta"]), z["H"], z["KVH"], z["Dh"],
                     cfg["serving"]["kv_block_size"])
        self.group = cfg["serving"]["swap_group"]
        self.w = w
        self._int4: Dict[int, dict] = {}
        self._fp: Dict[int, dict] = {}
        embed = w["embed"]
        head = w["lm_head"] if "lm_head" in w else embed.T
        self.embed = embed
        self.head = jax.jit(f8_columns)(head) if self.control else head
        self.final = w.get("final_norm")

    # -- weights of one layer --------------------------------------------
    def _raw(self, i: int) -> dict:
        return {k: v[i] for k, v in self.w.items()
                if k not in ("embed", "lm_head", "final_norm")}

    def layer(self, i: int, int4: bool) -> dict:
        """Layer ``i``'s weights at int4 or at the stated precision (bf16,
        sliced on each use; float8 for the control, kept)."""
        if not int4 and not self.control:
            return self._raw(i)
        cache = self._int4 if int4 else self._fp
        if i not in cache:
            lw = self._raw(i)
            if int4:
                q = jax.jit(functools.partial(int4_groupwise,
                                              group=self.group))
                lw.update({k: q(lw[k]) for k in MAT
                           if lw[k].size >= INT4_MIN_SIZE})
            else:
                lw.update({k: jax.jit(f8_columns)(lw[k]) for k in MAT})
            cache[i] = lw
        return cache[i]

    # -- one request -------------------------------------------------------
    def replay(self, tokens: Sequence[int], segments, want_rows,
               want_tokens=None):
        """Run ``segments`` -- (level, start, end, int8 blocks) in order, a
        ``None`` segment dropping the KV -- over ``tokens``.

        Returns a dict position -> (best logit, best token, logit of
        ``want_tokens[position]``) for ``want_rows``."""
        z = self.z
        L, KVH, Dh = z["L"], z["KVH"], z["Dh"]
        bs = self.arch[-1]
        n = len(tokens)
        # one store length and one query block for every request, so the
        # reference compiles its programs once
        T = self.store_len if n + 1 <= self.store_len else _pow2(n + 1, 256)
        S = min(max(16, min(512, (1 << 23) // T)), T)
        toks = np.zeros((T + S,), np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        want = set(want_rows)
        out = {}
        store = None
        for seg in segments:
            if seg is None:
                store = None
                continue
            level, a, b, qblocks = seg
            if store is None:
                store = [(jnp.zeros((T, KVH, Dh), jnp.float32),
                          jnp.zeros((T, KVH, Dh), jnp.float32))
                         for _ in range(L)]
            qmask = np.zeros((T // bs,), bool)
            for j in qblocks:
                qmask[j] = True
            qmask = jnp.asarray(qmask)
            s = a
            while s < b:
                m = min(b - s, S)
                # the query block covers [s_pad, s_pad + S); rows before s
                # (when shifted back to fit the store) are recomputed, not
                # rewritten
                s_pad = min(s, T - S)
                off = s - s_pad
                x = self.embed[jnp.asarray(toks[s_pad:s_pad + S])].astype(
                    jnp.float32)
                for i in range(L):
                    kst, vst = store[i]
                    x, kst, vst = _layer(
                        self.layer(i, i < level), x, kst, vst, qmask,
                        jnp.int32(s_pad), jnp.int32(off), jnp.int32(off + m),
                        arch=self.arch, control=self.control)
                    store[i] = (kst, vst)
                rows = [p for p in range(s, s + m) if p in want]
                if rows:
                    tgt = np.zeros((S,), np.int32)
                    for p in rows:
                        tgt[p - s_pad] = (want_tokens or {}).get(p, 0)
                    best, arg, at = (np.asarray(v) for v in _read(
                        x, self.final, self.head, jnp.asarray(tgt),
                        arch=self.arch, control=self.control))
                    for p in rows:
                        k = p - s_pad
                        out[p] = (float(best[k]), int(arg[k]), float(at[k]))
                s += m
        return out
