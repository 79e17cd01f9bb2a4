"""Plain float32 reference of the served model, replaying what the timed path
served.

Nothing here imports the program. The model's layers are its architecture
module's (``cfg["arch"]``, see :mod:`bench.lib.arch`), written from the
published architecture in ``jax.numpy`` at ``highest`` matmul precision,
on the benchmark's own weights. The reference computes each request's
positions in the order and under the precision the configuration states
for the step that served them:

- a step at swap level ``k`` runs the first ``k`` layers with int4 weights
  (every weight matrix of at least 2^14 entries): asymmetric
  round-to-nearest per group of ``swap_group`` along the input dimension
  (``scale = (max - min) / 15``, ``zero = round(-min / scale)``),
  quantized here from the same bf16 weights;
- a KV block the tier held in int8 is read back as ``q * scale + zero``
  with one affine pair per (layer, block): ``zero`` the mid-range,
  ``scale`` the half-range over 127, values rounded to the nearest step.

The control (``precision="control"``) is the same computation one step
lower than the configuration states: float8 e4m3 where it states bf16
(every weight matrix with one scale per output column, every matmul input
and stored K/V with one scale per row), int4 with one scale and zero per
output column where it states int4 groups, and int8 KV blocks at int4.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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
    """(..., K, N) weight -> its int4 asymmetric RTN value, per group of
    ``group`` along K."""
    *lead, k, n = w.shape
    g = w.astype(jnp.float32).reshape(*lead, k // group, group, n)
    lo, hi = g.min(axis=-2), g.max(axis=-2)
    scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
    zero = jnp.clip(jnp.round(-lo / scale), 0, 15)
    s, z = scale[..., None, :], zero[..., None, :]
    q = jnp.clip(jnp.round(g / s + z), 0, 15)
    return ((q - z) * s).reshape(*lead, k, n)


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
    """(..., K, N) -> rounded to float8 e4m3 with one power-of-two scale per
    output column (so the result is exact in bf16)."""
    wf = w.astype(jnp.float32)
    s = _pow2_scale(jnp.abs(wf).max(axis=-2, keepdims=True))
    return (round_e4m3(wf / s) * s).astype(w.dtype)


def f8_rows(x):
    """(..., Dh) -> rounded to float8 e4m3 with one scale per last-axis row."""
    s = _pow2_scale(jnp.abs(x).max(axis=-1, keepdims=True))
    return round_e4m3(x / s) * s


# ---------------------------------------------------------------------------
class Reference:
    """The reference for one configuration and one set of weights."""

    def __init__(self, cfg: dict, w: Dict[str, jax.Array],
                 precision: str = "exact", store_len: int = 256):
        assert precision in ("exact", "control")
        self.arch = cfg["arch"]
        self.control = precision == "control"
        self.store_len = store_len
        self.static = self.arch.ref_static(cfg)
        self.n_layers = self.arch.n_layers(cfg)
        self.block_size = cfg["serving"]["kv_block_size"]
        self.group = cfg["serving"]["swap_group"]
        self.w = w
        self._int4: Dict[int, dict] = {}
        self._fp: Dict[int, dict] = {}
        self.embed, head, self.final = self.arch.ref_globals(w)
        self.head = jax.jit(f8_columns)(head) if self.control else head

    # -- weights of one layer --------------------------------------------
    def layer(self, i: int, int4: bool) -> dict:
        """Layer ``i``'s weights at int4 or at the stated precision (bf16,
        sliced on each use; float8 for the control, kept). The control's
        int4 has one scale and zero per output column, not per group."""
        if not int4 and not self.control:
            return self.arch.layer_weights(self.w, i)
        cache = self._int4 if int4 else self._fp
        if i not in cache:
            lw = self.arch.layer_weights(self.w, i)
            mats = self.arch.matrices(lw)
            if int4:
                def q(a):
                    group = a.shape[-2] if self.control else self.group
                    return jax.jit(functools.partial(
                        int4_groupwise, group=group))(a)
                lw.update({k: q(lw[k]) for k in mats
                           if lw[k].size >= INT4_MIN_SIZE})
            else:
                lw.update({k: jax.jit(f8_columns)(lw[k]) for k in mats})
            cache[i] = lw
        return cache[i]

    # -- one request -------------------------------------------------------
    def replay(self, tokens: Sequence[int], segments, want_rows,
               want_tokens=None):
        """Run ``segments`` -- (level, start, end, int8 blocks) in order, a
        ``None`` segment dropping the KV -- over ``tokens``.

        Returns a dict position -> (best logit, best token, logit of
        ``want_tokens[position]``) for ``want_rows``."""
        arch, static = self.arch, self.static
        bs = self.block_size
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
                store = [arch.ref_store(static, T)
                         for _ in range(self.n_layers)]
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
                for i in range(self.n_layers):
                    x, store[i] = arch.ref_layer(
                        self.layer(i, i < level), x, store[i], qmask,
                        jnp.int32(s_pad), jnp.int32(off), jnp.int32(off + m),
                        static=static, control=self.control)
                rows = [p for p in range(s, s + m) if p in want]
                if rows:
                    tgt = np.zeros((S,), np.int32)
                    for p in rows:
                        tgt[p - s_pad] = (want_tokens or {}).get(p, 0)
                    best, arg, at = (np.asarray(v) for v in arch.ref_read(
                        x, self.final, self.head, jnp.asarray(tgt),
                        static=static, control=self.control))
                    for p in rows:
                        k = p - s_pad
                        out[p] = (float(best[k]), int(arg[k]), float(at[k]))
                s += m
        return out
