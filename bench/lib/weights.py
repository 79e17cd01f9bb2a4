"""Seeded random weights for a dense decoder configuration.

The benchmark makes the weights, in the dtype they are served in, on the
device, in one jitted call from ``--seed``. The layout here is the
benchmark's own (per-layer tensors stacked on a leading layer axis); the
reference reads it directly and :mod:`bench.lib.system` hands the same
arrays to the program under test in the program's layout.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "D": d, "H": h,
            "KVH": cfg["num_key_value_heads"],
            "Dh": cfg.get("head_dim") or d // h,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def shapes(cfg: dict) -> Dict[str, tuple]:
    """Name -> shape of every weight tensor, layers stacked first."""
    z = dims(cfg)
    L, D, H, KVH, Dh, F, V = (z[k] for k in ("L", "D", "H", "KVH", "Dh",
                                             "F", "V"))
    arch = cfg["architecture"]
    out = {"embed": (V, D),
           "wq": (L, D, H * Dh), "wk": (L, D, KVH * Dh),
           "wv": (L, D, KVH * Dh), "wo": (L, H * Dh, D),
           "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    if arch["norm"] == "rmsnorm":
        out.update(ln1=(L, D), ln2=(L, D), final_norm=(D,))
    if arch["qkv_bias"]:
        out.update(bq=(L, H * Dh), bk=(L, KVH * Dh), bv=(L, KVH * Dh))
    return out


def _init(name: str, shape: tuple, key, dtype):
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                                   # (.., fan_in, fan_out) matrices
        x = shape[-2] ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def base_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(cfg: dict, seed: int, dtype=jnp.bfloat16, device=None
         ) -> Dict[str, jax.Array]:
    """All weights of ``cfg`` from ``seed``, made on ``device`` in one call.
    Each tensor's stream depends only on the seed and its name."""
    shp = shapes(cfg)

    def build(key):
        return {n: _init(n, s, jax.random.fold_in(
                    key, zlib.crc32(n.encode()) & 0x7FFFFFFF), dtype)
                for n, s in shp.items()}
    out_sh = (None if device is None
              else jax.sharding.SingleDeviceSharding(device))
    return jax.jit(build, out_shardings=out_sh)(base_key(seed))
