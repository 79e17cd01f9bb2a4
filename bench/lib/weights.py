"""Seeded random weights for a configuration.

The benchmark makes the weights, in the dtype they are served in, on the
device, in one jitted call from ``--seed``. Their names, shapes and init
rules are the architecture module's (``cfg["arch"]``, see
:mod:`bench.lib.arch`); the layout is the benchmark's own, which the
reference reads directly and the module hands to the program under test in
the program's layout.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(cfg: dict, seed: int, dtype=jnp.bfloat16, device=None
         ) -> Dict[str, jax.Array]:
    """All weights of ``cfg`` from ``seed``, made on ``device`` in one call.
    Each tensor's stream depends only on the seed and its name."""
    arch = cfg["arch"]
    shp = arch.shapes(cfg)

    def build(key):
        return {n: arch.init(n, s, jax.random.fold_in(
                    key, zlib.crc32(n.encode()) & 0x7FFFFFFF), dtype)
                for n, s in shp.items()}
    out_sh = (None if device is None
              else jax.sharding.SingleDeviceSharding(device))
    return jax.jit(build, out_shardings=out_sh)(base_key(seed))
