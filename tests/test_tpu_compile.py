"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed beside JAX and compiles for
a topology that is described, not attached. Interpret mode cannot show a
block shape the chip's compiler refuses (the tiling rule on the last two
block dims, the VMEM limit), so these tests compile every kernel of the
serving main path at Qwen2-1.5B widths (12 query heads, 2 kv heads, head
dim 128, d_model 1536, d_ff 8960, bf16, 16-token KV blocks) and check that
the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is built inside a module fixture: describing it loads the TPU
library, which one process at a time may hold, so nothing here touches it
while the module is imported.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.archs import QWEN2_1P5B
from repro.engine import model_exec as me
from repro.kernels import dispatch
from repro.kernels import paged_attention as pa
from repro.kernels.wna16_gemm import wna16_gemm
from repro.models import lm

H, KVH, DH, BS = 12, 2, 128, 16          # Qwen2-1.5B attention
D_MODEL, D_FF, GROUP = 1536, 8960, 128   # Qwen2-1.5B MLP, AWQ group
SLOTS, MAX_NB = 32, 128                  # decode batch, table width
# pool capacities: a small pool, and 65536 blocks — above the ~35k that
# 95% of the default 16 GiB budget holds for this model even with no
# weights, and where a pool-wide SMEM sidecar row (4 f32 per block)
# exceeded the chip's 1 MiB of SMEM
CAPS = [1024, 65536]


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pool_shapes(kv_quant, cap):
    pool = ((cap, BS, KVH, DH), jnp.bfloat16)
    shapes = [pool, pool]
    if kv_quant:
        mirror = ((cap, BS, KVH, DH), jnp.int8)
        row = ((cap,), jnp.float32)
        shapes += [mirror, mirror, row, row, row, row]
    return shapes


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "kv_quant"])
def test_decode_kernel_compiles(one_chip, kv_quant, cap):
    def decode(q, kn, vn, tables, pos, kp, vp, *kvq):
        return pa.paged_attention_fused(q, kn, vn, kp, vp, tables, pos,
                                        interpret=False,
                                        kv_quant=kvq or None)
    shapes = [((SLOTS, H, DH), jnp.bfloat16), ((SLOTS, KVH, DH), jnp.bfloat16),
              ((SLOTS, KVH, DH), jnp.bfloat16), ((SLOTS, MAX_NB), jnp.int32),
              ((SLOTS,), jnp.int32)] + _pool_shapes(kv_quant, cap)
    _compile(decode, shapes, one_chip)


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "kv_quant"])
def test_chunk_prefill_kernel_compiles(one_chip, kv_quant, cap):
    """The fused batched-append chunk walk at the largest chunk bucket the
    engine forms under a 512-token step budget."""
    C = 512

    def chunk(q, kn, vn, table, pos0, kp, vp, *kvq):
        return pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, table, pos0,
                                              interpret=False,
                                              kv_quant=kvq or None)
    shapes = [((1, C, H, DH), jnp.bfloat16), ((1, C, KVH, DH), jnp.bfloat16),
              ((1, C, KVH, DH), jnp.bfloat16), ((1, MAX_NB), jnp.int32),
              ((), jnp.int32)] + _pool_shapes(kv_quant, cap)
    _compile(chunk, shapes, one_chip)


def _pool_updates():
    """name -> (pool update, its argument shapes, the arguments it
    donates) at Qwen2-1.5B widths over a 4096-block pool."""
    from repro.engine import kv_cache as kc
    L, cap, n = 28, 4096, 8
    pool = ((L, cap, BS, KVH, DH), jnp.bfloat16)
    mirror = ((L, cap, BS, KVH, DH), jnp.int8)
    row = ((L, cap), jnp.float32)
    idx = ((n,), jnp.int32)
    new = ((L, n, BS, KVH, DH), jnp.float32)
    layer_new = ((n, BS, KVH, DH), jnp.bfloat16)
    return {
        "set": (lambda k, v, i, a, b: kc._set_blocks((k, v), i, (a, b)),
                [pool, pool, idx, new, new], (0, 1)),
        "move": (lambda k, q, s, z, i, j: kc._move_blocks((k, q), (s, z),
                                                          i, j),
                 [pool, mirror, row, row, idx, idx], (0, 1, 2, 3)),
        "get": (lambda k, q, s, i: kc._get_blocks((k, q, s), i),
                [pool, mirror, row, idx], ()),
        "quantize": (lambda k, q, s, z, i: kc._quantize_into(k, q, s, z, i,
                                                             bits=8),
                     [pool, mirror, row, row, idx], (0, 1, 2, 3)),
        "dequantize": (kc._dequantize_into, [pool, mirror, row, row, idx],
                       (0, 2, 3)),
        "prefill_write": (lambda k, i, b: kc.write_blocks(k, i, b, layer=3),
                          [pool, idx, layer_new], (0,)),
    }


@pytest.mark.parametrize("name", sorted(_pool_updates()))
def test_pool_update_compiles_in_place(one_chip, name):
    """Pool updates keep no second copy of the pool on the chip: a scatter
    or gather of whole KV blocks makes XLA copy the pool into another tiled
    layout (a full pool array of temporaries), the slice-update loops do
    not."""
    fn, shapes, donate = _pool_updates()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    pool_bytes = 28 * 4096 * BS * KVH * DH * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_step_reads_stacked_pool_in_place(one_chip, step):
    """The engine's decode and chunk-prefill step programs hand the walks
    the stacked pool, not ``pool[layer]``: a Pallas operand is a buffer of
    its own, so a layer slice made XLA copy the layer out whole before each
    call. At 20,629 blocks (the one-chip benchmark's pool) such a slice is
    169 MB; a 4096-block slice lands in another memory space and shows no
    temporaries, so the test sizes the pool as served."""
    n_layers, cap, C = 2, 20629, 512
    cfg = dataclasses.replace(QWEN2_1P5B, n_layers=n_layers)
    kinds = tuple(lm.layer_kinds(cfg))
    specs = me.build_attention_specs(cfg, kinds)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    misc = shaped({k: v for k, v in params.items() if k != "segments"})
    layers = shaped(jax.eval_shape(lambda: tuple(
        lm.block_init(kind, jax.random.PRNGKey(i), cfg, jnp.bfloat16)
        for i, kind in enumerate(kinds))))
    pool = jax.ShapeDtypeStruct((n_layers, cap, BS, KVH, DH), jnp.bfloat16,
                                sharding=one_chip)
    if step == "decode":
        no_ssm = jax.ShapeDtypeStruct((0,), jnp.float32, sharding=one_chip)
        fn = functools.partial(me.paged_decode_step, cfg, kinds, specs)
        args = (misc, layers, i32(SLOTS, 1), i32(SLOTS), pool, pool,
                i32(SLOTS, MAX_NB), no_ssm, no_ssm)
    else:
        fn = functools.partial(me.paged_prefill_chunk, cfg, kinds, specs)
        args = (misc, layers, i32(1, C), i32(), pool, pool, i32(MAX_NB))
    prev = dispatch.set_mode("pallas")
    try:
        compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(*args).compile()
    finally:
        dispatch.set_mode(prev)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_bytes = cap * BS * KVH * DH * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 100
    layer_shape = rf"= \w+\[{cap},{BS},{KVH},{DH}\]"
    assert not re.findall(layer_shape, text)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["up", "down"])
@pytest.mark.parametrize("M", [SLOTS, 512], ids=["decode", "chunk"])
def test_wna16_gemm_compiles(one_chip, bits, K, N, M):
    def gemm(x, packed, scales, zeros, bias):
        return wna16_gemm(x, packed, scales, zeros, bias=bias, bits=bits,
                          group=GROUP, interpret=False)
    kp = K // 2 if bits == 4 else K
    shapes = [((M, K), jnp.bfloat16), ((kp, N), jnp.uint8),
              ((K // GROUP, N), jnp.float32), ((K // GROUP, N), jnp.float32),
              ((N,), jnp.bfloat16)]
    _compile(gemm, shapes, one_chip)
