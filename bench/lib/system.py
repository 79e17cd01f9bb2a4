"""The system under test: with the architecture modules' ``model_config``,
the only code of the benchmark that imports the program (``src/repro``).

It checks the parameter tree the architecture module hands the program
against the one the program builds itself, sizes the HBM budget to the
chip, builds ``MorphServeEngine`` as a deployment would run it
(``compute="real"``, the configuration's policy, Pallas kernels) and runs
once every step program a cell's traffic can reach, through the program's
public entry points.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import List

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax                                             # noqa: E402
import numpy as np                                     # noqa: E402
import jax.numpy as jnp                                # noqa: E402


def check_layout(cfg: dict, params: dict) -> None:
    """The tree handed over must be the one the program builds itself."""
    from repro.models import lm
    mc = cfg["arch"].model_config(cfg)
    want = jax.eval_shape(lambda k: lm.init_params(mc, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter layout")


# ---------------------------------------------------------------------------
# HBM budget
# ---------------------------------------------------------------------------
def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def size_budget(cfg: dict, bytes_limit: int) -> dict:
    """The engine's ``hbm_budget_bytes`` for a chip of ``bytes_limit``.

    The ledger books the active level's layers, 5% of the budget and the
    embedding, and the KV pool. On the device there is more: every int4
    variant beside the fp layers (the swap plan builds them for any
    policy), the pool array at its largest capacity (with every layer int4
    under the morph policy), the int8 mirrors of the whole pool and their
    per-(layer, block) scales when the int8 tier is on, and the step
    programs' temporaries, a share of the pool's bytes read from
    ``memory_analysis()`` of an AOT compile (``pool_k[li]`` is materialized
    as a kernel operand, and the int8 tier's programs relay the mirrors).
    While the engine is built, the stacked weights handed to it still sit
    beside its per-layer copies and the new pool. The budget is the largest
    whose device total stays under ``bytes_limit`` less
    ``serving.headroom_bytes`` at both times; under the morph policy
    the pool's start and largest capacity share one power-of-two bucket, so
    that no resize changes the pool's shape and no program recompiles."""
    s = cfg["serving"]
    layers = cfg["arch"].layer_bytes(cfg)
    misc = cfg["arch"].misc_bytes(cfg)
    L = len(layers)
    blk = sum(x["kv"] for x in layers)
    w0 = sum(x["fp"] for x in layers)
    w_min = sum(x["q"] for x in layers)
    if s["kv_quant"]:
        per_block = blk * (1.5 + s["kv_quant_step_temp_share"]) + 16 * L
    else:
        per_block = blk * (1.0 + s["step_temp_share"])
    fixed = w0 + w_min + misc
    room = bytes_limit - s["headroom_bytes"]
    # while the engine is built, the stacked weights handed to it are still
    # alive beside its per-layer copies and the new pool arrays
    most = int(min((room - fixed) // per_block,
                   (room - fixed - w0) // blk))
    if s["policy"] == "morph":
        delta = (w0 - w_min) // blk      # blocks freed with every layer int4
        # start + 1 and most in one bucket: pow2(start + 1) >= most
        if _pow2(most - delta) < most:
            most = _pow2(most - delta)
    else:
        delta = 0                        # the level is pinned at 0
    start = most - 1 - delta
    if start < 1:
        raise ValueError(f"{bytes_limit} bytes hold no KV pool beside the "
                         "weights")
    # the engine: start = (0.95 B - misc - w0) // blk
    budget = int((start * blk + w0 + misc) / 0.95) + 2
    return {"hbm_budget_bytes": budget, "start_blocks": start,
            "capacity": most, "block_bytes": blk,
            "device_bytes": int(fixed + most * per_block)}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def build_engine(cfg: dict, cell: dict, params: dict, budget: int):
    from repro.configs.base import ServingConfig
    from repro.engine import EngineConfig, KVQuantConfig, MorphServeEngine
    s = cfg["serving"]
    mc = cfg["arch"].model_config(cfg)
    sc = ServingConfig(hbm_budget_bytes=budget,
                       kv_block_size=s["kv_block_size"],
                       max_batch_slots=cell["max_batch_slots"],
                       max_seq_len=cell["max_seq_len"],
                       swap_levels=tuple(s["swap_levels"]),
                       swap_bits=s["swap_bits"], mode=s["mode"],
                       use_quant_kernel=True)
    ec = EngineConfig(policy=s["policy"], compute="real",
                      max_tokens_per_step=s["max_tokens_per_step"],
                      decode_nb_bucketing=s["decode_nb_bucketing"],
                      min_chunk_tokens=s["min_chunk_tokens"],
                      kv_quant=KVQuantConfig(enabled=s["kv_quant"],
                                             bits=s["kv_quant_bits"]))
    return MorphServeEngine(mc, params, sc, ec)


def reachable_levels(eng) -> List[int]:
    """Swap levels the engine's policy can serve at: a static policy pins
    one; the morph policy reaches every level of the plan up to its mode's
    cap."""
    if eng.ec.policy != "morph":
        return [eng.actuator.level]
    cap = eng.sc.max_level(eng.plan.n_layers)
    return [l for l in eng.plan.levels if l <= cap]


def _buckets(lo: int, hi: int) -> List[int]:
    """The power-of-two buckets ``pad_bucket(n, 1)`` takes for n in
    [lo, hi]."""
    out, b = [], _pow2(max(lo, 1))
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def program_shapes(eng, mix: dict) -> dict:
    """The step-program shapes a mix can reach (``MorphServeEngine``:
    ``_decode_real``, ``_prefill_chunk_real``, ``_prefill_real_many``,
    ``_prefill_real``): decode table widths; (chunk bucket, table width)
    of prefill chunks; whole-prompt lengths of the batched and the single
    prefill. A step leaves ``chunk_budget`` minus the decode rows for
    prompts; a prompt that does not fit whole streams in chunks of that
    size, the last one shorter."""
    bs = eng.pool.block_size
    pmin, pmax = mix["prompt"]["min"], mix["prompt"]["max"]
    most = -(-(pmax + mix["output"]["max"] + 1) // bs)
    decode = ([min(b, eng.max_nb) for b in _buckets(1, most)]
              if eng.ec.decode_nb_bucketing else [eng.max_nb])
    budget = eng.ec.max_tokens_per_step
    # prefill pieces share what a step's budget leaves after its decode rows,
    # so a piece of any length up to the budget can start anywhere in a
    # prompt (the engine pads it to a power-of-two multiple of the block)
    chunk = set()
    if pmax > bs:
        cp = bs
        while cp <= _pow2(-(-budget // bs)) * bs:
            for nb in _buckets(-(-cp // bs), -(-(pmax - 1 + cp) // bs)):
                chunk.add((cp, nb))
            cp *= 2
    # a prompt that fits what the step leaves is prefilled whole: batched
    # at a power-of-two length, or alone at its length in whole blocks
    whole_max = min(budget, pmax)
    batch = ([b for b in _buckets(1, whole_max) if b >= bs]
             if pmin <= budget else [])
    single = (sorted({bs * -(-(p + 1) // bs)
                      for p in range(pmin, whole_max + 1)})
              if pmin <= budget else [])
    return {"decode": sorted(set(decode)), "chunk": sorted(chunk),
            "batch": batch, "single": single}


def warm_programs(eng, shapes: dict, levels) -> int:
    """Run every step program in ``shapes`` once at each of ``levels``
    through ``ModelExec``'s entry points, as the engine's steps call them:
    on the engine's own pool and recurrent-state arrays, with the host-side
    copies of the token, position and table arrays and the argmax over the
    logits around each call. The window then compiles none of them. The
    tables are zeros, so the calls write only block 0, which no request
    holds yet. The int8 tier's variants are not reached: the pool hands
    the kernels its int8 operands only while a block is held int8. Returns
    the number of calls."""
    ex, pool, bs = eng.exec, eng.pool, eng.pool.block_size
    P = eng.ec.max_prefills_per_step

    def z(*shape):
        return jnp.array(np.zeros(shape, np.int32))

    n = 0
    for lvl in levels:
        ll = eng.actuator.layer_list(lvl)
        for nb in shapes["decode"]:
            # twice: the engine's (empty) recurrent-state arrays start
            # uncommitted and come back committed from its first decode,
            # and each is a program of its own
            for _ in range(2):
                logits, pool.k, pool.v, eng.ssm_conv, eng.ssm_ssm = \
                    ex.decode(ll, z(eng.slots, 1), z(eng.slots), pool.k,
                              pool.v, z(eng.slots, nb), eng.ssm_conv,
                              eng.ssm_ssm, pool.kv_quant_bundle())
                np.asarray(jnp.argmax(logits, axis=-1))
                n += 1
        for cp, nb in shapes["chunk"]:
            logits, pool.k, pool.v = ex.prefill_chunk(
                ll, z(1, cp), jnp.int32(0), pool.k, pool.v, z(nb),
                pool.kv_quant_bundle())
            int(jnp.argmax(logits[cp - 1]))
            n += 1
        for sp in shapes["batch"]:
            last, pool.k, pool.v = ex.prefill_batch(
                ll, z(P, sp), pool.k, pool.v, z(P, sp // bs),
                jnp.array(np.ones((P,), np.int32)))
            np.asarray(jnp.argmax(last, axis=-1))
            n += 1
        for sp in shapes["single"]:
            logits, pool.k, pool.v, eng.ssm_conv, eng.ssm_ssm = ex.prefill(
                ll, z(1, sp), pool.k, pool.v, z(sp // bs), eng.ssm_conv,
                eng.ssm_ssm, 0)
            int(jnp.argmax(logits[sp - 1]))
            n += 1
    jax.block_until_ready((pool.k, pool.v))
    return n
