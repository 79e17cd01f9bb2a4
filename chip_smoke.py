#!/usr/bin/env python3
"""Bring-up smoke for the MorphServe serving path on a TPU.

Run from the repository root, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas (v5e 2x2 host)

One chip. Qwen2-1.5B at its published widths (28 layers, d_model 1536, 12
query / 2 kv heads, d_ff 8960, vocab 151936) with random bf16 weights from
``--seed`` and a bf16 KV pool serves eight requests through
``MorphServeEngine.run_trace`` with compiled Pallas kernels. The HBM budget
is tight enough that the morph controller quantizes cold KV blocks and swaps
layers to int4, and one prompt is longer than the step's token budget, so
the paged decode kernel, the chunk-prefill kernel (both with in-kernel KV
dequant) and the wNa16 GEMM all run. A parity phase then runs one prompt's
chunked prefill and a few decode steps through ``ModelExec`` under the
``pallas`` and the ``xla`` dispatch modes and compares the logits, and a
kernel parity phase compares each kernel's output with the XLA path on the
same inputs, with faults planted to show its tolerance separates them.

Four chips (``--chips 4``). Only the replica path: a ``ServingCluster`` of
four real-compute replicas, each with its weights and KV pool on its own
device, serves a few requests while one replica drains and migrates a
running request's KV to a peer chip. The token streams must equal those of
the same requests served by one replica with no drain.

Every check raises when it fails (exit code 1). Lines before the last are
diagnostics; wall-clock numbers there are host-clock times around work that
ends in ``block_until_ready``. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or outside the repository, the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# pallas-vs-xla parity bound on the logits, relative to their largest
# magnitude. Both paths store bf16 and accumulate in f32 but round at
# different points: the kernels keep softmax probabilities and dequantized
# int4 weights in f32, XLA's default TPU matmul precision rounds them to
# bf16 first. That is a 2^-8 relative step per rounding, compounded over 28
# residual layers into percent-level logit noise. A fault in the first
# layers moves the logits by their own magnitude, but not every fault
# deep in the stack clears this bound (see PARITY_FAULTS): the kernel
# parity phase is the check that does.
PARITY_REL_TOL = 0.05

# the serving phase's traffic: (prompt tokens, generated tokens). All arrive
# at once; 1024 > the 256-token step budget, so it streams through chunked
# prefill while the rest are admitted whole.
SERVE_REQUESTS = ((1024, 64), (192, 32), (64, 48), (224, 16),
                  (160, 40), (256, 24), (96, 56), (128, 32))
STEP_TOKENS = 256
# KV the HBM budget leaves beside the fp weights at level 0, as a share of
# the blocks all requests hold at once: pressure builds at once
BUDGET_KV_SHARE = 0.6


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# the duration event JAX records for every XLA backend compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# one chip: serve through the engine
# ---------------------------------------------------------------------------
def serving_config(cfg, params, requests, *, step_tokens: int = STEP_TOKENS,
                   monitor_window_s: float = 0.02):
    """Engine knobs of the serving phase. The budget holds the fp weights
    plus ``BUDGET_KV_SHARE`` of the blocks ``requests`` hold at once (the
    ledger keeps 5% for activations). The virtual clock advances a few ms
    per step at full width, so the controller may take one rung per
    ``monitor_window_s`` of it."""
    from repro.configs import ServingConfig
    from repro.core import tree_bytes
    from repro.engine import EngineConfig, KVQuantConfig
    from repro.engine.kv_cache import kv_block_bytes
    import jax.numpy as jnp
    bs = 16
    blocks = [-(-(p + g) // bs) for p, g in requests]
    blk = kv_block_bytes(cfg, bs, jnp.dtype(cfg.dtype).itemsize)
    budget = int((tree_bytes(params)
                  + int(BUDGET_KV_SHARE * sum(blocks)) * blk) / 0.95)
    sc = ServingConfig(hbm_budget_bytes=budget, kv_block_size=bs,
                       max_batch_slots=8, max_seq_len=(max(blocks) + 1) * bs,
                       # one relief level: 4 layers at int4 (performance
                       # mode lets the smallest models swap that deep)
                       swap_levels=(0, 4), swap_bits=4, mode="performance",
                       use_quant_kernel=True,
                       monitor_window_s=monitor_window_s)
    ec = EngineConfig(policy="morph", compute="real", seed=0,
                      max_tokens_per_step=step_tokens,
                      # one decode table width: fewer programs to compile
                      decode_nb_bucketing=False,
                      # a small quantize-cold cap: the tier runs dry
                      # quickly and relief escalates to a layer swap
                      kv_quant=KVQuantConfig(enabled=True, bits=8,
                                             max_quant_frac=0.125))
    return sc, ec


def serve_phase(cfg, params, *, seed: int = 0, log=print,
                requests=SERVE_REQUESTS, step_tokens: int = STEP_TOKENS,
                monitor_window_s: float = 0.02) -> dict:
    """Serve ``requests`` ((prompt, generated) token counts, all arriving
    at once) through ``MorphServeEngine.run_trace`` and check every request
    finished with its token count and that swaps, kv-quant events and chunk
    steps all happened. Returns the engine and the phase's counters."""
    import jax
    import numpy as np
    from repro.engine import MorphServeEngine, TraceRequest

    check(max(p for p, _ in requests) > step_tokens,
          "no prompt is longer than the step budget: nothing would chunk")
    sc, ec = serving_config(cfg, params, requests, step_tokens=step_tokens,
                            monitor_window_s=monitor_window_s)
    rng = np.random.default_rng(seed)
    trace = [TraceRequest(0.0, p, g, tuple(int(t) for t in
                                           rng.integers(0, cfg.vocab, p)))
             for p, g in requests]
    eng = MorphServeEngine(cfg, params, sc, ec)
    compiles = [0]
    steps = []                   # (wall seconds, compiled during the step)
    step = eng.step

    def on_event(event, _secs, **_kw):
        compiles[0] += event == BACKEND_COMPILE_EVENT

    def timed_step():
        c0, t0 = compiles[0], time.perf_counter()
        dt = step()
        jax.block_until_ready((eng.pool.k, eng.pool.v))
        steps.append((time.perf_counter() - t0, compiles[0] > c0))
        return dt
    eng.step = timed_step
    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        eng.run_trace(trace, max_steps=5000)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    wall = time.perf_counter() - t0

    levels = [t.swap_level for t in eng.monitor.history]
    stats = {
        "requests": len(trace),
        # a preempted request folds its tokens into the prompt: count the
        # logical stream against the original budget
        "finished": sum(r.state.name == "FINISHED"
                        and len(r.logical_stream()) == r.orig_max_new_tokens
                        for r in eng.all_requests),
        "swaps": sum(a != b for a, b in zip([0] + levels, levels)),
        "max_level": max(levels, default=0),
        "kv_quant_events": sum(k == "quantize"
                               for _, k, _ in eng.kv_quant_events),
        "chunk_steps": sum(r.prefill_chunks for r in eng.all_requests),
        "preemptions": sum(r.preemptions for r in eng.all_requests),
        "steps": len(steps),
        "compiles": compiles[0],
        "pool_dtype": str(eng.pool.k.dtype),
    }
    steady = sorted(s for s, c in steps if not c)
    stats.update({
        "run_trace_wall_s": wall, "first_step_wall_s": steps[0][0],
        "steady_steps": len(steady),
        "steady_step_wall_median_s": (steady[len(steady) // 2]
                                      if steady else None),
        "steady_step_wall_min_s": steady[0] if steady else None,
        "step_wall_s": [t for t, _ in steps],
        "step_compiled": [c for _, c in steps]})
    log("serve: " + ", ".join(f"{k} {stats[k]}" for k in (
        "requests", "finished", "swaps", "max_level", "kv_quant_events",
        "chunk_steps", "preemptions", "steps", "compiles", "pool_dtype")))
    log(f"serve wall-clock: run_trace {wall:.3f} s; first step (compile) "
        f"{steps[0][0]:.3f} s; {len(steady)} steps without a compile, "
        f"median {stats['steady_step_wall_median_s']} s, "
        f"min {stats['steady_step_wall_min_s']} s")
    check(stats["finished"] == stats["requests"],
          f"only {stats['finished']}/{stats['requests']} requests finished "
          "with their token count")
    check(stats["swaps"] >= 1, "no layer swap happened")
    check(stats["kv_quant_events"] >= 1, "no quantize-cold batch happened")
    check(stats["chunk_steps"] >= 1, "no chunked-prefill step happened")
    return {"engine": eng, **stats}


# ---------------------------------------------------------------------------
# one chip: pallas vs xla logits through ModelExec
# ---------------------------------------------------------------------------
# kernel-level parity bound: each kernel's output against the XLA path on
# the same inputs, relative to the output's largest magnitude. The two
# read the same bf16 pool and weights and accumulate in f32; they differ
# in summation order, in the bf16 rounding of the output and in matmul
# passes that round f32 probabilities or dequantized weights to bf16
# (2^-9 relative each), well under 1% of scale for one kernel call. The
# planted faults below must each read above it.
KERNEL_REL_TOL = 0.02

# faults planted in what the block walk reads, in one layer: a wrong kv
# head, a wrong table entry, a dropped dequant term. At the kernel level
# each must exceed KERNEL_REL_TOL. Through 28 layers to the logits they
# need not exceed PARITY_REL_TOL: a one-block fault in a middle or late
# layer moved them by 2-3% of their scale in a CPU probe at 28 layers and
# reduced width, which is why the kernels are compared one by one as well
PARITY_FAULTS = ("kv_head", "table_entry", "dequant")


def plant_fault(pool, fault: str, layer: int, fp_ids, q_ids) -> None:
    """Corrupt ``layer``'s paged context the way a walk bug would read it:
    ``kv_head`` swaps the kv heads (a wrong head index), ``table_entry``
    puts the first quantized block's state in the first fp block (a wrong
    table entry), ``dequant`` zeroes the quantized blocks' scale/zero (the
    sidecar FMA dropped: they read as zeros)."""
    def edit(names, fn):
        for name in names:
            setattr(pool, name, fn(getattr(pool, name)))
    if fault == "kv_head":
        edit(("k", "v", "qk", "qv"),
             lambda a: a.at[layer].set(a[layer][..., ::-1, :]))
    elif fault == "table_entry":
        a, b = fp_ids[0], q_ids[0]
        edit(("k", "v", "qk", "qv", "k_scale", "v_scale", "k_zero",
              "v_zero"), lambda x: x.at[layer, a].set(x[layer, b]))
    elif fault == "dequant":
        idx = list(q_ids)
        edit(("k_scale", "v_scale", "k_zero", "v_zero"),
             lambda x: x.at[layer, idx].set(0.0))
    else:
        raise ValueError(f"unknown fault {fault!r}")


def parity_phase(cfg, params, layer_list, *, seed: int = 0, log=print,
                 chunk: int = 256, n_chunks: int = 2, n_decode: int = 4,
                 fault_layers=None):
    """One prompt's ``n_chunks``-chunk prefill (the later chunks over
    int8-quantized context) plus teacher-forced decode steps through
    ``ModelExec`` under the resolved Pallas mode and under ``xla``; checks
    the max |logit| difference relative to the largest |logit| against
    ``PARITY_REL_TOL``. Then each of ``PARITY_FAULTS`` is planted in each
    of ``fault_layers`` (default: first, middle and last) on the Pallas
    side — same shapes, so no new program — and the readings are reported
    (not checked: see ``PARITY_FAULTS``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import model_exec
    from repro.engine.kv_cache import PagedKVPool
    from repro.kernels import dispatch
    from repro.models import lm

    bs = 16
    n_tok = chunk * n_chunks + n_decode
    nb = -(-(n_tok + 1) // bs)
    n_quant = chunk // bs // 2           # cold half of the first chunk
    toks = np.random.default_rng(seed + 1).integers(0, cfg.vocab, n_tok)
    kinds = lm.layer_kinds(cfg)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    mode = dispatch.resolve()

    def make(m):
        prev = dispatch.set_mode(m)
        try:
            return model_exec.ModelExec(cfg, params, kinds, kv_quant=True)
        finally:
            dispatch.set_mode(prev)

    def run(m, ex, fault=None, layer=0):
        prev = dispatch.set_mode(m)
        try:
            pool = PagedKVPool(cfg, nb + 1, bs, dtype=jnp.dtype(cfg.dtype),
                               device=device)
            ids = pool.alloc.alloc(nb)
            out = []
            for c in range(n_chunks):
                pos0 = c * chunk
                width = model_exec.pad_bucket(pool.blocks_for(pos0 + chunk), 1)
                table = np.zeros((width,), np.int32)
                table[:min(width, nb)] = ids[:width]
                logits, pool.k, pool.v = ex.prefill_chunk(
                    layer_list, jnp.array(toks[None, pos0:pos0 + chunk]),
                    jnp.int32(pos0), pool.k, pool.v, jnp.array(table),
                    pool.kv_quant_bundle())
                out.append(np.asarray(logits, np.float32))
                if c == 0:
                    # the first chunk's blocks go cold: the later chunks
                    # and the decode steps read them through the dequant
                    pool.quantize_blocks(ids[:n_quant], bits=8)
                    if fault is not None:
                        plant_fault(pool, fault, layer,
                                    ids[n_quant:chunk // bs], ids[:n_quant])
            tables = np.asarray([ids], np.int32)
            for i in range(n_decode):
                p = chunk * n_chunks + i
                # no recurrent state (attention-only model): two distinct
                # empty arrays, since decode donates both
                logits, pool.k, pool.v, _, _ = ex.decode(
                    layer_list, jnp.array([[toks[p]]], jnp.int32),
                    jnp.array([p], jnp.int32), pool.k, pool.v,
                    jnp.array(tables), jnp.zeros((0,)), jnp.zeros((0,)),
                    pool.kv_quant_bundle())
                out.append(np.asarray(logits, np.float32))
            return out
        finally:
            dispatch.set_mode(prev)

    kernel_ex = make(mode)
    want = run("xla", make("xla"))
    scale = max(float(np.abs(w).max()) for w in want)

    def rel_diff(got):
        return max(float(np.abs(g - w).max())
                   for g, w in zip(got, want)) / max(scale, 1e-30)

    got = run(mode, kernel_ex)
    rel = rel_diff(got)
    log(f"parity ({mode} vs xla): max |dlogit| {rel * scale:.6g}, "
        f"max |logit| {scale:.6g}, relative {rel:.6g} "
        f"(tolerance {PARITY_REL_TOL})")
    check(all(np.isfinite(g).all() for g in got), "non-finite logits")
    check(rel <= PARITY_REL_TOL,
          f"pallas-vs-xla logits differ by {rel:.4g} of their scale")
    if fault_layers is None:
        fault_layers = sorted({0, cfg.n_layers // 2, cfg.n_layers - 1})
    faults = {f"{f}@{layer}": rel_diff(run(mode, kernel_ex, f, layer))
              for f in PARITY_FAULTS for layer in fault_layers}
    if faults:
        log("parity with a fault planted in one layer (relative): "
            + ", ".join(f"{k} {v:.6g}" for k, v in faults.items()))
    return {"max_abs_diff": rel * scale, "max_abs_logit": scale,
            "relative": rel, "tolerance": PARITY_REL_TOL, "faults": faults}


def kernel_parity_phase(cfg, *, seed: int = 0, log=print, device=None,
                        slots: int = 8, chunk: int = 256) -> dict:
    """Each serving kernel at ``cfg``'s widths, under the resolved Pallas
    mode and under ``xla`` on the same inputs: fused decode over ``slots``
    rows and the fused chunk prefill, both over a pool whose context
    blocks alternate fp and int8, and the wNa16 int4 GEMM d_model -> d_ff.
    Each output must agree within ``KERNEL_REL_TOL`` of its scale; then
    every ``PARITY_FAULTS`` fault (and, for the GEMM, scales read one
    column off) planted on the Pallas side must read above it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine.kv_cache import PagedKVPool
    from repro.kernels import dispatch, ops
    from repro.quant import QTensor, quantize_tensor

    bs, H, KVH, Dh = 16, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    mode = dispatch.resolve()
    rng = np.random.default_rng(seed + 2)
    device = device or jax.devices()[0]
    put = lambda a: jax.device_put(jnp.asarray(a), device)  # noqa: E731
    cfg1 = cfg.replace(n_layers=1)

    # decode rows hold 3-24 blocks of context; the chunk row starts its
    # chunk mid-block after 12.5 blocks of context
    lens = rng.integers(2 * bs + 1, 24 * bs, slots)
    pos0 = 12 * bs + bs // 2
    rows = [int(n) // bs + 1 for n in lens] + [-(-(pos0 + chunk) // bs)]

    def new_pool():
        """The parity pool: random fp content in every block, then every
        other context block quantized to int8 (blocks that hold the new
        tokens stay fp: the engine never quantizes a sequence's tail)."""
        r = np.random.default_rng(seed + 3)
        p = PagedKVPool(cfg1, sum(rows) + 1, bs, dtype=dt, device=device)
        ids = [p.alloc.alloc(n) for n in rows]
        every = [b for row in ids for b in row]
        shape = (1, len(every), bs, KVH, Dh)
        p.scatter_blocks(every, r.standard_normal(shape),
                         r.standard_normal(shape))
        p.quantize_blocks([b for row in ctx_of(ids) for b in row[::2]],
                          bits=8)
        return p, ids

    def ctx_of(ids):
        return [row[:-1] for row in ids[:-1]] + [ids[-1][:pos0 // bs]]

    pool, ids = new_pool()
    ctx = ctx_of(ids)
    width = max(rows)
    tables = np.zeros((slots, width), np.int32)
    for i, row in enumerate(ids[:-1]):
        tables[i, :len(row)] = row
    ctable = np.asarray([ids[-1]], np.int32)
    q = put(rng.standard_normal((slots, H, Dh)).astype(dt))
    qc = put(rng.standard_normal((1, chunk, H, Dh)).astype(dt))
    pos = put(lens.astype(np.int32))
    spec = ops.AttentionSpec(q_heads=H, kv_heads=KVH, kv_quant=True)

    def new_kv(p):
        """The new tokens' K/V as the fp pool holds them (the xla path
        reads them from the pool, the kernels take them as operands)."""
        k, v = (np.asarray(a[0], np.float32) for a in (p.k, p.v))
        dec = [np.stack([a[tables[i, n // bs], n % bs]
                         for i, n in enumerate(lens)]) for a in (k, v)]
        chk = [a[ctable[0]].reshape(-1, KVH, Dh)[None, pos0:pos0 + chunk]
               for a in (k, v)]
        return [put(a.astype(dt)) for a in dec + chk]

    def attend(m, p):
        kn, vn, kc, vc = new_kv(p)
        bundle = tuple(a[0] for a in p.kv_quant_bundle())
        prev = dispatch.set_mode(m)
        try:
            dec = ops.paged_decode_attention(
                q, kn, vn, p.k[0], p.v[0], put(tables), pos, spec,
                kv_quant=bundle)
            chk = ops.paged_prefill_attention(
                qc, p.k[0], p.v[0], put(ctable), pos0, spec, k_new=kc,
                v_new=vc, kv_quant=bundle)
            return {"decode": np.asarray(dec, np.float32),
                    "chunk": np.asarray(chk, np.float32)}
        finally:
            dispatch.set_mode(prev)

    x = put(rng.standard_normal((slots, cfg.d_model)).astype(dt))
    qt = quantize_tensor(put(rng.standard_normal((cfg.d_model, cfg.d_ff))
                             .astype(dt) * 0.05), bits=4, group=128,
                         use_kernel=True)

    def gemm(m, t):
        prev = dispatch.set_mode(m)
        try:
            return np.asarray(ops.wna16_matmul(x, t), np.float32)
        finally:
            dispatch.set_mode(prev)

    def rel(got, want):
        return float(np.abs(got - want).max()) / max(
            float(np.abs(want).max()), 1e-30)

    want = {**attend("xla", pool), "wna16": gemm("xla", qt)}
    got = {**attend(mode, pool), "wna16": gemm(mode, qt)}
    clean = {k: rel(got[k], want[k]) for k in want}
    log(f"kernel parity ({mode} vs xla, relative): "
        + ", ".join(f"{k} {v:.6g}" for k, v in clean.items())
        + f" (tolerance {KERNEL_REL_TOL})")
    check(all(np.isfinite(g).all() for g in got.values()),
          "non-finite kernel output")
    bad = [k for k, v in clean.items() if not v <= KERNEL_REL_TOL]
    check(not bad, f"kernels {bad} differ from the xla path beyond "
          f"{KERNEL_REL_TOL} of scale")

    faults = {}
    for fault in PARITY_FAULTS:
        for kern, row in (("decode", 0), ("chunk", slots)):
            p, _ = new_pool()
            plant_fault(p, fault, 0, ctx[row][1::2], ctx[row][::2])
            faults[f"{kern}/{fault}"] = rel(attend(mode, p)[kern],
                                            want[kern])
    children, aux = qt.tree_flatten()
    packed, scales, zeros, inv = children
    # the first group's scales read one output column off
    bad_qt = QTensor.tree_unflatten(
        aux, (packed, scales.at[0].set(jnp.roll(scales[0], 1)), zeros, inv))
    faults["wna16/scale_column"] = rel(gemm(mode, bad_qt), want["wna16"])
    log(f"kernel parity with a planted fault (relative, must exceed "
        f"{KERNEL_REL_TOL}): "
        + ", ".join(f"{k} {v:.6g}" for k, v in faults.items()))
    weak = [k for k, v in faults.items() if not v > KERNEL_REL_TOL]
    check(not weak, f"planted faults {weak} stay within the kernel parity "
          "tolerance: it cannot tell a broken kernel from rounding noise")
    return {"relative": clean, "tolerance": KERNEL_REL_TOL,
            "faults": faults}


def one_chip(seed: int, log=print) -> dict:
    import jax
    from repro.configs.archs import QWEN2_1P5B
    from repro.kernels import dispatch
    from repro.models import lm

    check(dispatch.resolve() == "pallas",
          f"dispatch resolves to {dispatch.resolve()!r}, not 'pallas'")
    log(f"dispatch: {dispatch.resolve()}")
    cfg = QWEN2_1P5B
    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"init wall-clock: {time.perf_counter() - t0:.3f} s "
        f"({cfg.name}, {cfg.n_layers} layers, bf16 random weights)")
    res = serve_phase(cfg, params, seed=seed, log=log)
    eng = res["engine"]
    check(res["pool_dtype"] == "bfloat16",
          f"KV pool is {res['pool_dtype']}, not bfloat16")
    # the serving phase's deepest level: int4 layers through the wNa16 GEMM
    layers = eng.actuator.layer_list(res["max_level"])
    t0 = time.perf_counter()
    parity = parity_phase(cfg, params, layers, seed=seed, log=log)
    log(f"parity wall-clock: {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    kernels = kernel_parity_phase(cfg, seed=seed, log=log)
    log(f"kernel parity wall-clock: {time.perf_counter() - t0:.3f} s")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")
    res.pop("engine")
    return {"serve": res, "parity": parity, "kernel_parity": kernels,
            "peak_bytes_in_use": mem.get("peak_bytes_in_use")}


# ---------------------------------------------------------------------------
# four chips: one-chip replicas, drain with KV migration
# ---------------------------------------------------------------------------
CLUSTER_LAYERS = 4          # published widths, depth cut for compile time
# each replica's HBM budget as a share of its chip's memory. The KV pool
# then takes two thirds of the chip (10.4 of 15.75 GiB on a v5e), so a pool
# update that built a second pool array instead of updating in place
# (+5.2 GiB) would not fit, while the decode and chunk programs' own
# temporaries (two layer slices of the pool as kernel operands, 2.6 GiB,
# AOT compile) still do
CLUSTER_BUDGET_SHARE = 0.75


def cluster_phase(seed: int, n_replicas: int = 4, log=print,
                  cfg=None, budget_bytes: int = None) -> dict:
    """``n_replicas`` one-chip replicas serve eight equal requests while
    replica 0 drains; checks placement, a migration, and stream identity
    with one replica. ``cfg`` defaults to Qwen2-1.5B widths at
    ``CLUSTER_LAYERS`` layers; ``budget_bytes`` to ``CLUSTER_BUDGET_SHARE``
    of the first device's memory."""
    import gc

    import jax
    import numpy as np
    from repro.configs import ServingConfig
    from repro.configs.archs import QWEN2_1P5B
    from repro.core import tree_bytes
    from repro.distributed.cluster import ServingCluster
    from repro.distributed.faults import FaultPlan, FaultSpec
    from repro.distributed.migration import MigrationConfig
    from repro.engine import EngineConfig, TraceRequest
    from repro.engine.request import RState
    from repro.models import lm

    devs = jax.devices()
    check(len(devs) >= n_replicas,
          f"{len(devs)} devices, {n_replicas} replicas asked for")
    cfg = cfg or QWEN2_1P5B.replace(n_layers=CLUSTER_LAYERS)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    limit = (devs[0].memory_stats() or {}).get("bytes_limit")
    if budget_bytes is None:
        check(limit is not None, "the device reports no memory limit")
        budget_bytes = int(CLUSTER_BUDGET_SHARE * limit)
    sc = ServingConfig(hbm_budget_bytes=budget_bytes, kv_block_size=16,
                       max_batch_slots=8, max_seq_len=512,
                       use_quant_kernel=True)
    ec = EngineConfig(policy="static_fp16", compute="real", seed=seed,
                      max_tokens_per_step=256, decode_nb_bucketing=False)
    rng = np.random.default_rng(seed)
    # equal prompts: every whole-prompt prefill has one padded length, so
    # a request's numerics do not depend on what it was batched with
    trace = [TraceRequest(0.0, 128, 32, tuple(int(t) for t in
                                              rng.integers(0, cfg.vocab, 128)))
             for _ in range(8)]
    # every replica's virtual clock runs 100x slow, so requests are still
    # decoding when the drain fires at the second dispatch round
    slow = tuple(FaultSpec("slow", 0.0, replica=i, factor=100.0)
                 for i in range(n_replicas))

    def streams(cl):
        return {q.cluster_id: tuple(q.logical_stream())
                for q in cl.collect_requests()
                if q.cluster_id is not None and q.state == RState.FINISHED}

    t0 = time.perf_counter()
    cl = ServingCluster(cfg, params, sc, ec, n_replicas=n_replicas,
                        migration=MigrationConfig())
    def devices_of(e):
        return {next(iter(a.devices())) for a in
                (e.pool.k, e.pool.v, e.exec.misc["embed"],
                 *jax.tree.leaves(e.plan.fp_layers[0][1]))}

    # no engine stays bound to a local: the reference run below needs the
    # cluster's pools freed
    for i, where in enumerate([devices_of(r.engine) for r in cl.replicas]):
        check(where == {devs[i]},
              f"replica {i} arrays live on {where}, not {devs[i]}")
    pool_shape = cl.replicas[0].engine.pool.k.shape
    pool_bytes = 2 * cl.replicas[0].engine.pool.k.nbytes
    log(f"cluster: replica i holds its params and pool on jax.devices()[i] "
        f"for i < {n_replicas}; KV pool {pool_bytes} bytes per replica "
        f"(device memory limit {limit})")
    if limit is not None:
        check(tree_bytes(params) + 1.5 * pool_bytes > limit,
              "a second pool array would fit beside the pool: the run "
              "could not tell an in-place pool update from a copy")
    cl.run(list(trace), FaultPlan(seed=seed, specs=slow + (
        FaultSpec("drain", 0.5, replica=0),)), horizon_s=600.0)
    got = streams(cl)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:n_replicas]]
    log(f"cluster wall-clock: {time.perf_counter() - t0:.3f} s; drains "
        f"{cl.drains}, migrations ok {cl.migrations_ok} of "
        f"{cl.migrations_attempted}, blocks moved {cl.migrated_blocks}, "
        f"re-dispatched {cl.redispatched}; peak_bytes_in_use per device "
        f"{peaks}")
    stats = {"replicas": n_replicas, "drains": cl.drains,
             "migrations_ok": cl.migrations_ok,
             "migrated_blocks": cl.migrated_blocks,
             "redispatched": cl.redispatched, "pool_bytes": pool_bytes,
             "peak_bytes_in_use": peaks}
    # the reference replica takes device 0's memory: free the cluster's
    del cl
    gc.collect()
    check(not any(a.shape == pool_shape for a in jax.live_arrays()),
          "the cluster's KV pools are still alive")
    ref = ServingCluster(cfg, params, sc, ec, n_replicas=1)
    ref.run(list(trace), FaultPlan(seed=seed, specs=slow[:1]),
            horizon_s=600.0)
    want = streams(ref)
    check(stats["migrations_ok"] >= 1,
          "the drain migrated no running request")
    check(len(got) == len(want) == len(trace),
          f"finished {len(got)} (cluster) / {len(want)} (one replica) of "
          f"{len(trace)}")
    same = sum(got[c] == want[c] for c in want)
    log(f"streams equal to the one-replica run: {same}/{len(want)}")
    check(same == len(want), "token streams differ from the one-replica run")
    return {**stats, "streams_equal": same, "requests": len(trace)}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=None,
                    help="also write the phases' numbers to this JSON file")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repository's src/repro is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX sees {dev.platform} "
              "devices only)", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    log = lambda s: print(s, flush=True)   # noqa: E731
    if args.chips == 4:
        report = cluster_phase(args.seed, log=log)
    else:
        report = one_chip(args.seed, log=log)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps({"device": device, **report},
                                       indent=1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
