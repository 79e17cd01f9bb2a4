"""Kernel microbenchmarks: wNa16 GEMM + paged attention + decode step.

``wna16_bench`` measures the quantized fast path at decode shapes
(M ∈ {1, 8, 16}, int4/int8): the fused path (Pallas on TPU; the XLA-fused
packed-dequant fallback on this container) vs an unfused dequant-then-matmul
that materializes the fp32 weight, plus elastic pool-resize latency with and
without capacity bucketing → ``BENCH_wna16.json``. The modeled HBM weight
bytes are the TPU story (packed bytes only vs a dequantized fp32 round-trip).

The decode-step benchmark measures the engine's fused decode attention op
(``ops.paged_decode_attention``) at a fixed ``max_nb`` with the block table
truncated to the live power-of-two bucket — the HBM-traffic lever this data
plane is built around. The chunk-prefill leg (``chunk_prefill_bench``,
refreshable alone via ``--only-chunk``) does the same for chunked-prefill
attention and additionally asserts token identity of the fused Pallas chunk
kernel vs the gather reference. Results land in ``BENCH_decode.json`` so
the perf trajectory is machine-readable across PRs."""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.configs import reduced, MORPH_LLAMA2_7B
from repro.engine.kv_cache import PagedKVPool
from repro.engine.model_exec import pad_bucket
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.quant import qlinear, quantize_tensor


def run(smoke: bool = False):
    rows = []
    # paged attention (jnp reference path)
    B, H, KVH, Dh, nb, bs = 8, 32, 8, 128, 256, 16
    maxnb = 16 if smoke else 64
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nb, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nb, bs, KVH, Dh))
    tables = jax.random.randint(ks[3], (B, maxnb), 0, nb)
    lens = jax.random.randint(ks[4], (B,), 1, maxnb * bs)
    pref = jax.jit(ref.paged_attention_ref)
    us = timeit(lambda: jax.block_until_ready(pref(q, kp, vp, tables, lens)))
    rows.append((f"paged_attn_B{B}_H{H}_T{maxnb*bs}", us,
                 "jnp_gather_path"))
    return rows


def wna16_bench(smoke: bool = False):
    """Quantized fast path at decode shapes: fused epilogue path vs
    dequant-then-matmul, plus elastic pool-resize latency with and without
    capacity bucketing. Emits ``BENCH_wna16.json``.

    Wall-clock on this container measures what actually executes here (the
    XLA-fused packed-dequant fallback for the fused path; two dispatches
    with a materialized fp32 weight for the unfused one). The modeled HBM
    weight traffic is the TPU story: the fused kernel reads only the packed
    bytes, the unfused path additionally writes + re-reads the dequantized
    fp32 weight.
    """
    K, N, group = (512, 512, 128) if smoke else (2048, 2048, 128)
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N)) * 0.05
    dense_f32 = K * N * 4
    gemm_rows = []
    ratios = {}
    for bits in (4, 8):
        qt = quantize_tensor(w, bits=bits, group=group)
        qk = qt.with_use_kernel()
        fused_bytes = qt.nbytes
        dequant_bytes = qt.nbytes + 2 * dense_f32   # deq write + GEMM read
        ratios[bits] = fused_bytes / dequant_bytes
        for M in (1, 8, 16):
            x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
            fused = jax.jit(lambda x, qt=qk: qlinear.matmul(x, qt))
            us_fused = timeit(lambda: jax.block_until_ready(fused(x)))
            deq = jax.jit(lambda qt: qt.dequantize(jnp.float32))
            mm = jax.jit(lambda x, wd: x @ wd)
            us_unfused = timeit(
                lambda: jax.block_until_ready(mm(x, deq(qt))))
            row = {"name": f"wna16_M{M}_int{bits}", "M": M, "bits": bits,
                   "K": K, "N": N, "group": group,
                   "fused_us": us_fused, "dequant_matmul_us": us_unfused,
                   "fused_weight_bytes": fused_bytes,
                   "dequant_weight_bytes": dequant_bytes,
                   "weight_bytes_ratio": fused_bytes / dequant_bytes,
                   "weight_bytes_vs_bf16": fused_bytes / (K * N * 2)}
            if M == 1:
                # kernel-body validation-mode timing (not a perf number)
                prev = ops.set_quant_kernel_mode("pallas_interpret")
                try:
                    fi = jax.jit(lambda x, qt=qk: qlinear.matmul(x, qt))
                    row["pallas_interpret_us"] = timeit(
                        lambda: jax.block_until_ready(fi(x)), n=2, warmup=1)
                finally:
                    ops.set_quant_kernel_mode(prev)
            gemm_rows.append(row)

    # elastic KV pool resize: within-bucket metadata update vs legacy copy
    cfg = reduced(MORPH_LLAMA2_7B)
    base = 64 if smoke else 256
    lo, hi = base + 1, base + base // 4      # both inside bucket(base + 1)
    resize_rows = []
    for bucketed in (True, False):
        pool = PagedKVPool(cfg, lo, 16, bucket_capacity=bucketed)
        state = {"cur": lo}

        def flip(pool=pool, state=state):
            nxt = hi if state["cur"] == lo else lo
            assert pool.resize(nxt)
            state["cur"] = nxt
            jax.block_until_ready(pool.k)

        us = timeit(flip)
        resize_rows.append({
            "name": f"pool_resize_{'bucketed' if bucketed else 'legacy'}",
            "us_per_resize": us, "blocks": (lo, hi),
            "capacity": pool.capacity, "device_copies": pool.copies})
    speedup = resize_rows[1]["us_per_resize"] / \
        max(resize_rows[0]["us_per_resize"], 1e-9)
    payload = {
        "config": {"K": K, "N": N, "group": group,
                   "backend": jax.default_backend(), "smoke": smoke,
                   "quant_kernel_mode": ops.quant_kernel_mode()},
        "gemm": gemm_rows,
        "resize": resize_rows,
        "fused_weight_bytes_ratio_int4": ratios[4],
        "fused_weight_bytes_ratio_int8": ratios[8],
        "resize_within_bucket_speedup": speedup,
    }
    out = os.environ.get("BENCH_WNA16_JSON", "BENCH_wna16.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def decode_bench(smoke: bool = False):
    """Per-step decode attention at fixed max_nb: full-table gather (seed
    path) vs the live power-of-two bucket, for short and long live contexts.

    Emits BENCH_decode.json: {name, us_per_call, nb_table, live_ctx} rows +
    the short-context speedup (full / bucketed)."""
    B, H, KVH, Dh, nb_pool, bs = 8, 32, 8, 128, 560, 16
    maxnb = 16 if smoke else 64
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (nb_pool, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nb_pool, bs, KVH, Dh))
    kn = jax.random.normal(ks[3], (B, KVH, Dh))
    vn = jax.random.normal(ks[4], (B, KVH, Dh))
    # globally distinct live tables (engine block-ownership contract)
    tables = jnp.array(
        1 + np.random.default_rng(0).permutation(B * maxnb).reshape(B, maxnb),
        jnp.int32)

    def step_us(nb_t, pos):
        fn = jax.jit(lambda q, kn, vn, kp, vp, t, p:
                     ops.paged_decode_attention(q, kn, vn, kp, vp, t, p))
        t = tables[:, :nb_t]
        return timeit(lambda: jax.block_until_ready(
            fn(q, kn, vn, kp, vp, t, pos)))

    results = []
    scenarios = [("short_ctx", 2 * bs - 1), ("long_ctx", maxnb * bs - 1)]
    speedups = {}
    for name, ctx in scenarios:
        pos = jnp.full((B,), ctx, jnp.int32)
        live_nb = ctx // bs + 1
        nb_bucket = min(pad_bucket(live_nb, 1), maxnb)
        us_full = step_us(maxnb, pos)
        us_bucket = step_us(nb_bucket, pos)
        speedups[name] = us_full / us_bucket
        results.append({"name": f"decode_{name}_full", "us_per_call": us_full,
                        "nb_table": maxnb, "live_ctx": ctx})
        results.append({"name": f"decode_{name}_bucketed",
                        "us_per_call": us_bucket, "nb_table": nb_bucket,
                        "live_ctx": ctx})
    payload = {
        "config": {"B": B, "H": H, "KVH": KVH, "Dh": Dh, "block_size": bs,
                   "max_nb": maxnb, "backend": jax.default_backend(),
                   "smoke": smoke},
        "results": results,
        "speedup_short_ctx": speedups["short_ctx"],
        "speedup_long_ctx": speedups["long_ctx"],
    }
    out = os.environ.get("BENCH_DECODE_JSON", "BENCH_decode.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def chunk_prefill_bench(smoke: bool = False):
    """Chunk-prefill attention leg: the engine's xla gather path at the full
    vs the live-bucketed table width, plus the fused Pallas chunk kernel
    (batched-append variant, interpret mode on this container — a kernel-
    body validation timing, not a perf number) and its token identity vs
    the gather reference (both outputs projected through one random unembed
    and argmax-compared per chunk position).

    Updates the ``chunk_prefill`` key of BENCH_decode.json in place so it
    composes with ``decode_bench`` whichever runs first. CI gates
    ``speedup_bucketed_table`` and ``token_identical_vs_ref``."""
    B, H, KVH, Dh, bs, C = 1, 32, 8, 128, 16, 64
    maxnb = 16 if smoke else 64
    nb_pool = maxnb + 8
    pos0 = 3 * bs                      # context paged by earlier chunks
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, C, H, Dh))
    kp = jax.random.normal(ks[1], (nb_pool, bs, KVH, Dh))
    vp = jax.random.normal(ks[2], (nb_pool, bs, KVH, Dh))
    kn = jax.random.normal(ks[3], (B, C, KVH, Dh))
    vn = jax.random.normal(ks[4], (B, C, KVH, Dh))
    tables = jnp.array(
        1 + np.random.default_rng(1).permutation(maxnb).reshape(B, maxnb),
        jnp.int32)
    # engine contract: chunk KV sits in the pool at the table offset
    idx = pos0 + np.arange(C)
    blk = np.asarray(tables)[0][idx // bs]
    kp = kp.at[blk, idx % bs].set(kn[0])
    vp = vp.at[blk, idx % bs].set(vn[0])
    nb_bucket = min(pad_bucket((pos0 + C) // bs + 1, 1), maxnb)

    def gather_us(nb_t):
        fn = jax.jit(lambda q, kp, vp, t:
                     pa.paged_chunk_gather_attention(q, kp, vp, t, pos0))
        t = tables[:, :nb_t]
        return timeit(lambda: jax.block_until_ready(fn(q, kp, vp, t)))

    us_full = gather_us(maxnb)
    us_bucket = gather_us(nb_bucket)
    t = tables[:, :nb_bucket]
    us_kernel = timeit(lambda: jax.block_until_ready(
        pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, t, pos0,
                                       interpret=True)), n=2, warmup=1)
    # token identity: same pseudo-unembed over both attention outputs
    out_ref = pa.paged_chunk_gather_attention(q, kp, vp, t, pos0)
    out_ker = pa.paged_chunk_attention_fused(q, kn, vn, kp, vp, t, pos0,
                                             interpret=True)
    unembed = jax.random.normal(jax.random.PRNGKey(9), (H * Dh, 256))
    toks_ref = jnp.argmax(out_ref.reshape(B, C, -1) @ unembed, -1)
    toks_ker = jnp.argmax(out_ker.reshape(B, C, -1) @ unembed, -1)
    section = {
        "config": {"B": B, "H": H, "KVH": KVH, "Dh": Dh, "block_size": bs,
                   "chunk": C, "pos0": pos0, "max_nb": maxnb,
                   "backend": jax.default_backend(), "smoke": smoke},
        "results": [
            {"name": "chunk_prefill_gather_full", "us_per_call": us_full,
             "nb_table": maxnb},
            {"name": "chunk_prefill_gather_bucketed",
             "us_per_call": us_bucket, "nb_table": nb_bucket},
            {"name": "chunk_prefill_pallas_interpret",
             "us_per_call": us_kernel, "nb_table": nb_bucket},
        ],
        "speedup_bucketed_table": us_full / us_bucket,
        "token_identical_vs_ref": bool((toks_ref == toks_ker).all()),
    }
    out = os.environ.get("BENCH_DECODE_JSON", "BENCH_decode.json")
    payload = {}
    if os.path.exists(out):
        with open(out) as f:
            payload = json.load(f)
    payload["chunk_prefill"] = section
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes for CI")
    ap.add_argument("--only-chunk", action="store_true",
                    help="refresh only the chunk_prefill section of "
                         "BENCH_decode.json")
    # tolerate foreign argv when invoked via benchmarks/run.py
    args, _ = ap.parse_known_args()
    print("name,us_per_call,derived")
    if not args.only_chunk:
        for name, us, derived in run(smoke=args.smoke):
            print(f"{name},{us:.1f},{derived}")
        wpay = wna16_bench(smoke=args.smoke)
        for r in wpay["gemm"]:
            print(f"{r['name']},{r['fused_us']:.1f},"
                  f"dequant_us={r['dequant_matmul_us']:.1f};"
                  f"weight_bytes_ratio={r['weight_bytes_ratio']:.3f}")
        for r in wpay["resize"]:
            print(f"{r['name']},{r['us_per_resize']:.1f},"
                  f"copies={r['device_copies']}")
        print(f"wna16 int4 modeled weight-byte ratio (fused/dequant): "
              f"{wpay['fused_weight_bytes_ratio_int4']:.3f}")
        print(f"pool resize within-bucket speedup: "
              f"{wpay['resize_within_bucket_speedup']:.1f}x")
        payload = decode_bench(smoke=args.smoke)
        for r in payload["results"]:
            print(f"{r['name']},{r['us_per_call']:.1f},"
                  f"nb_table={r['nb_table']};live_ctx={r['live_ctx']}")
        print(f"decode short-ctx speedup (bucketed vs full table): "
              f"{payload['speedup_short_ctx']:.2f}x")
    cpay = chunk_prefill_bench(smoke=args.smoke)
    for r in cpay["results"]:
        print(f"{r['name']},{r['us_per_call']:.1f},"
              f"nb_table={r['nb_table']}")
    print(f"chunk-prefill bucketed-table speedup: "
          f"{cpay['speedup_bucketed_table']:.2f}x")
    print(f"chunk-prefill kernel token-identical vs reference: "
          f"{cpay['token_identical_vs_ref']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
