"""CPU tests of the benchmark's arithmetic: FLOP and byte counts against
hand counts at each configuration's widths, the client-side metric rules,
the traffic generator, and the lookup of cells, mixes and metric readers
by name."""
import json
import os
import shutil
import types
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import run as R  # noqa: E402
from bench.lib import arch, flops, stats, traffic  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return arch.bind(json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                                .read_text()))


# --- FLOPs and bytes --------------------------------------------------------
def test_decode_cost_qwen_by_hand():
    c = cfg("qwen2-1.5b")
    # one row, 100 tokens of context before the new one, 2 of its 7 blocks
    # held int8. Per layer: 12 heads x 128 dims x (100 + 1) keys x 4 flops;
    # 5 bf16 blocks x (K and V) x 16 x 2 x 128 x 2 B, 2 int8 blocks x
    # (16 x 2 x 128 x 2 B + 16 B of scales), q and out 2 x 12 x 128 x 2 B,
    # new k and v 2 x 2 x 128 x 2 B.
    f, b = flops.decode_attn_cost(c, [(100, 2)])
    assert f == 28 * 4 * 12 * 128 * 101
    assert b == 28 * (5 * 16384 + 2 * 8208 + 6144 + 1024)


def test_decode_cost_olmo_by_hand():
    c = cfg("olmo-1b")
    # 16 kv heads of 128: a bf16 block is 2 x 16 x 16 x 128 x 2 B = 131072
    f, b = flops.decode_attn_cost(c, [(32, 0), (17, 1)])
    assert f == 16 * 4 * 16 * 128 * (33 + 18)
    per_row_io = 2 * 16 * 128 * 2 * 2
    assert b == 16 * (2 * 131072 + per_row_io) \
        + 16 * (131072 + (65536 + 16) + per_row_io)


def test_chunk_cost_by_hand():
    c = cfg("qwen2-1.5b")
    # a 256-token chunk after 512 tokens of context, none of it int8
    f, b = flops.chunk_attn_cost(c, [(512, 256, 0)])
    assert f == 28 * 4 * 12 * 128 * (256 * 512 + 256 * 257 // 2)
    assert b == 28 * (32 * 16384 + 256 * 2 * (2 * 12 * 128 + 2 * 2 * 128))
    c = cfg("olmo-1b")
    f, b = flops.chunk_attn_cost(c, [(0, 64, 0)])
    assert f == 16 * 4 * 16 * 128 * (64 * 65 // 2)
    assert b == 16 * 64 * 2 * (2 * 16 * 128 + 2 * 16 * 128)


def test_step_flops_by_hand():
    c = cfg("olmo-1b")
    layer = 2048 * 3 * 2048 + 2048 * 2048 + 3 * 2048 * 8192
    head = 2048 * 50304
    assert flops.matmul_params(c) == 16 * layer + head
    got = flops.step_flops(c, [10], [(0, 4)], logits_rows=2)
    want = (2 * 16 * layer * 5 + 2 * head * 2
            + 16 * 4 * 16 * 128 * (10 + 1) + 16 * 4 * 16 * 128 * 10)
    assert got == want


def test_step_mfu_over_all_steps_and_prefill_steps():
    c = cfg("olmo-1b")
    pk = {"bf16_flops": 1e12}

    def step(t0, chunks, rows):
        return types.SimpleNamespace(t0=t0, t1=t0 + 0.5, decode_ctx=[(10, 0)],
                                     chunks=chunks, wholes=[],
                                     logits_rows=rows)

    ctx = types.SimpleNamespace(cfg=c, peaks=pk, flops=flops,
                                steps=[step(0.0, [], 1),
                                       step(0.5, [(0, 4, 0)], 2)])
    f_dec = flops.step_flops(c, [10], [], 1)
    f_pre = flops.step_flops(c, [10], [(0, 4)], 2)
    got = {n: R.load_reader(n)(ctx)
           for n in ("step.mfu", "step.mfu.prefill")}
    assert got["step.mfu"] == pytest.approx(100 * (f_dec + f_pre) / 1e12)
    assert got["step.mfu.prefill"] == pytest.approx(100 * f_pre / 0.5e12)


def test_relief_readers_weight_levels_by_wall_time_and_count_events():
    def step(t0, dt, level, events):
        return types.SimpleNamespace(t0=t0, t1=t0 + dt, level=level,
                                     events=events)

    ctx = types.SimpleNamespace(steps=[step(0.0, 1.0, 0, 1),
                                       step(1.0, 3.0, 8, 0),
                                       step(4.0, 1.0, 4, 2)])
    got = {n: R.load_reader(n)(ctx)
           for n in ("relief.level_mean", "relief.events")}
    assert got["relief.level_mean"] == pytest.approx((0 + 24 + 4) / 5.0)
    assert got["relief.events"] == 3
    empty = types.SimpleNamespace(steps=[])
    assert R.load_reader("relief.level_mean")(empty) is None
    assert R.load_reader("relief.events")(empty) is None


def test_roofline_takes_the_larger_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000, 50, pk) == 10.0
    assert flops.roofline_s(100, 50, pk) == 5.0


# --- client-side metrics ----------------------------------------------------
def track(due, stamps, n, state="FINISHED"):
    return types.SimpleNamespace(due_s=due, stamps=stamps, max_new_tokens=n,
                                 outcome=state, left_queue_s=None)


def test_ttft_from_due_time_and_censoring():
    t = track(10.0, [10.5, 10.6], 2)
    assert stats.ttft_s(t, 20.0) == pytest.approx(0.5)
    waiting = track(18.0, [], 4, "QUEUED")
    assert stats.ttft_s(waiting, 20.0) == pytest.approx(2.0)
    late = track(19.0, [20.5], 4, "RUNNING")      # first token after the end
    assert stats.ttft_s(late, 20.0) == pytest.approx(1.0)


def test_attainment_rules():
    cell = {"ttft_limit_s": 2.0, "gap_limit_ms": 100.0}
    v = lambda t: stats.slo_verdict(t, 30.0, **{  # noqa: E731
        "ttft_limit_s": 2.0, "gap_limit_ms": 100.0})
    assert v(track(10.0, [11.0, 11.05, 11.1], 3)) is True
    assert v(track(10.0, [12.5, 12.55, 12.6], 3)) is False      # late TTFT
    assert v(track(10.0, [11.0, 11.5, 12.0], 3)) is False       # slow gaps
    assert v(track(10.0, [], 3, "FAILED")) is False
    assert v(track(10.0, [], 3, "SHED")) is False
    assert v(track(28.5, [], 3, "QUEUED")) is None              # undecided
    assert v(track(20.0, [], 3, "QUEUED")) is False             # waited 10 s
    assert v(track(25.0, [25.5, 25.6], 9, "RUNNING")) is None   # decoding
    m = stats.end_to_end([track(10.0, [11.0, 11.05, 11.1], 3),
                          track(12.0, [], 3, "FAILED"),
                          track(5.0, [10.5, 10.6], 2)], 10.0, 30.0, cell)
    assert m["n_due"] == 2 and m["n_failed"] == 1
    assert m["slo_attainment"] == 50.0
    # tokens emitted in the window: 3 + 2; gaps both of whose tokens are in
    assert m["output_tok_per_s"] == pytest.approx(5 / 20.0)
    assert m["n_gaps"] == 3
    assert m["itl_p99_ms"] == pytest.approx(100.0)  # gaps 50, 50, 100 ms
    assert m["ttft_p95_s"] == pytest.approx(30.0 - 12.0)  # censored


def test_nearest_rank():
    assert stats.nearest_rank(range(1, 101), 0.95) == 95
    assert stats.nearest_rank([3.0], 0.95) == 3.0
    assert stats.nearest_rank([], 0.95) is None


# --- traffic ----------------------------------------------------------------
# a BurstGPT-like mix: Poisson base, bursts of Gamma(3, 4) within 0.8 s
MIX = {"shape_seed": 7,
       "burst": {"prob": 0.08, "shape": 3.0, "scale": 4.0, "span_s": 0.8},
       "prompt": {"median": 512, "sigma": 0.7, "min": 16, "max": 1536},
       "output": {"median": 192, "sigma": 0.6, "min": 8, "max": 512}}


def test_traffic_same_seed_same_requests():
    a = traffic.schedule(MIX, 2.0, 30.0, 2**33 + 7, 1000)
    b = traffic.schedule(MIX, 2.0, 30.0, 2**33 + 7, 1000)
    assert a == b and len(a) > 20


def test_traffic_seeds_differ_but_offer_the_same_work():
    a = traffic.schedule(MIX, 2.0, 30.0, 1, 1000)
    b = traffic.schedule(MIX, 2.0, 30.0, 2, 1000)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    for x in a:
        assert MIX["prompt"]["min"] <= len(x.prompt) <= MIX["prompt"]["max"]
        assert 0.0 <= x.due_s < 30.0


def test_lengths_are_restricted_not_clipped():
    """Lengths come from the lognormal restricted to the mix's range: none
    piles up on its ends."""
    spec = {"median": 512, "sigma": 0.8, "min": 272, "max": 2048}
    x = traffic._lengths(np.random.default_rng(0), 20000, spec)
    assert x.min() >= 272 and x.max() <= 2048
    # clipping would put ~21% on 272 and ~4% on 2048
    assert (x == 272).mean() < 0.005 and (x == 2048).mean() < 0.005
    assert 700 < x.mean() < 760


def test_burst_mix_mean_rate():
    # Poisson base plus 0.08 x Gamma(3, 4) = 0.96 more per arrival
    assert traffic.mean_rate(MIX, 1.0) == pytest.approx(1.96)
    n = len(traffic.arrival_times(MIX, 1.0, 5000.0,
                                  np.random.default_rng(0)))
    assert n / 5000.0 == pytest.approx(1.96, rel=0.1)


# --- found by name ----------------------------------------------------------
def test_new_cell_and_metric_are_found_by_name(tmp_path):
    """A cell, a mix and a per-layer metric added as files plus
    BENCHMARK.json entries are found and run, with no edit to the code."""
    root = tmp_path / "tree"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen2-1.5b.tiny",
                               "config": "qwen2-1.5b",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "test.requests_due", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "itl_p99_ms",
                               "workloads": ["qwen2-1.5b.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "cells" / "qwen2-1.5b.tiny.json").write_text(
        json.dumps({"rate_rps": 1.0}))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"shape_seed": 3, "prompt": {"median": 8, "sigma": 0.1, "min": 4,
                                     "max": 16},
         "output": {"median": 4, "sigma": 0.1, "min": 2, "max": 8}}))
    (root / "bench" / "metrics" / "test.requests_due.py").write_text(
        "def read(ctx):\n"
        "    return len(ctx.stats.due_in(ctx.window.tracks, ctx.start,"
        " ctx.end))\n")
    spec = R.load_spec("qwen2-1.5b.tiny", root)
    assert spec["cfg"]["name"] == "qwen2-1.5b"
    assert spec["mix"]["shape_seed"] == 3
    assert {m["name"] for m in spec["end_to_end"]} == {
        "itl_p99_ms", "output_tok_per_s", "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == ["test.requests_due"]
    arr = traffic.schedule(spec["mix"], spec["cell"]["rate_rps"], 10.0, 5, 50)
    ctx = types.SimpleNamespace(
        stats=stats, start=2.0, end=10.0,
        window=types.SimpleNamespace(tracks=arr))
    got = R.per_layer_values(spec, ctx, root)
    want = sum(1 for a in arr if 2.0 <= a.due_s < 10.0)
    assert got == {"test.requests_due": {"value": float(want),
                                         "unit": "requests"}}


def test_every_listed_metric_has_a_reader_and_every_cell_a_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        spec = R.load_spec(w["name"])
        assert spec["per_layer"], w["name"]
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
