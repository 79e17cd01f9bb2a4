#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell ``bench/cells/<cell>.json``
(configuration, traffic, rate, limits), its configuration file (named in
``BENCHMARK.json``), the architecture module ``bench/arch/<module>.py`` that
file names, the traffic mix ``bench/traffic/<mix>.json`` and, with
``--trace 1``, one reader ``bench/metrics/<metric>.py`` per per-layer metric.

The run makes the weights from the seed on the chip, builds the engine with
the chip's memory as its budget, compiles and loads every step program the
cell's traffic can reach, starts the open-loop traffic ``preroll_s`` before
the window, serves for ``--seconds`` on the wall clock, then frees the
engine and checks a sample of the served tokens against the plain float32
reference. Lines on standard error are diagnostics; the last of them are the
numbers compared with their limits. The last line on standard output is one
JSON object. Without a TPU (or with fewer chips than the cell asks for) the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                                        # noqa: E402
import gc                                              # noqa: E402
import importlib.util                                  # noqa: E402
import json                                            # noqa: E402
import shutil                                          # noqa: E402
import sys                                             # noqa: E402
import types                                           # noqa: E402
from pathlib import Path                               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import arch                             # noqa: E402

# the persistent compilation cache lives inside the checkout, at a fixed path
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / "bench_out"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the benchmark's data, found by name
# ---------------------------------------------------------------------------
def load_spec(workload: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = arch.bind(json.loads((root / configs[w["config"]]["file"])
                               .read_text()), root)
    cell = json.loads((root / "bench" / "cells" / f"{workload}.json")
                      .read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in reported]
    return {"bench": bench, "workload": w, "cfg": cfg, "cell": cell,
            "mix": mix, "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bench["run_seconds"]}


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_values(spec: dict, ctx, root: Path = ROOT) -> dict:
    """Each per-layer metric's reader on ``ctx``; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
def chip(chips: int):
    """The devices to run on; raises :class:`NoChip` unless JAX sees at
    least ``chips`` TPUs whose kind has published peaks."""
    import jax
    from bench.lib.peaks import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform} devices only")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU devices, the cell asks for {chips}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def setup(spec: dict, seed: int, device, tamper=None):
    """Weights from ``seed`` on ``device``, the engine sized to the chip,
    every step program the cell's mix can reach run once (traced, and
    compiled or loaded from the persistent cache), and the warm-up replay
    (see :func:`warm_up`).
    Returns (engine, timings, sizing)."""
    import jax
    from bench.lib import system, weights
    cfg, cell, mix = spec["cfg"], spec["cell"], spec["mix"]
    name = spec["workload"]["name"]
    parts = {}
    t = time.perf_counter()
    w = weights.make(cfg, seed, device=device)
    jax.block_until_ready(w)
    parts["init_s"] = time.perf_counter() - t
    limit = (device.memory_stats() or {}).get("bytes_limit") \
        or cell.get("bytes_limit")
    size = system.size_budget(cfg, limit)
    t = time.perf_counter()
    params = cfg["arch"].program_params(cfg, w)
    system.check_layout(cfg, params)
    eng = system.build_engine(cfg, cell, params, size["hbm_budget_bytes"])
    del w, params
    jax.block_until_ready(eng.pool.k)
    parts["engine_s"] = time.perf_counter() - t
    if eng.pool.capacity != size["capacity"]:
        raise RuntimeError(f"pool capacity {eng.pool.capacity}, sized for "
                           f"{size['capacity']}")
    if tamper is not None:
        tamper(eng)
    t = time.perf_counter()
    parts["programs"] = system.warm_programs(
        eng, system.program_shapes(eng, mix), system.reachable_levels(eng))
    parts["programs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = warm_up(eng, spec, seed)
    parts["warm_s"] = time.perf_counter() - t
    mem = device.memory_stats() or {}
    log(f"[{name}] set-up: init {parts['init_s']:.3f} s, engine (swap plan, "
        f"pool) {parts['engine_s']:.3f} s, {parts['programs']} step program "
        f"calls (traced, compiled or loaded, run once) "
        f"{parts['programs_s']:.3f} s, warm-up replay {parts['warm_s']:.3f} s "
        f"({len(warm.steps)} steps, {warm.compiles} compiles); budget "
        f"{size['hbm_budget_bytes']} B, pool {eng.pool.capacity} blocks of "
        f"{size['block_bytes']} B ({2 * eng.pool.k.nbytes} B), "
        f"bytes_in_use {mem.get('bytes_in_use')}, "
        f"peak {mem.get('peak_bytes_in_use')}, limit {limit}")
    return eng, parts, size


# the warm-up replay's seed differs from the run's own
WARM_SALT = 0x5EED


def warm_up(eng, spec: dict, seed: int):
    """Serve the cell's own mix at its rate for ``warm_s`` from another
    seed, so that the small host-side programs the traffic reaches (table
    conversions, argmax, the int8 tier's block updates) are compiled or
    loaded before the window; then hand back every request it left in the
    engine. Returns its ``Window``."""
    from bench.lib import traffic, window
    cell = spec["cell"]
    arr = traffic.schedule(spec["mix"], cell["rate_rps"], cell["warm_s"],
                           seed ^ WARM_SALT, spec["cfg"]["vocab_size"])
    win = window.Window(eng, arr)
    win.run(time.perf_counter(), 0.0, cell["warm_s"])
    eng.release_queued()
    for r in list(eng.running):
        eng.detach_request(r)
    win.eng = None
    return win


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             device, control: bool = False, out_dir: Path = OUT_DIR,
             root: Path = ROOT, t_process: float = None,
             tamper=None) -> dict:
    """One run of one cell on ``device``. With ``control`` (calibration of
    the limit only), the lower-precision control takes the program's place
    in the comparison, so ``correct`` should come out false; ``tamper``
    (tests only) is called on the engine after it is built."""
    import jax
    from bench.lib import correct, flops, peaks, reference, stats
    from bench.lib import traffic, weights, window
    from bench.lib import trace as tr

    t_process = T_PROCESS if t_process is None else t_process
    cfg, cell, mix = spec["cfg"], spec["cell"], spec["mix"]
    name = spec["workload"]["name"]
    pk = peaks.peaks_for(device.device_kind) if device.platform == "tpu" \
        else None
    eng, parts, size = setup(spec, seed, device, tamper)

    # --- the window ------------------------------------------------------------
    preroll, window_s = cell["preroll_s"], float(seconds)
    horizon = preroll + window_s
    arrivals = traffic.schedule(mix, cell["rate_rps"], horizon, seed,
                                cfg["vocab_size"])
    win = window.Window(eng, arrivals, detail=trace)
    trace_dir = out_dir / f"trace-{name}-{seed}"
    span = {}

    def on_tick(now):
        if not trace:
            return
        t_on = horizon - cell.get("trace_s", 3.0)
        if "a" not in span and now >= t_on:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            span["a"] = time.perf_counter()
            span["a_rel"] = now

    t_sched = time.perf_counter()
    setup_s = t_sched - t_process + preroll
    end_rel = win.run(t_sched, preroll, window_s, on_tick)
    if trace:
        span["b"] = time.perf_counter()
        span["b_rel"] = span["b"] - t_sched
        jax.profiler.stop_trace()
    mem = device.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    start, end = preroll, preroll + window_s
    e2e = stats.end_to_end(win.tracks, start, end, cell)
    in_window = [s for s in win.steps if start <= s.t0 < end]
    slow = sorted(in_window, key=lambda s: s.t0 - s.t1)[:3]
    log(f"[{name}] slowest steps in the window (s, compiles): "
        + ", ".join(f"{s.t1 - s.t0:.3f} at {s.t0:.3f} ({s.compiles})"
                    for s in slow))
    qs = (0.5, 0.9, 0.95, 0.99)
    step_ms = [1e3 * (s.t1 - s.t0) for s in in_window]
    gap_ms = [1e3 * g for g in stats.gaps_in(win.tracks, start, end)]
    log(f"[{name}] step ms at p50/p90/p95/p99 "
        f"{[stats.nearest_rank(step_ms, q) for q in qs]}; inter-token gap ms "
        f"{[stats.nearest_rank(gap_ms, q) for q in qs]}")
    # the pool's usage over the window's steps: the engine's Telemetry, one
    # record per step
    mark = eng.sc.kv_pressure_high
    hist = eng.monitor.history[len(eng.monitor.history) - len(win.steps):]
    high_s = sum(s.t1 - s.t0 for s, t in zip(win.steps, hist)
                 if start <= s.t0 < end and t.kv_usage >= mark)
    log(f"[{name}] KV usage at or over {mark} of the pool for {high_s:.3f} "
        f"of {sum(s.t1 - s.t0 for s in in_window):.3f} step seconds in the "
        f"window")
    log(f"[{name}] window: {e2e['n_due']} requests due, {e2e['n_failed']} "
        f"failed, {e2e['n_gaps']} gaps, {e2e['n_decided']} decided for "
        f"attainment; generator at most {win.max_late_s * 1e3:.3f} ms late; "
        f"{len(win.steps)} steps, {win.compiles} compiles during serving; "
        f"engine clock ahead of the wall by {eng.now - end_rel:.3f} s; "
        f"levels (wall s) {win.level_time}; peak_bytes_in_use {peak}")

    result = {"correct": False, "attempted": e2e["n_due"],
              "failed": e2e["n_failed"], "metrics": {},
              "device": {"platform": device.platform,
                         "kind": device.device_kind, "count": 1,
                         "memory_peak_bytes": peak}}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    if not trace:
        vals = dict(e2e, setup_s=setup_s)
        for m in spec["end_to_end"]:
            if vals.get(m["name"]) is None:
                raise RuntimeError(f"no value for {m['name']}")
            result["metrics"][m["name"]] = {"value": vals[m["name"]],
                                            "unit": units[m["name"]]}
    else:
        red = tr.reduce_planes(tr.load(tr.find_xplane(str(trace_dir))))
        shutil.rmtree(trace_dir, ignore_errors=True)
        bd, by_span = tr.breakdown(red)
        in_win = [s for s in win.steps if s.t0 >= start and s.t1 <= end]
        traced = [s for s in win.steps
                  if s.t0 >= span["a_rel"] and s.t1 <= span["b_rel"]]
        ctx = types.SimpleNamespace(
            cfg=cfg, cell=cell, peaks=pk, window=win, start=start, end=end,
            steps=in_win, traced_steps=traced, trace=red, flops=flops,
            stats=stats)
        result["metrics"] = per_layer_values(spec, ctx, root)
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = bd
        log(f"[{name}] trace: {span['b'] - span['a']:.3f} s traced, "
            f"{len(traced)} steps, device busy {red['busy_s']:.6f} s of "
            f"{red['window_s']:.6f} s; idle by host span {by_span}")

    # --- correct: the reference, once the program is gone ---------------------
    picked_n = cell["check_tokens"]
    picked = correct.sample(win.tracks, seed, picked_n, cell["check_requests"])
    for t_ in win.tracks:
        if t_.req is not None:
            t_.req.block_ids = []
    win.eng = None
    del eng
    gc.collect()
    t = time.perf_counter()
    w = weights.make(cfg, seed, device=device)
    with jax.default_matmul_precision("highest"):
        ref = reference.Reference(cfg, w, "exact", cell["max_seq_len"])
        got = correct.widest_gap(ref, picked)
        if control:
            log(f"[{name}] the program's own logit_gap {got['gap']!r} "
                f"(mean {got['mean_gap']!r}); the control takes its place")
            got = correct.widest_gap(ref, picked, reference.Reference(
                cfg, w, "control", cell["max_seq_len"]))
    check_s = time.perf_counter() - t
    limit_gap = cell["correct_gap_limit"]
    ok = got["requests"] > 0 and got["gap"] <= limit_gap
    result["correct"] = bool(ok)
    checks = {"logit_gap": {"value": got["gap"], "limit": limit_gap}}
    log(f"[{name}] reference: {got['requests']} requests, {got['tokens']} "
        f"served tokens, levels {got['levels']}, {got['int8_blocks']} int8 "
        f"blocks, mean gap {got['mean_gap']!r}, {check_s:.3f} s")
    for k, v in checks.items():
        log(f"{k} {v['value']!r} limit {v['limit']!r}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the lower-precision control in the "
                         "program's place (calibration of the limit)")
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        enable_cache()
        devs = chip(spec["workload"]["chips"])
    except (NoChip, KeyError, FileNotFoundError, ImportError) as e:
        log(f"bench: {e}")
        return 2
    res = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   device=devs[0], control=bool(args.control))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
