#!/usr/bin/env python3
"""Find the knee of a cell's mix: the highest rate the served path sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,6,8

One process builds the cell's engine once, then offers the cell's mix at
each base rate in turn for ``--seconds`` and drains the engine before the
next. Each rate prints one JSON line: the offered and completed output
tokens per second, the TTFT tail, and the backlog left at the end. A rate
is sustained while completed tokens keep up with offered ones and the
backlog does not grow. The benchmark's runs do not use this; a cell's
``rate_rps`` is set from it once, when the cell is defined.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--poisson", type=int, choices=(0, 1), default=0,
                    help="drop the mix's bursts: plain Poisson arrivals")
    args = ap.parse_args(argv)
    spec = R.load_spec(args.workload)
    R.enable_cache()
    try:
        dev = R.chip(spec["workload"]["chips"])[0]
    except R.NoChip as e:
        R.log(f"sweep: {e}")
        return 2
    from bench.lib import stats, traffic, window
    spec["cell"] = dict(spec["cell"], warm_s=0.0)
    eng, _, _ = R.setup(spec, args.seed, dev)
    mix, cell, cfg = spec["mix"], spec["cell"], spec["cfg"]
    if args.poisson:
        mix = {k: v for k, v in mix.items() if k != "burst"}
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        arrivals = traffic.schedule(mix, rate, args.seconds, args.seed + i,
                                    cfg["vocab_size"])
        win = window.Window(eng, arrivals, detail=True)
        t0 = time.perf_counter()
        win.run(t0, 0.0, args.seconds)
        start = 0.25 * args.seconds
        e2e = stats.end_to_end(win.tracks, start, args.seconds, cell)
        offered = sum(a.max_new_tokens for a in arrivals
                      if a.due_s >= start) / (args.seconds - start)
        waiting = [t for t in win.tracks if t.req is not None
                   and t.outcome in ("QUEUED", "PREEMPTED")]
        oldest = max((args.seconds - t.due_s for t in waiting), default=0.0)
        print(json.dumps({
            "rate_rps": rate, "mean_rps": traffic.mean_rate(mix, rate),
            "offered_tok_per_s": offered,
            "output_tok_per_s": e2e["output_tok_per_s"],
            "ttft_p95_s": e2e["ttft_p95_s"], "itl_p99_ms": e2e["itl_p99_ms"],
            "slo_attainment": e2e["slo_attainment"],
            "queued_at_end": len(waiting), "oldest_wait_s": oldest,
            "steps": len(win.steps), "compiles": win.compiles,
            "step_ms_p50": 1e3 * (stats.nearest_rank(
                [s.t1 - s.t0 for s in win.steps], 0.5) or 0.0),
            "decode_batch_mean": (sum(len(s.decode_ctx) for s in win.steps)
                                  / max(len(win.steps), 1)),
            "levels": win.level_time}), flush=True)
        t_drain = time.perf_counter()
        eng.release_queued()
        while eng.running \
                and time.perf_counter() - t_drain < 30.0:
            win._step(t0, time.perf_counter() - t0)
        for r in list(eng.running):
            eng.detach_request(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
