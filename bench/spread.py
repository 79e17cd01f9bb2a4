#!/usr/bin/env python3
"""Run one cell several times, one process after another, and report the
spread of each metric.

    python3 bench/spread.py --workload <cell> --seeds 11,12,13 --seconds <s>
        [--trace 0|1] [--control 0|1] [--sets 2] --out <dir>

Each run is ``bench/run.py`` in a process of its own (a chip belongs to one
process at a time). ``--sets 2`` runs the seeds in order, then the same
seeds again. Each run's last line of standard output goes to
``<out>/<cell>-<seed>-<set>.json`` and its standard error to ``.err``
beside it. After each run one line is printed: seed, exit code, wall
seconds and the result. At the end one JSON line per set gives, for each
metric, the values in seed order, the median and the spread: the distance
between the first and the third quartile (``statistics.quantiles(values,
n=4)``) as a share of the median. The benchmark's runs do not use this;
the bounds in ``BENCHMARK.json`` are set from what it prints.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values):
    """Interquartile distance over the median; None below three values."""
    if len(values) < 3:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med) if med else None


def summary(results) -> dict:
    names = sorted({k for r in results for k in r.get("metrics", {})})
    out = {}
    for n in names:
        v = [r["metrics"][n]["value"] for r in results if n in r["metrics"]]
        out[n] = {"values": v, "median": statistics.median(v),
                  "spread": spread(v)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    for k in range(1, args.sets + 1):
        results = []
        for seed in seeds:
            cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", args.seconds, "--trace", str(args.trace),
                   "--control", str(args.control)]
            stem = out / f"{args.workload}-{seed}-{k}"
            t = time.perf_counter()
            with open(f"{stem}.err", "w") as err:
                p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                   stderr=err, text=True)
            wall = time.perf_counter() - t
            lines = p.stdout.strip().splitlines()
            res = json.loads(lines[-1]) if p.returncode == 0 and lines \
                else {}
            Path(f"{stem}.json").write_text(json.dumps(res) + "\n")
            print(json.dumps({"set": k, "seed": seed, "rc": p.returncode,
                              "wall_s": wall, "result": res}), flush=True)
            if res:
                results.append(res)
        print(json.dumps({"set": k, "runs": len(results),
                          "correct": [r["correct"] for r in results],
                          "metrics": summary(results)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
