"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, time
per device operation and per kernel, and idle gaps by host span.

Device planes are named ``/device:TPU:<n>``; their operations are the
events of the line ``XLA Ops``. Host spans are the events of the host
plane whose names the harness gives its own calls (``bench.submit``,
``engine.step``, ``bench.block_until_ready``, ``bench.wait_arrival``).
Events carry absolute start times in ns on one clock.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, List, Tuple

HOST_SPANS = ("bench.submit", "engine.step", "bench.block_until_ready",
              "bench.wait_arrival")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _events(plane, line_name=None) -> Iterable[Tuple[str, int, int]]:
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for e in line.events:
            yield e.name, int(e.start_ns), int(e.duration_ns)


def short_name(op: str) -> str:
    """``%paged_decode_attention.1 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention``: the instruction's name without the
    suffixes the compiler adds (``.3``, ``.29.remat2``)."""
    return op.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]


def union_ns(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_planes(planes, t0_ns: int = None, t1_ns: int = None) -> dict:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` of ``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them. The window is [t0, t1] in ns,
    defaulting to the extent of the harness's host spans (the traced part
    of the serving loop), or else of the device activity."""
    host = sorted((s, s + d, n) for p in planes
                  if not p.name.startswith("/device")
                  for n, s, d in _events(p) if n in HOST_SPANS)
    if host and t0_ns is None:
        t0_ns = host[0][0]
    if host and t1_ns is None:
        t1_ns = max(e for _, e, _ in host)
    dev = [p for p in planes if p.name.startswith("/device:TPU:")]
    dev = [p for p in dev if any(l.name == OPS_LINE for l in p.lines)]
    if not dev:
        raise ValueError("the trace holds no TPU operations")
    ops: Dict[str, float] = {}
    busy_s, chips = 0.0, 0
    all_iv: List[Tuple[int, int]] = []
    for p in dev:
        iv = []
        for name, s, d in _events(p, OPS_LINE):
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + d * 1e-9
            iv.append((s, s + d))
        if not iv:
            continue
        chips += 1
        lo = min(a for a, _ in iv) if t0_ns is None else t0_ns
        hi = max(b for _, b in iv) if t1_ns is None else t1_ns
        merged = union_ns([(max(a, lo), min(b, hi)) for a, b in iv
                           if b > lo and a < hi])
        busy_s += sum(b - a for a, b in merged) * 1e-9
        all_iv.extend(merged)
    if chips == 0:
        raise ValueError("the trace holds no TPU operations")
    merged = union_ns(all_iv)
    lo = merged[0][0] if t0_ns is None else t0_ns
    hi = merged[-1][1] if t1_ns is None else t1_ns
    starts = [h[0] for h in host]
    gaps = []
    prev = lo
    for a, b in merged + [(hi, hi)]:
        if a > prev:
            mid = (prev + a) // 2
            k = bisect.bisect_right(starts, mid)
            what = [n for s, e, n in host[max(0, k - 8):k] if s <= mid < e]
            gaps.append((what[-1] if what else "no host span",
                         (a - prev) * 1e-9))
        prev = max(prev, b)
    return {"busy_s": busy_s / chips, "window_s": (hi - lo) * 1e-9,
            "ops": ops, "gaps": gaps, "chips": chips}


def kernel_seconds(ops: Dict[str, float], kernel: str) -> float:
    """Device seconds of the operations named ``kernel`` (a Pallas call's
    ``name``)."""
    return ops.get(kernel, 0.0)


def breakdown(red: dict, top: int = 10):
    """The result line's ``breakdown`` (the device operations that took
    most time, the longest idle gaps named by the host span they fell in)
    and the idle seconds summed per host span."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    by_span: Dict[str, float] = {}
    for name, s in red["gaps"]:
        by_span[name] = by_span.get(name, 0.0) + s
    gaps = sorted(red["gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}, by_span


Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Event = collections.namedtuple("Event", "name start_ns duration_ns")


def load(path: str) -> List[Plane]:
    """The trace's planes as plain lists (``ProfileData`` iterates once)."""
    from jax.profiler import ProfileData
    return [Plane(p.name, [Line(l.name, [Event(e.name, e.start_ns,
                                               e.duration_ns)
                                         for e in l.events])
                           for l in p.lines])
            for p in ProfileData.from_file(path).planes]
