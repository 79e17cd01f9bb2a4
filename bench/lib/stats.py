"""End-to-end metric arithmetic on the client's wall-clock records.

All times are seconds since the schedule began. The window is
``[start, end)``; only requests due inside it count for latency and
attainment, and only tokens emitted inside it count for throughput and
inter-token gaps.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional

TERMINAL_MISS = ("FAILED", "SHED")


def nearest_rank(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-quantile (0..1) by nearest rank: the smallest value with
    at least ``q`` of the sample at or below it."""
    v = sorted(values)
    if not v:
        return None
    return v[max(math.ceil(q * len(v)) - 1, 0)]


def due_in(tracks, start: float, end: float) -> list:
    return [t for t in tracks if start <= t.due_s < end]


def ttft_s(tr, end: float) -> float:
    """First-token wall time minus due time; a request with no first token
    by ``end`` counts at its wait so far."""
    first = tr.stamps[0] if tr.stamps else None
    if first is None or first > end:
        return end - tr.due_s
    return first - tr.due_s


def gaps_in(tracks, start: float, end: float) -> List[float]:
    """Every gap between consecutive output tokens of one request where
    both tokens were emitted inside the window."""
    out = []
    for t in tracks:
        s = t.stamps
        out.extend(b - a for a, b in zip(s, s[1:]) if a >= start and b < end)
    return out


def tokens_in(tracks, start: float, end: float) -> int:
    return sum(1 for t in tracks for s in t.stamps if start <= s < end)


def slo_verdict(tr, end: float, ttft_limit_s: float,
                gap_limit_ms: float) -> Optional[bool]:
    """True: met both limits; False: missed; None: the window cannot tell.

    A failed, rejected or shed request misses. A request whose first token
    came after the TTFT limit, or whose wait at ``end`` already exceeds it,
    misses. A request that finished inside the window is judged on its
    TTFT and its mean inter-token gap. Any other (still decoding, or still
    waiting within the limit) is left out."""
    if tr.outcome in TERMINAL_MISS:
        return False
    if ttft_s(tr, end) > ttft_limit_s:
        return False
    done = (tr.outcome == "FINISHED" and len(tr.stamps) == tr.max_new_tokens
            and tr.stamps[-1] < end)
    if not done:
        return None
    s = tr.stamps
    mean_gap = (s[-1] - s[0]) / (len(s) - 1) if len(s) > 1 else 0.0
    return mean_gap * 1e3 <= gap_limit_ms


def end_to_end(tracks, start: float, end: float, cell: dict) -> dict:
    """The four client-side metrics of one run, with their sample sizes."""
    due = due_in(tracks, start, end)
    gaps = gaps_in(tracks, start, end)
    verdicts = [slo_verdict(t, end, cell["ttft_limit_s"], cell["gap_limit_ms"])
                for t in due]
    decided = [v for v in verdicts if v is not None]
    p99_gap = nearest_rank(gaps, 0.99)
    return {
        "ttft_p95_s": nearest_rank([ttft_s(t, end) for t in due], 0.95),
        "itl_p99_ms": None if p99_gap is None else p99_gap * 1e3,
        "output_tok_per_s": tokens_in(tracks, start, end) / (end - start),
        "slo_attainment": (100.0 * sum(decided) / len(decided)
                           if decided else None),
        "n_due": len(due), "n_gaps": len(gaps), "n_decided": len(decided),
        "n_failed": sum(t.outcome in TERMINAL_MISS for t in due),
    }
