"""Scheduler: 95th percentile (nearest rank) of the program's own wait from
submit to the first slot (``admit_wall_s - submit_wall_s``), over the
requests due in the window and submitted before the profiler started; one
not admitted by then counts at its wait so far, so the profiler's start
stall stays out. Requests the engine refused without a slot are left out.
Moves ``ttft_p95_s``."""
from bench.lib import records


def read(ctx):
    pairs = records.step_spans(ctx)
    if pairs is None:
        return None
    cut = records.profiler_start(ctx, pairs)
    waits = []
    for tr in ctx.stats.due_in(ctx.window.tracks, ctx.start, ctx.end):
        r = tr.req
        sub = getattr(r, "submit_wall_s", None)
        if sub is None or sub >= cut:
            continue
        if r.admit_wall_s is None and r.state.name in ("FAILED", "SHED"):
            continue
        adm = cut if r.admit_wall_s is None else min(r.admit_wall_s, cut)
        waits.append(adm - sub)
    return ctx.stats.nearest_rank(waits, 0.95)
