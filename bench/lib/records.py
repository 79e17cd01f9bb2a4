"""The program's own records, as the per-layer readers reach them through
``ctx.window.eng``: the host spans on each step's ``Telemetry``
(``repro.engine.spans``), the requests' wall stamps and the process's
compile log, all on ``time.perf_counter``. A program that keeps none of
them gives None, and the metrics that read them are left out."""
from __future__ import annotations

from typing import List, Optional, Tuple

# compile phases (``repro.engine.spans.PHASES``) by what they cost
TRACE_PHASES = ("trace", "lower")       # Python tracing, jaxpr -> MLIR
COMPILE_PHASES = ("compile",)           # backend compile or cache load


def step_spans(ctx) -> Optional[List[Tuple[object, object]]]:
    """(the harness's step record, the program's ``StepSpans``) for every
    step the window's harness ran: the engine's last Telemetry records,
    one per ``engine.step`` call."""
    steps, hist = ctx.window.steps, ctx.window.eng.monitor.history
    if not steps or len(hist) < len(steps):
        return None
    recs = [getattr(t, "spans", None) for t in hist[len(hist) - len(steps):]]
    if any(r is None for r in recs):
        return None
    return list(zip(steps, recs))


def schedule_start(pairs) -> float:
    """The ``perf_counter`` reading of the harness's time 0: each step is
    stamped just before the program's ``serve.step`` opens."""
    offs = sorted(sp.start_s - st.t0 for st, sp in pairs)
    if offs[len(offs) // 2] - offs[0] > 0.005:
        raise ValueError("the engine's Telemetry records are not the "
                         "harness's steps")
    return offs[0]


def profiler_start(ctx, pairs) -> float:
    """``perf_counter`` reading by which the profiler had not started: the
    end of the program's last step before the first traced one (the
    harness starts the profiler between steps, and that start stalls the
    loop); the window's end where no step was traced."""
    if ctx.traced_steps:
        first = ctx.traced_steps[0]
        before = [sp for st, sp in pairs if st.t1 <= first.t0]
        if before:
            return before[-1].end_s
    return schedule_start(pairs) + ctx.end


def host_exposed_s(sp) -> float:
    """Host seconds of a step with no device work in flight: from the
    step's start to its first ``exec.*`` call, plus from the end of its
    last ``serve.readback`` to the step's end; a step with no device call
    counts whole."""
    if sp.device_first_s is None:
        return sp.end_s - sp.start_s
    tail = sp.end_s - sp.device_wait_end_s \
        if sp.device_wait_end_s is not None else 0.0
    return sp.device_first_s - sp.start_s + tail


def compile_seconds(ctx, phases) -> Optional[float]:
    """Seconds of the compile ``phases`` that ended before the window."""
    pairs = step_spans(ctx)
    log = getattr(ctx.window.eng, "compile_log", None)
    if pairs is None or log is None:
        return None
    before = schedule_start(pairs) + ctx.start
    return sum(secs for t, _fam, phase, secs in log
               if t < before and phase in phases)
