"""Host spans inside the serving step, on the profiler's clock.

``span(name, **args)`` enters ``jax.profiler.TraceAnnotation(name, **args)``:
under a profiler session the span lands on the trace's host plane, on the
clock of the device's ``XLA Ops``; with no session an annotation costs well
under a microsecond, so there is no switch. The span also adds its
``time.perf_counter`` duration less that of the spans opened inside it (its
self time) to the record of the step in flight: the outermost span opens a
new :class:`StepSpans`, which the engine hangs on that step's ``Telemetry``.

Names: ``serve.*`` inside ``MorphServeEngine.step``, ``exec.*`` around each
jitted step-program call of ``ModelExec`` (dispatch only), ``relief.*``
where the relief ladder acts.

``watch_compiles`` registers one process-wide ``jax.monitoring`` listener
that books JAX's compile phases to the program family of the ``exec.*`` span
in flight (``other`` outside one) in :data:`COMPILE_LOG`.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

DEVICE_CALL = "exec."           # a step-program call: starts device work
READBACK = "serve.readback"     # a blocking device -> host read


class StepSpans:
    """One step's host time (``perf_counter`` seconds): its start and end,
    the self seconds of each span name, the start of its first ``exec.*``
    call and the end of its last ``serve.readback`` (None without one)."""
    __slots__ = ("start_s", "end_s", "self_s", "device_first_s",
                 "device_wait_end_s")

    def __init__(self, start_s: float):
        self.start_s = start_s
        self.end_s: Optional[float] = None
        self.self_s: Dict[str, float] = {}
        self.device_first_s: Optional[float] = None
        self.device_wait_end_s: Optional[float] = None


_open: List["span"] = []                 # the spans open now, outermost first
_step: Optional[StepSpans] = None        # the outermost span's record


class span:
    """``with span(name, **args) as rec``: ``rec`` is the record of the
    outermost span open (this one's, if none was)."""
    __slots__ = ("name", "ann", "t0", "child_s")

    def __init__(self, name: str, **args):
        self.name = name
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self) -> StepSpans:
        global _step
        self.ann.__enter__()
        self.child_s = 0.0
        self.t0 = t = perf_counter()
        if not _open:
            _step = StepSpans(t)
        elif (_step.device_first_s is None
              and self.name.startswith(DEVICE_CALL)):
            _step.device_first_s = t
        _open.append(self)
        return _step

    def __exit__(self, *exc) -> None:
        t = perf_counter()
        _open.pop()
        d = t - self.t0
        rec = _step
        rec.self_s[self.name] = (rec.self_s.get(self.name, 0.0)
                                 + d - self.child_s)
        if _open:
            _open[-1].child_s += d
        else:
            rec.end_s = t
        if self.name == READBACK:
            rec.device_wait_end_s = t
        self.ann.__exit__(*exc)


# ---------------------------------------------------------------------------
# compile phases per program family
# ---------------------------------------------------------------------------
PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          # wraps compile_or_get_cached: a persistent-cache load fires it too
          "/jax/core/compile/backend_compile_duration": "compile",
          "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}

# (perf_counter at the phase's end, family, phase, seconds), in order
COMPILE_LOG: List[Tuple[float, str, str, float]] = []
_watching = False


def _family() -> str:
    for s in reversed(_open):
        if s.name.startswith(DEVICE_CALL):
            return s.name[len(DEVICE_CALL):]
    return "other"


def _on_duration(event: str, secs: float, **_kw) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    t = perf_counter()
    # a jit traced while an outer program traces or lowers reports its own
    # trace, inside the outer phase's time: keep the outer phase only
    while (COMPILE_LOG and COMPILE_LOG[-1][2] == "trace"
           and COMPILE_LOG[-1][0] - COMPILE_LOG[-1][3] >= t - secs):
        COMPILE_LOG.pop()
    COMPILE_LOG.append((t, _family(), phase, secs))


def watch_compiles() -> None:
    """Register the compile-phase listener (once per process)."""
    global _watching
    if not _watching:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True

