"""The readers of the program's own records (``bench/lib/records.py``) on a
hand-built context: a stub engine with known host spans, request stamps and
compile log, values computed by hand. A program that keeps no such records
gives None for each."""
import types

import pytest

from bench import run as R
from bench.lib import stats

NAMES = ("loop.host_exposed_ms", "sched.admit_wait_p95_s", "setup.trace_s",
         "setup.compile_s")
T0 = 1000.0            # perf_counter reading of the schedule's time 0
D = 1e-5               # harness stamp -> serve.step entry, and exit -> stamp


def ns(**kw):
    return types.SimpleNamespace(**kw)


def step(t0, t1, first=None, wait_end=None):
    """A harness step record at [t0, t1] (schedule time) and the program's
    spans of the same step; ``first`` / ``wait_end`` in seconds after the
    step's start / before its end."""
    sp = ns(start_s=T0 + t0 + D, end_s=T0 + t1 - D, self_s={},
            device_first_s=None if first is None else T0 + t0 + D + first,
            device_wait_end_s=(None if wait_end is None
                               else T0 + t1 - D - wait_end))
    return ns(t0=t0, t1=t1), ns(spans=sp)


def track(due, submit, admit, state="RUNNING"):
    req = ns(submit_wall_s=T0 + submit,
             admit_wall_s=None if admit is None else T0 + admit,
             state=ns(name=state))
    return ns(due_s=due, req=req)


def context():
    """Window [6, 10): steps at 5.0 (pre-roll), 6.0, 6.2, then the profiler
    starts (its stall lies between 6.3 and 8.0), and the traced steps at
    8.0 and 8.2."""
    pairs = [step(5.0, 5.1, first=0.05, wait_end=0.0),
             step(6.0, 6.1, first=0.004, wait_end=0.002),   # 6 ms
             step(6.2, 6.3, first=0.002),                   # 2 ms, no wait
             step(8.0, 8.1),                                # no device call
             step(8.2, 8.3, first=0.001, wait_end=0.001)]   # 2 ms
    steps = [s for s, _ in pairs]
    tracks = [track(5.5, 5.5, 5.6),             # due before the window
              track(6.0, 6.0, 6.05),            # 0.05
              track(6.0, 6.02, 8.05),           # admitted after the stall:
                                                # censored at the cut
              track(6.1, 6.1, 6.25),            # 0.15
              track(6.2, 6.2, None, "QUEUED"),  # still waiting: so far
              track(6.25, 6.25, None, "FAILED"),    # refused: left out
              track(7.0, 8.0, 8.05),            # submitted after the cut
              track(9.0, 9.0, 9.1)]
    log = [(T0 - 100, "other", "trace", 1.0),
           (T0 - 90, "decode", "trace", 3.0),
           (T0 - 89, "decode", "lower", 0.5),
           (T0 - 80, "decode", "compile", 10.0),
           (T0 - 79, "decode", "cache_load", 2.0),
           (T0 + 5.5, "prefill_chunk", "compile", 4.0),   # in the pre-roll
           (T0 + 7.0, "decode", "compile", 8.0),          # in the window
           (T0 + 7.0, "other", "trace", 0.25)]
    eng = ns(monitor=ns(history=[ns(spans=None)] * 3 + [t for _, t in pairs]),
             compile_log=log)
    win = ns(eng=eng, steps=steps, tracks=tracks)
    return ns(window=win, start=6.0, end=10.0, stats=stats,
              steps=[s for s in steps if s.t0 >= 6.0 and s.t1 <= 10.0],
              traced_steps=steps[3:])


def read(name, ctx):
    return R.load_reader(name)(ctx)


def test_host_exposed_mean_over_the_window_steps():
    # 6 ms, 2 ms, the whole 8.0 step (no device call), 2 ms
    whole = 0.1 - 2 * D
    assert read("loop.host_exposed_ms", context()) == pytest.approx(
        1e3 * (0.006 + 0.002 + whole + 0.002) / 4)


def test_admit_wait_censored_at_the_profiler_start():
    """The cut is the end of the last step before the traced ones (6.3 s):
    the request admitted after the stall counts 6.3 - 6.02, not 2.03."""
    cut = 6.3 - D
    waits = [0.05, cut - 6.02, 0.15, cut - 6.2]
    got = read("sched.admit_wait_p95_s", context())
    assert got == pytest.approx(stats.nearest_rank(waits, 0.95))
    assert got == pytest.approx(cut - 6.02)


def test_admit_wait_without_a_traced_step_cuts_at_the_window_end():
    ctx = context()
    ctx.traced_steps = []
    # every request due in the window counts; the one still queued counts
    # at its wait until the window's end, 10 - 6.2
    assert read("sched.admit_wait_p95_s", ctx) == pytest.approx(3.8 + D)


def test_setup_seconds_before_the_window():
    ctx = context()
    assert read("setup.trace_s", ctx) == pytest.approx(1.0 + 3.0 + 0.5)
    assert read("setup.compile_s", ctx) == pytest.approx(10.0 + 4.0)


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_programs_records(name):
    """A program that keeps no host spans, stamps or compile log (as the
    one before these records), and an engine that has not stepped."""
    ctx = context()
    eng = ctx.window.eng
    eng.monitor.history = [ns(time_s=0.0)] * len(eng.monitor.history)
    del eng.compile_log
    for tr in ctx.window.tracks:
        tr.req = ns(state=tr.req.state)
    assert read(name, ctx) is None
    ctx = context()
    ctx.window.eng.monitor.history = []
    assert read(name, ctx) is None


def test_the_chat_cell_reports_them():
    spec = R.load_spec("qwen2-1.5b.chat")
    got = {m["name"]: m for m in spec["per_layer"]}
    for name in NAMES:
        assert got[name]["source"] == "program_counter"
