"""Host spans inside the serving step (``engine/spans.py``): what a profiler
trace of a few real-compute steps holds, the per-step totals on each
``Telemetry``, the per-request wall stamps, and the compile phases booked
per program family."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ServingConfig, reduced, MORPH_LLAMA2_7B
from repro.core import tree_bytes
from repro.engine import (EngineConfig, KVQuantConfig, MorphServeEngine,
                          TraceRequest, spans)
from repro.engine.kv_cache import kv_block_bytes
from repro.models import lm

ROOT = Path(__file__).resolve().parents[1]

# what one step can hold, parent first
NESTING = {"serve.schedule": "serve.step", "serve.prefill": "serve.step",
           "serve.decode_blocks": "serve.step", "serve.decode": "serve.step",
           "serve.account": "serve.step", "serve.morph": "serve.step",
           "exec.prefill_chunk": "serve.prefill",
           "exec.prefill": "serve.prefill",
           "exec.prefill_batch": "serve.prefill",
           "exec.decode": "serve.decode"}


def _bench_trace():
    spec = importlib.util.spec_from_file_location(
        "bench_trace", ROOT / "bench" / "lib" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_engine(cfg, params, *, blocks=40, slots=4, compute="real",
                policy="static_fp16", **ecfg_kw):
    wb = tree_bytes(params)
    bb = kv_block_bytes(cfg, 16, 4)
    budget = int((wb + blocks * bb) / 0.95) + 2 * bb
    sc = ServingConfig(hbm_budget_bytes=budget, kv_block_size=16,
                       max_batch_slots=slots, max_seq_len=256,
                       swap_levels=(0, 1, 2, 4), mode="performance",
                       kv_resize_step_frac=0.25)
    return MorphServeEngine(cfg, params, sc,
                            EngineConfig(policy=policy, compute=compute,
                                         seed=7, **ecfg_kw))


@pytest.fixture(scope="module")
def model():
    cfg = reduced(MORPH_LLAMA2_7B)
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """Two short prompts are prefilled whole in one batch, then a 70-token
    prompt streams in chunks of 24 beside their decode; every step runs
    under the profiler."""
    cfg, params = model
    eng = make_engine(cfg, params, max_tokens_per_step=24)
    for tr in (TraceRequest(0.0, 8, 6), TraceRequest(0.0, 9, 6),
               TraceRequest(0.0, 70, 4)):
        eng.submit(tr)
    n_log = len(spans.COMPILE_LOG)
    trace_dir = tmp_path_factory.mktemp("trace")
    steps = 0
    with jax.profiler.trace(str(trace_dir)):
        while eng.queue or eng.running:
            eng.step()
            steps += 1
            assert steps < 40
    bt = _bench_trace()
    path = bt.find_xplane(str(trace_dir))
    return eng, steps, n_log, bt, bt.load(path), path


def _host_spans(planes):
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for p in planes if not p.name.startswith("/device")
                  for l in p.lines for e in l.events
                  if e.name.startswith(("serve.", "exec.", "relief.")))


def _parent(spans_, i):
    """The innermost span that holds span ``i``."""
    s, e, _ = spans_[i]
    holders = [x for j, x in enumerate(spans_)
               if j != i and x[0] <= s and e <= x[1]]
    return min(holders, key=lambda x: x[1] - x[0])[2] if holders else None


def test_every_span_lands_nested_under_its_step(served):
    eng, steps, _, _, planes, _ = served
    hs = _host_spans(planes)
    roots = [x for x in hs if x[2] == "serve.step"]
    assert len(roots) == steps
    names = {x[2] for x in hs}
    # a lone whole prompt (exec.prefill) is not in this mix
    assert set(NESTING) - {"exec.prefill"} | {"serve.step",
                                              "serve.readback"} <= names
    for i, (_, _, name) in enumerate(hs):
        parent = _parent(hs, i)
        if name == "serve.step":
            assert parent is None
        elif name == "serve.readback":
            assert parent in ("serve.prefill", "serve.decode")
        else:
            assert parent == NESTING[name], (name, parent)
    # in step order: each step schedules first and ends with its
    # bookkeeping and the relief tick
    for s, e, _ in roots:
        kids = [x[2] for x in hs if s <= x[0] and x[1] <= e
                and x[2] in NESTING and NESTING[x[2]] == "serve.step"]
        assert kids[0] == "serve.schedule"
        assert kids[-2:] == ["serve.account", "serve.morph"]
    # the steps' Telemetry records are the trace's steps, in order
    hist = eng.monitor.history[-steps:]
    durs = [t.spans.end_s - t.spans.start_s for t in hist]
    assert np.allclose(durs, [(e - s) * 1e-9 for s, e, _ in roots],
                       rtol=0.05, atol=2e-4)


def test_prefill_span_carries_its_request_id(served):
    """The whole-prompt batch carries its first request's id and its size;
    each chunk carries its request's id."""
    eng, _, _, _, _, path = served
    from jax.profiler import ProfileData
    stats = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for l in p.lines for e in l.events if e.name == "serve.prefill"]
    short, short2, long_ = eng.all_requests
    assert stats[0] == {"rid": short.rid, "n": 2}
    assert long_.prefill_chunks >= 3
    assert stats[1:] == [{"rid": long_.rid}] * long_.prefill_chunks


def test_self_times_fit_the_step(served):
    eng, steps, _, _, _, _ = served
    for t in eng.monitor.history[-steps:]:
        rec = t.spans
        wall = rec.end_s - rec.start_s
        assert 0 < sum(rec.self_s.values()) <= wall + 1e-9
        assert min(rec.self_s.values()) >= -1e-9
        assert rec.self_s.keys() >= {"serve.step", "serve.schedule",
                                     "serve.account", "serve.morph"}
        if rec.device_first_s is not None:
            assert rec.start_s <= rec.device_first_s <= rec.end_s
        if rec.device_wait_end_s is not None:
            assert rec.device_first_s <= rec.device_wait_end_s <= rec.end_s


def test_submit_and_admit_stamps(served):
    eng = served[0]
    for r in eng.all_requests:
        assert r.submit_wall_s is not None and r.admit_wall_s is not None
        assert r.submit_wall_s <= r.admit_wall_s


def test_compile_phases_are_booked_to_their_family(served):
    """The engine's first decode traces, lowers and compiles under family
    ``decode``; the same call again books nothing."""
    eng, _, n_log, _, _, _ = served
    fams = {}
    for _, fam, phase, secs in spans.COMPILE_LOG[n_log:]:
        fams.setdefault(fam, set()).add(phase)
        assert secs >= 0
    assert {"trace", "lower", "compile"} <= fams["decode"]
    assert {"trace", "compile"} <= fams["prefill_chunk"]
    # one trace per program lowered: jits traced inside another program's
    # trace or lowering are booked to that phase
    for fam in ("decode", "prefill_chunk", "prefill_batch"):
        n = {p: sum(1 for x in spans.COMPILE_LOG[n_log:]
                    if x[1] == fam and x[2] == p) for p in ("trace", "lower")}
        assert n["trace"] == n["lower"] >= 1, (fam, n)

    def decode():
        out = eng.exec.decode(
            eng.actuator.layer_list(), jax.numpy.zeros((eng.slots, 1),
                                                       np.int32),
            jax.numpy.zeros((eng.slots,), np.int32), eng.pool.k, eng.pool.v,
            jax.numpy.zeros((eng.slots, 1), np.int32), eng.ssm_conv,
            eng.ssm_ssm, eng.pool.kv_quant_bundle())
        _, eng.pool.k, eng.pool.v, eng.ssm_conv, eng.ssm_ssm = out

    decode()
    n = len(spans.COMPILE_LOG)
    decode()
    assert not [x for x in spans.COMPILE_LOG[n:] if x[1] == "decode"]


def test_no_program_span_takes_a_harness_name(served):
    eng, steps, _, bt, planes, _ = served
    names = {x[2] for x in _host_spans(planes)}
    for t in eng.monitor.history[-steps:]:
        names |= set(t.spans.self_s)
    assert not names & set(bt.HOST_SPANS)
    assert not [n for n in names if n.startswith("bench.")]


@pytest.mark.parametrize("kv_quant,acts", [
    (False, {"relief.swap", "relief.kv_resize"}),
    (True, {"relief.kv_quantize", "relief.kv_resize"})])
def test_relief_spans_mark_the_ladder_acting(model, kv_quant, acts):
    """Under KV pressure the morph policy swaps a layer (or, with the int8
    tier on, quantizes cold blocks first) and grows the pool; the steps it
    acts in carry those ``relief.*`` spans."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=6, policy="morph",
                      kv_quant=KVQuantConfig(enabled=kv_quant))
    eng.run_trace([TraceRequest(0.001 * i, 30, 32) for i in range(10)],
                  max_steps=6000)
    acted = set()
    for t in eng.monitor.history:
        acted |= {n for n in t.spans.self_s if n.startswith("relief.")}
    assert acted == acts


def test_nested_trace_is_booked_once():
    """A jit traced inside another's trace reports its own trace duration
    too; only the outermost is kept."""
    inner = jax.jit(lambda x: x * 2)
    outer = jax.jit(lambda x: inner(x) + 1)
    spans.watch_compiles()
    n = len(spans.COMPILE_LOG)
    with spans.span("exec.nested_probe"):
        outer(np.float32(1.0))
    traces = [x for x in spans.COMPILE_LOG[n:] if x[2] == "trace"]
    assert len(traces) == 1 and traces[0][1] == "nested_probe"
