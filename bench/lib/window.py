"""Drive the served path on the wall clock: an open-loop schedule of
requests into ``MorphServeEngine.submit`` / ``step``.

The engine keeps a modelled clock (``engine.now`` advances by the cost
model's step time). Before each step the harness sets
``engine.now = max(engine.now, wall seconds since the schedule began)``, so
admission and the relief controller's pacing run on wall time. Every
emitted token is stamped with the wall clock after the step that produced
it has finished on the device.

Besides the timings, the harness keeps what the reference needs to replay
each request: per step, the swap level and the positions whose KV the step
computed, and when any block of the request was first held in int8.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Track:
    """One request as the client and the reference see it."""
    due_s: float
    prompt: tuple
    max_new_tokens: int
    req: object = None               # the engine's Request
    submit_s: float = 0.0
    left_queue_s: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    # (level, first position computed, end position, tokens emitted before,
    #  after) for every step that touched the request; a None entry marks a
    #  preemption (its KV was dropped)
    entries: List = dataclasses.field(default_factory=list)
    # (entry index from which it holds, frozenset of logical blocks that have
    #  ever been held int8 since the last preemption)
    quant: List = dataclasses.field(default_factory=list)

    @property
    def outcome(self) -> str:
        return self.req.state.name if self.req is not None else "NOT_SENT"


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    level: int
    decode_ctx: List          # (context before the token, int8 blocks)
    chunks: List              # (start, tokens, int8 context blocks)
    wholes: List              # (start, tokens) of whole-prompt prefills
    logits_rows: int
    compiles: int
    level_after: int = 0
    events: int = 0           # level changes, int8 batches, pool resizes


def computed(r) -> int:
    """Positions of ``r`` whose KV is in the pool."""
    st = r.state.name
    if st in ("QUEUED", "PREEMPTED", "FAILED", "SHED"):
        return 0
    if st == "PREFILLING":
        return r.prefill_pos
    return len(r.prompt) + len(r.generated) - 1


def emitted(r) -> int:
    """Tokens served so far (generations folded into the prompt by a
    preemption included)."""
    return len(r.prompt) - r.orig_prompt_len + len(r.generated)


class Window:
    def __init__(self, eng, arrivals, *, detail: bool = False):
        self.eng = eng
        self.tracks = [Track(a.due_s, a.prompt, a.max_new_tokens)
                       for a in arrivals]
        self.steps: List[StepRec] = []
        self.detail = detail          # per-step costs (traced runs)
        self.compiles = 0
        self.max_late_s = 0.0
        self._by_rid: Dict[int, Track] = {}
        self._ever: Dict[int, set] = {}
        self._qstate = None
        self.level_time: Dict[int, float] = {}
        self._ev = (0, 0)

    def _on_event(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1

    def _quant_sig(self):
        e = self.eng
        return (len(e.kv_quant_events), len(e.pool.qbits),
                e.compaction_moves, len(e.resize_log))

    def _scan_quant(self, reqs):
        qb = self.eng.pool.qbits
        for r in reqs:
            tr = self._by_rid.get(r.rid)
            if tr is None:
                continue
            ever = self._ever.setdefault(r.rid, set())
            now = {j for j, b in enumerate(r.block_ids) if b in qb}
            if not now <= ever:
                ever |= now
                tr.quant.append((len(tr.entries), frozenset(ever)))

    def run(self, t_start: float, preroll_s: float, seconds: float,
            on_tick: Optional[Callable[[float], None]] = None) -> float:
        """Serve until ``preroll_s + seconds`` after ``t_start`` (a
        ``perf_counter`` reading). Returns the wall time of the end."""
        from repro.engine import TraceRequest
        eng = self.eng
        end = preroll_s + seconds
        ann = jax.profiler.TraceAnnotation
        i, n = 0, len(self.tracks)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        try:
            while True:
                now = time.perf_counter() - t_start
                if on_tick is not None:
                    on_tick(now)
                if now >= end:
                    return now
                with ann("bench.submit"):
                    while i < n and self.tracks[i].due_s <= now:
                        tr = self.tracks[i]
                        tr.submit_s = time.perf_counter() - t_start
                        self.max_late_s = max(self.max_late_s,
                                              tr.submit_s - tr.due_s)
                        tr.req = eng.submit(TraceRequest(
                            tr.due_s, len(tr.prompt), tr.max_new_tokens,
                            prompt_tokens=tr.prompt))
                        self._by_rid[tr.req.rid] = tr
                        i += 1
                if not eng.queue and not eng.running:
                    nxt = self.tracks[i].due_s if i < n else end
                    with ann("bench.wait_arrival"):
                        time.sleep(max(0.0, min(nxt, end) - now))
                    continue
                self._step(t_start, now)
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _step(self, t_start: float, now: float) -> None:
        eng = self.eng
        eng.now = max(eng.now, now)
        level = eng.actuator.level
        before = {r.rid: (r, computed(r), emitted(r), r.preemptions,
                          r.state.name, r.prefill_chunks)
                  for r in eng.running}
        queued = {r.rid: (r, 0, emitted(r), r.preemptions, "QUEUED",
                          r.prefill_chunks) for r in eng.queue}
        c0 = self.compiles
        t0 = time.perf_counter() - t_start
        with jax.profiler.TraceAnnotation("engine.step"):
            eng.step()
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready((eng.pool.k, eng.pool.v))
        t1 = time.perf_counter() - t_start
        self.level_time[level] = self.level_time.get(level, 0.0) + t1 - t0
        after = list(eng.running)
        touched = dict(before)
        # admitted this step (and possibly finished or failed in it too)
        for r in after + [q[0] for q in queued.values()
                          if q[0].state.name != "QUEUED"]:
            if r.rid not in touched:
                touched[r.rid] = queued.get(r.rid) or (
                    r, 0, emitted(r), r.preemptions, "QUEUED",
                    r.prefill_chunks)
        rec = StepRec(t0, t1, level, [], [], [], 0, self.compiles - c0)
        qb = eng.pool.qbits
        bs = eng.pool.block_size
        for rid, (r, cb, eb, pb, st0, ch0) in touched.items():
            tr = self._by_rid.get(rid)
            if tr is None:
                continue
            if rid in queued and tr.left_queue_s is None:
                tr.left_queue_s = t1
            ea = emitted(r)
            tr.stamps.extend([t1] * (ea - eb))
            reset = r.preemptions > pb
            ca = computed(r)
            if reset:
                # the step's work up to the last token it emitted stands;
                # the rest of the request's KV is gone
                end = tr.req.orig_prompt_len + ea - 1 if ea > eb else cb
                if end > cb:
                    tr.entries.append((level, cb, end, eb, ea))
                tr.entries.append(None)
                self._ever.pop(rid, None)
                continue
            if ca > cb:
                tr.entries.append((level, cb, ca, eb, ea))
            if not self.detail or ca <= cb:
                continue
            nq = (lambda upto: sum(1 for b in r.block_ids[:-(-upto // bs)]
                                   if b in qb)) if qb else (lambda upto: 0)
            p_end = len(r.prompt)
            if st0 == "RUNNING":
                rec.decode_ctx.append((cb, nq(cb)))
                rec.logits_rows += 1
                continue
            piece = min(ca, p_end) - cb
            if piece > 0:
                if r.prefill_chunks > ch0:
                    rec.chunks.append((cb, piece, nq(cb)))
                else:
                    rec.wholes.append((cb, piece))
                rec.logits_rows += int(cb + piece == p_end)
            if ca > p_end:
                rec.decode_ctx.append((p_end, nq(p_end)))
                rec.logits_rows += 1
        ev = (len(eng.kv_quant_events), len(eng.resize_log))
        rec.level_after = eng.actuator.level
        rec.events = (int(rec.level_after != level) + ev[0] - self._ev[0]
                      + ev[1] - self._ev[1])
        self._ev = ev
        sig = self._quant_sig()
        if sig != self._qstate:
            self._qstate = sig
            self._scan_quant(after)
        self.steps.append(rec)
