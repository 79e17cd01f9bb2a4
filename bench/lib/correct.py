"""Decide ``correct``: the served tokens against the plain reference.

After the window closes, a sample of the requests the engine finished,
drawn from the run seed, with the longest among them, is replayed through
:class:`bench.lib.reference.Reference` step by step as the window served
it. For every served token the number read is how far its reference logit
lies below the reference's best at that position (0 when it is the
reference's own choice). The run's number is the widest such gap.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def segments(track) -> List:
    """The request's reference segments: consecutive steps merged while the
    swap level and the set of int8 blocks stay the same. A ``None`` marks a
    preemption, after which the KV is computed anew."""
    quant = list(track.quant)
    out: List = []
    qset: frozenset = frozenset()
    qi = 0
    for idx, e in enumerate(track.entries):
        while qi < len(quant) and quant[qi][0] <= idx:
            qset = quant[qi][1]
            qi += 1
        if e is None:
            out.append(None)
            qset = frozenset()
            # int8 marks recorded after the preemption start afresh
            continue
        level, a, b = e[0], e[1], e[2]
        last = out[-1] if out else None
        if (last is not None and last[0] == level and last[2] == a
                and last[3] == qset):
            out[-1] = (level, last[1], b, qset)
        else:
            out.append((level, a, b, qset))
    return out


def served(track) -> Dict[int, int]:
    """Position whose logits chose each served token -> that token."""
    r = track.req
    stream = list(r.prompt[r.orig_prompt_len:]) + list(r.generated)
    p0 = r.orig_prompt_len
    return {p0 + k - 1: int(t) for k, t in enumerate(stream)}


def tokens_of(track) -> List[int]:
    r = track.req
    return list(track.prompt) + list(r.prompt[r.orig_prompt_len:]) \
        + list(r.generated)


def finished(tracks) -> list:
    return [t for t in tracks if t.req is not None
            and t.outcome == "FINISHED" and t.entries]


def sample(tracks, seed: int, target_tokens: int, max_requests: int) -> list:
    """The longest finished request; one request for each swap level and
    for int8 KV where any finished request ran on them; then requests
    drawn from ``seed`` until ``target_tokens`` served tokens are in."""
    done = finished(tracks)
    if not done:
        return []
    rng = np.random.default_rng(seed)
    order = [done[i] for i in rng.permutation(len(done))]
    pick = [max(done, key=lambda t: (len(t.prompt) + t.max_new_tokens,
                                     t.due_s))]
    levels = sorted({e[0] for t in done for e in t.entries if e})

    def add(pred):
        for t in order:
            if pred(t):
                if t not in pick:
                    pick.append(t)
                return

    for lv in levels:
        if not any(e and e[0] == lv for t in pick for e in t.entries):
            add(lambda t, lv=lv: any(e and e[0] == lv for e in t.entries))
    if not any(t.quant for t in pick):
        add(lambda t: bool(t.quant))
    for t in order:
        if sum(x.max_new_tokens for x in pick) >= target_tokens \
                or len(pick) >= max_requests:
            break
        if t not in pick:
            pick.append(t)
    return pick[:max_requests]


def widest_gap(ref, picked, control=None) -> dict:
    """Replay each picked request through ``ref``; the widest gap of a
    served token below the reference's best (and the mean gap), and what
    was covered. With ``control``, the control stands in the program's
    place: at each served position the token compared is the one
    ``control`` puts first there, given the same prompt and served tokens
    before it."""
    worst, total, n_tok, n_req = 0.0, 0.0, 0, 0
    levels, int8_blocks = set(), 0
    for t in picked:
        toks, segs, want = tokens_of(t), segments(t), served(t)
        if control is not None:
            low = control.replay(toks, segs, want.keys(), want)
            want = {p: arg for p, (_b, arg, _a) in low.items()}
        res = ref.replay(toks, segs, want.keys(), want)
        for p, (best, _arg, at) in res.items():
            worst = max(worst, best - at)
            total += best - at
        n_tok += len(res)
        n_req += 1
        levels |= {e[0] for e in t.entries if e}
        int8_blocks += len(t.quant[-1][1]) if t.quant else 0
    return {"gap": worst, "mean_gap": total / max(n_tok, 1),
            "tokens": n_tok, "requests": n_req, "levels": sorted(levels),
            "int8_blocks": int8_blocks}
