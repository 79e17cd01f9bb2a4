"""Engine loop: the program's host time per step with no device work in
flight, in ms -- from ``serve.step`` entry to its first ``exec.*`` call,
plus from the end of its last ``serve.readback`` to ``serve.step`` exit --
mean over the window's steps. Moves ``output_tok_per_s``."""
from bench.lib import records


def read(ctx):
    pairs = records.step_spans(ctx)
    if pairs is None:
        return None
    in_win = {id(s) for s in ctx.steps}
    vals = [records.host_exposed_s(sp) for st, sp in pairs
            if id(st) in in_win]
    return 1e3 * sum(vals) / len(vals) if vals else None
