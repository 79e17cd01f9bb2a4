"""Relief ladder: the swap level the window's steps ran at, weighted by each
step's wall time (``Window.level_time`` restricted to the window): 0 when
every step ran on the fp weights, the ladder's deepest level when every
step ran there. Moves ``ttft_p95_s``: the ladder trades precision for the
KV room that keeps a burst's requests admitted."""


def read(ctx):
    wall = sum(s.t1 - s.t0 for s in ctx.steps)
    if wall <= 0:
        return None
    return sum(s.level * (s.t1 - s.t0) for s in ctx.steps) / wall
