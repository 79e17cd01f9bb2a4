"""Scheduler: 95th percentile over the requests due in the window of the
time from due until the client first saw the request leave the queue
(censored at the window's end). Moves ``ttft_p95_s``."""


def read(ctx):
    due = ctx.stats.due_in(ctx.window.tracks, ctx.start, ctx.end)
    waits = [(t.left_queue_s if t.left_queue_s is not None
              and t.left_queue_s < ctx.end else ctx.end) - t.due_s
             for t in due]
    return ctx.stats.nearest_rank(waits, 0.95)
