"""Median time a request waits in the admission queue, from submission
to its first admission into a prefill group, over the requests first
admitted in the window (the scheduler's lifecycle stamps)."""


def read(ctx):
    v = ctx.stats.get("queue_wait_p50_s")
    return None if v is None else 1e3 * v
