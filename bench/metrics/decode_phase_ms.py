"""Median decode phase of a request, from its first token on the host to
its item, over the requests completed in the window: the decode rounds
with the joins that stall them (the scheduler's lifecycle stamps)."""


def read(ctx):
    v = ctx.stats.get("decode_phase_p50_s")
    return None if v is None else 1e3 * v
