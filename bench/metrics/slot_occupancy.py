"""Mean share of the 32 KV slots holding a decoding request, sampled at
every decode step of the window (the scheduler's own counter)."""


def read(ctx):
    if not ctx.stats.get("decode_steps"):
        return None
    return 100.0 * ctx.stats["slot_occupancy"]
