"""Share of the traced window with no op running on the device."""

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
