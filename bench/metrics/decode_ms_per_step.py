"""Device time of one decode step, from the trace: the XLA modules whose
name holds "decode", their summed device time over their executions."""

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = trace.module_time(ctx.trace, r"decode")
    return 1e3 * t / n if n else None
