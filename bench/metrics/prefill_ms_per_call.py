"""Device time of one prefill program (fresh or resume), from the trace:
the XLA modules whose name holds "prefill", their summed device time over
their executions."""

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = trace.module_time(ctx.trace, r"prefill")
    return 1e3 * t / n if n else None
