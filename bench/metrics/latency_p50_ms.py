"""Median request latency over every request due in the window, from due
time to item.  Host clock."""

import numpy as np


def read(ctx):
    if not ctx.latencies_ms:
        return None
    return float(np.percentile(ctx.latencies_ms, 50))
