"""The paged-decode attention kernel's share of its roofline: the least
time its calls in the traced window could take (the larger of bytes over
HBM bandwidth and operations over the bf16 peak, per call, from the page
tables of each decode step) over the kernel's device time in the trace."""

from bench import costs, trace

KERNEL = r"paged_decode|decode_kernel"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t, n = trace.op_time(ctx.trace, KERNEL)
    if not n:
        return None
    least = 0.0
    for c in ctx.calls:
        if c["kind"] != "decode":
            continue
        flops, nbytes = costs.paged_decode_cost(
            ctx.model, ctx.engine["kv_dtype"], ctx.engine["page_size"],
            c["positions"])
        least += max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                     flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / t
