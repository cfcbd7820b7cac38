"""The served step's share of the chip's bf16 peak: useful model
operations of the prefill and decode calls in the traced window (real
tokens, routed experts, causal attention, the head where a token is
sampled) over the device time of every program in the window times the
peak.  fp8 weights count at the bf16 peak: v5e has no fp8 matrix unit."""

from bench import costs, trace


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    busy, n = trace.module_time(ctx.trace, r".")
    if not n:
        return None
    flops = 0
    for c in ctx.calls:
        if c["kind"] == "decode":
            flops += costs.decode_flops(ctx.model, c["positions"])
        else:
            flops += costs.prefill_flops(ctx.model, c["tokens"], c["starts"])
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops_per_s"])
