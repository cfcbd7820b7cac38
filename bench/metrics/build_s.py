"""Seconds to build the served weights and engine (the program's
leaf-at-a-time quantizing build, or the bf16 tree), ending when every
parameter and the KV pool is on the device.  Host clock."""


def read(ctx):
    return ctx.build_s
