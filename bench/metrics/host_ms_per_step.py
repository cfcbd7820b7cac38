"""Host time of an engine round: the ``serve.step`` span less the time
inside ``serve.device_wait`` and the select readbacks, averaged over the
rounds of the window that dispatched a device program (engine counter)."""


def read(ctx):
    v = ctx.stats.get("host_s_per_step")
    return None if v is None else 1e3 * v
