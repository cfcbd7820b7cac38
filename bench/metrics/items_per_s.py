"""Items (recommendations) completed inside the window, per second of
window.  Host clock."""


def read(ctx):
    return ctx.items_in_window / ctx.seconds
