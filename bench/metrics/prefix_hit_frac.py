"""History tokens the prefix cache served (not prefilled) as a share of
the history tokens submitted in the window (the store's own counter)."""


def read(ctx):
    if not ctx.history_tokens:
        return None
    return 100.0 * ctx.stats["prefix_tokens_saved"] / ctx.history_tokens
