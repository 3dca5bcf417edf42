"""answer_s: the whole window over the sweep answers completed in it."""


def read(ctx):
    if not ctx.units:
        return None
    return ctx.window_s / len(ctx.units)
