"""setup_s: seconds from process start to the window's start: making the
graph, the work's set-up and the warm-up unit, compilation included."""


def read(ctx):
    return ctx.setup_s
