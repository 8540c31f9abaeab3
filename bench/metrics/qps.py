"""qps: requests answered by the time the window closed, per second of
the window."""


def read(ctx):
    return ctx.done_in_window / ctx.seconds
