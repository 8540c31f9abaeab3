"""setup_s: process start to the first timed request: data drawn from the
seed, the index built by bulk ingest and uploaded, every batch shape of
the traffic compiled or loaded from the compile cache, a second of
warm-up traffic."""


def read(ctx):
    return ctx.setup_s
