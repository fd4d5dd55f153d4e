"""Process start to the opening of the window: weights, build, compile or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
