"""Device: share of the traced window with no operation running, in %."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
