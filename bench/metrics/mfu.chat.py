"""Device: model FLOPs served in the traced window over the window and the bf16 peak, in %."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
