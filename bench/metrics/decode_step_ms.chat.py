"""Model step: decode seconds over decode steps (ServeMetrics), in ms."""
from bench import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
