"""Scheduler: admission prefill seconds over prefill plus decode seconds (ServeMetrics), in %."""
from bench import readers


def read(ctx):
    return readers.prefill_share(ctx)
