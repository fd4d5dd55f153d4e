"""Model step: mean admission time (prefill, cache insert, first token)."""
from bench import readers


def read(ctx):
    return readers.prefill_ms_per_request(ctx)
