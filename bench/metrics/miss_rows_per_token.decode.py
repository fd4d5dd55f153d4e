"""Offload: (token, expert) rows the decode miss callbacks fetched, per output token."""
from bench import readers


def read(ctx):
    n = readers.tokens_in_window(ctx)
    d = ctx.delta('store.fallback_rows')
    return d / n if n and d is not None else None
