"""Offload: MB the decode miss callbacks return (the store's fetch_bytes, hit rows as zeros), per output token."""
from bench import readers


def read(ctx):
    n = readers.tokens_in_window(ctx)
    b = ctx.delta("store.fetch_bytes")
    return b / 1e6 / n if n and b is not None else None
