"""Offload: host ms inside the decode miss callback (the store's fetch_s), per decode step."""


def read(ctx):
    s, n = ctx.delta("store.fetch_s"), ctx.delta("steps")
    return s / n * 1e3 if s is not None and n else None
