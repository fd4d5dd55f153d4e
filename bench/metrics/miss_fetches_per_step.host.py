"""Offload: decode miss callbacks (the store's fallback_fetches: one per distinct missing expert of a layer) per decode step."""


def read(ctx):
    f, n = ctx.delta("store.fallback_fetches"), ctx.delta("steps")
    return f / n if f is not None and n else None
