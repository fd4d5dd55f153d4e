"""Offload: % of the decode (token, expert) rows routed to the host store's layers that the device pool held (1 - the store's fallback_rows over its routed_rows)."""


def read(ctx):
    r, f = ctx.delta("store.routed_rows"), ctx.delta("store.fallback_rows")
    return 100.0 * (1.0 - f / r) if r and f is not None else None
