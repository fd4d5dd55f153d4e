"""Offload: host ms in the store's stage and commit/fold (stage_s + commit_s), per decode step."""


def read(ctx):
    s, c = ctx.delta("store.stage_s"), ctx.delta("store.commit_s")
    n = ctx.delta("steps")
    return (s + c) / n * 1e3 if s is not None and c is not None and n \
        else None
