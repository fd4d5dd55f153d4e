"""Scheduler: % of the traced window the device idles while the serving loop runs its own code (in a dali:serve.step span, in none of its children)."""
from bench import spans


def read(ctx):
    return spans.idle_sched_share(ctx)
