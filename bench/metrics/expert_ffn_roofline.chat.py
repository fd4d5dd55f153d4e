"""Kernel: expert FFN least time over its device time, admissions and decode steps."""
from bench import readers


def read(ctx):
    return readers.expert_ffn_roofline(ctx)
