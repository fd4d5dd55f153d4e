"""Offload: device ms a decode step waits on host transfers with no fetch_weights span open (the callback's result crossing to the device)."""
from bench import spans


def read(ctx):
    return spans.miss_transfer_ms_per_step(ctx)
