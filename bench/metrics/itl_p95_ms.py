"""p95 of every gap between consecutive output tokens in the window.

At the cell's rate about 9% of the gaps hold one admission (a step of
~58 ms against ~126 ms with a prefill between), so p95 reads the stall
an admission puts on every running request; p99 falls on the edge
between one admission and two and swings run to run."""
from bench import readers


def read(ctx):
    return readers.pct(readers.itl_ms(ctx), 95)
