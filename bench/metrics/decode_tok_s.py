"""Output tokens emitted in the window over the whole window."""
from bench import readers


def read(ctx):
    return readers.tokens_in_window(ctx) / ctx.window_s
