"""Kernel: expert FFN least time over its device time, decode steps at full batch (the reader of expert_ffn_roofline.chat, for cells that report decode_tok_s)."""
from bench import readers


def read(ctx):
    return readers.expert_ffn_roofline(ctx)
