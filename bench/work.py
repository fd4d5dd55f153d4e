"""The work a layer needs, counted from shapes and routing, never from
what the program happens to execute: no padded rows, no capacity
buckets, no experts swept without a row routed to them.  A kernel that
skips work therefore still reads at most 100% of its roofline.

``m`` is a configuration file's dict; what depends on the model's
family (its widths' keys, the FLOPs of a token) is the family's
(``bench/families/``).
"""
from __future__ import annotations

from bench.families import family


def expert_ffn_call(m: dict, rows: int, touched: int,
                    dtype_bytes: int = 2):
    """(FLOPs, bytes) one expert-FFN layer call needs: ``rows`` routed
    (token, expert) rows, ``touched`` distinct experts holding them.

    FLOPs: 3 matrix products of d x f per row, 2 FLOPs a multiply-add.
    Bytes: the touched experts' three matrices, each row's activation
    read once and its output written once."""
    d, f = family(m).moe_dims(m)[:2]
    flops = 6 * d * f * rows
    nbytes = dtype_bytes * (3 * d * f * touched + 2 * d * rows)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of compute time and memory time at
    the chip's peaks, and which of the two binds."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def token_flops(m: dict, context: int, logits: bool = True) -> float:
    """Model FLOPs one token needs when it attends to ``context`` keys,
    with the LM head where its logits are needed."""
    return family(m).token_flops(m, context, logits)


def prefill_flops(m: dict, length: int) -> float:
    """Model FLOPs of a prompt of ``length`` tokens (logits at the last)."""
    return family(m).prefill_flops(m, length)
