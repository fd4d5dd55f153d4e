"""Arithmetic shared by the metric readers in ``bench/metrics/``.  Each
reader is ``read(ctx) -> float | None`` (None: nothing to read, and the
metric is left out of the result line); ``ctx`` is ``run.Ctx``."""
from __future__ import annotations

import numpy as np

from bench import work
from bench.families import family


def pct(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def tokens_in_window(ctx) -> int:
    return sum(1 for r in ctx.recs for x in r.times if ctx.in_window(x))


def ttft_ms(ctx):
    """Due time -> first token, every request due in the window."""
    return [(r.times[0] - r.due) * 1e3 for r in ctx.due_in_window()
            if r.times]


def itl_ms(ctx):
    """Every gap between consecutive output tokens ending in the window
    (tokens one step emits together are one step apart)."""
    out = []
    for r in ctx.recs:
        t = r.times
        out += [(b - a) * 1e3 for a, b in zip(t, t[1:]) if ctx.in_window(b)]
    return out


def queue_wait_ms(ctx):
    return [(r.admit[0] - r.due) * 1e3 for r in ctx.due_in_window()
            if r.admit is not None]


def decode_step_ms(ctx):
    steps = ctx.delta("steps")
    return ctx.delta("decode_s") / steps * 1e3 if steps else None


def prefill_ms_per_request(ctx):
    d = [(t1 - t0) * 1e3 for t0, t1, _ in ctx.adm_log if ctx.in_window(t1)]
    return float(np.mean(d)) if d else None


def prefill_share(ctx):
    p, d = ctx.delta("prefill_s"), ctx.delta("decode_s")
    return 100.0 * p / (p + d) if p + d > 0 else None


def idle_share(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace.idle_share()
    return None if v is None else 100.0 * v


def served_flops(ctx) -> float:
    """Model FLOPs of the work served in the window: every prompt admitted
    in it, and every token a decode step in it emitted (at the context
    that token's step attended)."""
    m = ctx.m
    total = sum(work.prefill_flops(m, n) for _, t1, n in ctx.adm_log
                if ctx.in_window(t1))
    for r in ctx.recs:
        L = len(r.gen.prompt)
        for j, x in enumerate(r.times[1:], start=1):
            if ctx.in_window(x):
                total += work.token_flops(m, L + j)
    return total


def mfu(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    f = served_flops(ctx)
    if f <= 0:
        return None
    return 100.0 * f / ctx.trace.window_s / ctx.peak["bf16_flops"]


# the Pallas kernel's op in a TPU trace: ``expert_ffn.<n>``
EXPERT_FFN = r"^expert_ffn(\.\d+)?$"


def expert_ffn_roofline(ctx):
    """Least time of the expert-FFN work the window needed (per layer
    call: routed rows of real tokens, the experts they touch) over the
    kernel's device time.  Admissions: every prompt token's top-k rows;
    a prompt of 64 or more tokens touches every expert.  Decode steps:
    live slots' rows, experts touched as the step's own routing
    telemetry says."""
    if ctx.trace is None:
        return None
    kernel_s, n = ctx.trace.kernel_seconds(EXPERT_FFN)
    if n == 0 or kernel_s <= 0:
        return None
    m, peak = ctx.m, ctx.peak
    _, _, E, K, L = family(m).moe_dims(m)
    need = 0.0
    for _, t1, tokens in ctx.adm_log:
        if ctx.in_window(t1):
            rows = tokens * K
            need += L * work.least_time(
                *work.expert_ffn_call(m, rows, min(E, rows)), peak)[0]
    for t0, t1, live, touched in ctx.step_log:
        if not ctx.in_window(t1) or not live:
            continue
        if touched is None:
            return None
        for per_layer in np.asarray(touched).sum(axis=-1).reshape(-1):
            need += work.least_time(*work.expert_ffn_call(
                m, live * K, int(per_layer)), peak)[0]
    return 100.0 * need / kernel_s
