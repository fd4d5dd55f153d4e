"""The program's own host spans in a traced run, and the per-layer numbers
read from them beside the device operations.

The program opens a profiler host span named ``dali:<layer>.<what>``
around each pass of its serving loop and the calls the loop makes
(``dali:serve.*``, serving/scheduler.py), and inside the expert store's
hooks and host callbacks (``dali:store.*``, serving/expert_store.py);
identifiers ride as the span's arguments.  ``bench/trace.py`` keeps the
harness's ``bench:`` spans only, so this module reads the run's profile
again for the ``dali:`` spans, each with the host thread (trace line) it
ran on.  A program that opens no such span gives none, and every reader
here then reads nothing.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Tuple

from bench import trace as T

PREFIX = "dali:"
STEP = "dali:serve.step"
FETCH = "dali:store.fetch_weights"


@dataclass
class Span:
    name: str
    start: int               # ns, trace clock
    end: int
    line: Tuple[str, int]    # (host plane, line index): the thread it ran on
    args: dict


def reduce_spans(pd) -> List[Span]:
    """Every ``dali:`` span of a ``ProfileData``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append(Span(ev.name, s, s + int(ev.duration_ns),
                                    (plane.name, i), T._stats(ev)))
    return out


def traced_spans(ctx) -> Optional[List[Span]]:
    """The ``dali:`` spans of this run's trace (None: an untraced run).
    The trace is the one ``run.run_cell`` wrote and reduced into
    ``ctx.trace``."""
    if ctx.trace is None:
        return None
    from jax.profiler import ProfileData
    from bench.run import CACHE
    path = T.find_xplane(os.path.join(CACHE, "trace"))
    return reduce_spans(ProfileData.from_file(path))


def measure(intervals) -> int:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> List[Tuple[int, int]]:
    """The parts of ``a`` that no interval of ``b`` covers (both sorted
    and disjoint, as ``trace.union`` gives them)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def host_wait_ns(red: T.Reduced, spans: List[Span]) -> Tuple[float, float]:
    """(ns the device waited on transfers from the host in the window,
    the part of them with no ``fetch_weights`` span open), averaged over
    devices.  A wait is an op marked ``is_host_transfer=true``: one that
    ``trace.busy_intervals`` leaves out of busy time."""
    inside = T.union([(x.start, x.end) for x in spans if x.name == FETCH],
                     red.window)
    wait = rest = 0
    for ops in red.devices.values():
        w = T.union([(o.start, o.end) for o in ops if o.waits_on_host],
                    red.window)
        wait += measure(w)
        rest += measure(minus(w, inside))
    n = max(1, len(red.devices))
    return wait / n, rest / n


def sched_idle_ns(red: T.Reduced, spans: List[Span]) -> float:
    """ns with no device op running while the serving loop runs its own
    code: inside a ``dali:serve.step`` span and in none of the other
    ``dali:`` spans on that thread, averaged over devices."""
    steps = [x for x in spans if x.name == STEP]
    lines = {x.line for x in steps}
    own = minus(T.union([(x.start, x.end) for x in steps], red.window),
                T.union([(x.start, x.end) for x in spans
                         if x.line in lines and x.name != STEP]))
    idle = 0
    for ops in red.devices.values():
        idle += measure(minus(own, T.union(T.busy_intervals(ops),
                                           red.window)))
    return idle / max(1, len(red.devices))


def miss_transfer_ms_per_step(ctx):
    """Device ms a decode step waits on the miss callbacks' transfers
    outside their host gather (the ``fetch_weights`` spans)."""
    spans = traced_spans(ctx)
    steps = ctx.delta("steps")
    if not spans or not steps or not any(x.name == FETCH for x in spans):
        return None
    return host_wait_ns(ctx.trace, spans)[1] / 1e6 / steps


def idle_sched_share(ctx):
    """% of the traced window the device idles while the serving loop
    runs its own code (``sched_idle_ns``)."""
    spans = traced_spans(ctx)
    red = ctx.trace
    if (not spans or not red.devices or red.window_s <= 0
            or not any(x.name == STEP for x in spans)):
        return None
    return 100.0 * sched_idle_ns(red, spans) / 1e9 / red.window_s
