"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the intervals in which an operation
ran), per-operation device time, a kernel's device time, and the idle
gaps labelled with the host span that was open across them.

Device operations are the events of the ``XLA Ops`` lines of the
``/device:<kind>:<n>`` planes (a TPU trace).  The CPU backend has no
device plane: there an operation is a host-thread event carrying an
``hlo_op`` statistic, which is what the tests read.  The harness itself
refuses to run without a TPU.

A TPU trace names each op by its HLO text (``%fusion.3 = bf16[...]
fusion(...)``); the name kept is the instruction's (``fusion.3``), the
text is kept as the op's detail.  Ops nest: a ``while`` or
``conditional`` spans the ops of its body.  Busy time is the union of
the leaf ops (those that contain no other op), less the ops that only
wait for a transfer from the host (``is_host_transfer=true``: a host
callback's data), so a device stalled on a callback reads idle.  Device
and host planes share one clock to within about 2 ms on a v5e
(measured: each op starts ~1.8 ms before the host span that issued it).

The window is the host span named ``bench:window``; everything is
clipped to it.  Host spans are the ``bench:`` annotations the harness
wraps around its calls into the program.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")
OP_LINES = ("XLA Ops",)
HLO_NAME = re.compile(r"^%?([^\s=]+) = ")
HOST_WAIT = re.compile(r"is_host_transfer=true")


@dataclass
class Op:
    name: str
    start: int          # ns, trace clock
    end: int
    detail: str = ""    # the op's HLO text / hlo op, where the trace has it
    leaf: bool = True   # contains no other op
    self_ns: int = 0    # duration less the union of the ops it contains

    @property
    def waits_on_host(self) -> bool:
        return bool(HOST_WAIT.search(self.detail))


def nest(ops: List[Op]) -> List[Op]:
    """Sort ``ops`` and mark containers; set each op's self time."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    inner: Dict[int, List[Tuple[int, int]]] = {}
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
            inner.setdefault(id(stack[-1]), []).append((o.start, o.end))
        stack.append(o)
    for o in ops:
        covered = sum(e - s for s, e in union(inner.get(id(o), [])))
        o.self_ns = (o.end - o.start) - covered
    return ops


@dataclass
class Reduced:
    window: Tuple[int, int]
    devices: Dict[int, List[Op]]
    spans: List[Tuple[str, int, int]]
    busy_ns: Dict[int, int] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.busy_ns or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> List[Tuple[str, float]]:
        """Device self seconds per operation, largest first (all devices
        summed, divided by their number); an op is named by its HLO
        instruction, a host-transfer wait gets a ``wait:`` prefix."""
        tot: Dict[str, int] = {}
        for ops in self.devices.values():
            for o in ops:
                if self._clipped(o) <= 0:
                    continue
                t = min(o.self_ns, self._clipped(o))
                k = ("wait:" if o.waits_on_host else "") + o.name
                tot[k] = tot.get(k, 0) + t
        n = max(1, len(self.devices))
        return sorted(((k, v / n / 1e9) for k, v in tot.items()),
                      key=lambda kv: -kv[1])

    def kernel_seconds(self, pattern: str) -> Tuple[float, int]:
        """(device seconds, events) of the ops whose name matches
        ``pattern`` (a regular expression), per device."""
        rx = re.compile(pattern)
        t = n = 0
        for ops in self.devices.values():
            for o in ops:
                c = self._clipped(o)
                if c > 0 and rx.search(o.name):
                    t += c
                    n += 1
        k = max(1, len(self.devices))
        return t / k / 1e9, n

    def _clipped(self, o: Op) -> int:
        return min(o.end, self.window[1]) - max(o.start, self.window[0])

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest gaps with no device op running, each named
        by the innermost host span covering most of it
        (``host:server-loop``: no harness span, the server's own loop)."""
        gaps = []
        for ops in self.devices.values():
            iv = union(busy_intervals(ops), self.window)
            edges = [self.window[0]] + [x for a in iv for x in a] + \
                [self.window[1]]
            gaps += [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self._label(s, e), (e - s) / 1e9) for s, e in gaps[:top]]

    def _label(self, s: int, e: int) -> str:
        best, cover = "host:server-loop", 0
        for name, a, b in self.spans:
            if name == WINDOW_SPAN:
                continue
            c = min(b, e) - max(a, s)
            # ties go to the later (inner) span
            if c > 0 and c >= cover:
                best, cover = name, c
        return best


def union(intervals, clip=None) -> List[Tuple[int, int]]:
    """Merged, sorted intervals, optionally clipped to ``clip``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if clip is not None:
            s, e = max(s, clip[0]), min(e, clip[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(ops: List[Op]) -> List[Tuple[int, int]]:
    """Intervals of the leaf ops that do device work."""
    return [(o.start, o.end) for o in ops if o.leaf and not o.waits_on_host]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:           # a stat type the binding cannot convert
        return {}


def reduce_file(path: str, window=None) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window)


def _op(ev, st) -> Op:
    s = int(ev.start_ns)
    m = HLO_NAME.match(ev.name)
    return Op(m.group(1) if m else ev.name, s, s + int(ev.duration_ns),
              ev.name if m else str(st.get("long_name", "")))


def reduce_profile(pd, window=None) -> Reduced:
    """Reduce a ``ProfileData``; ``window`` (start, end) in trace ns
    overrides the ``bench:window`` span."""
    devices: Dict[int, List[Op]] = {}
    cpu_ops: List[Op] = []
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and m.group(1) != "CPU":
                if line.name not in OP_LINES:
                    continue
                ops = devices.setdefault(int(m.group(2)), [])
                for ev in line.events:
                    ops.append(_op(ev, {} if ev.name.startswith("%")
                                   else _stats(ev)))
                continue
            if not plane.name.startswith("/host:"):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, e))
                    continue
                st = _stats(ev)
                if "hlo_op" in st:
                    cpu_ops.append(Op(str(st["hlo_op"]), s, e,
                                      str(st.get("hlo_module", ""))))
    if not devices and cpu_ops:
        devices[0] = cpu_ops
    if window is None:
        wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        window = (min(s for s, _ in wins), max(e for _, e in wins))
    red = Reduced(window=tuple(window), devices=devices, spans=spans)
    for dev, ops in devices.items():
        nest(ops)
        iv = union(busy_intervals(ops), red.window)
        red.busy_ns[dev] = sum(e - s for s, e in iv)
    return red
