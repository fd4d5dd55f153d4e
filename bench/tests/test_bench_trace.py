"""Trace reduction: a recorded CPU trace (``record_trace.py``) and a
synthetic trace shaped like a TPU profile; the peaks table."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import peaks, readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_recorded_cpu_trace_reduces():
    r = trace.reduce_file(os.path.join(DATA, "cpu_tiny.xplane.pb"))
    # three 20 ms host sleeps inside the window
    assert 0.06 < r.window_s < 0.2
    assert 0 < r.busy_s < r.window_s
    assert 0.5 < r.idle_share() < 1.0
    names = [k for k, _ in r.op_seconds()]
    assert any(n.startswith("dot") for n in names)
    secs, n = r.kernel_seconds(r"^dot")
    assert n == 3 and 0 < secs <= r.busy_s
    gaps = r.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench:host_wait"] * 3
    assert all(0.015 < g[1] < 0.05 for g in gaps)


def test_recorded_tpu_trace_reduces():
    """A v5e trace of the expert-FFN kernel and a matmul, three times
    each, with host sleeps between them (recorded on the chip)."""
    r = trace.reduce_file(os.path.join(DATA, "tpu_tiny.xplane.pb"),
                          window=(0, 10**12))
    assert list(r.devices) == [0]
    secs, n = r.kernel_seconds(readers.EXPERT_FFN)
    assert n == 3 and 0 < secs < r.busy_s
    names = [k for k, _ in r.op_seconds()]
    assert "expert_ffn.1" in names and "fusion" in names
    assert all(not k.startswith("%") for k in names)
    assert r.busy_s < 0.01 and r.idle_share() > 0.9
    assert {g[0] for g in r.idle_gaps(3)} <= {"bench:sleep",
                                              "host:server-loop"}


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _profile():
    """Window 0..100; device ops at 10..30, 20..40 (overlapping), 60..70;
    host spans: decode 5..45, pre_step 50..95."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench:window", 0, 100), _ev("bench:decode", 5, 40),
        _ev("bench:store.pre_step", 50, 45)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step", 10, 60)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 10, 20, long_name="fusion.1 = matmul"),
            _ev("custom-call.2", 20, 20,
                long_name="custom-call.2 = _kernel_ragged"),
            _ev("fusion.1", 60, 10), _ev("copy.3", 150, 10)])])
    return NS(planes=[host, dev])


def test_tpu_shaped_trace_union_ops_kernel_and_gaps():
    r = trace.reduce_profile(_profile())
    assert r.window == (0, 100)
    assert r.busy_ns == {0: 40}                 # (10..40) + (60..70)
    assert r.idle_share() == pytest.approx(0.6)
    assert r.op_seconds() == [("fusion.1", 30e-9), ("custom-call.2", 20e-9)]
    assert r.kernel_seconds("^custom-call") == (20e-9, 1)
    # gaps: 0..10, 40..60, 70..100 ; the longest sits in pre_step
    assert r.idle_gaps() == [("bench:store.pre_step", 30e-9),
                             ("bench:store.pre_step", 20e-9),
                             ("bench:decode", 10e-9)]


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 6), (20, 30)], (1, 25)) == \
        [(1, 9), (20, 25)]


def test_no_window_span_is_an_error():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce_profile(p)


def test_peaks_known_kind_and_unknown_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_containers_and_host_waits_are_not_busy():
    """A while loop (0..100) holding a fusion (10..20), a host callback's
    receive (20..80, waiting on the host) and a fusion (80..90): busy is
    the two fusions, and the receive is reported as a wait."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench:window", 0, 100), _ev("bench:store.pre_step", 15, 70)])])
    ops = [_ev("%while.1 = (s32[]) while(...)", 0, 100),
           _ev("%fusion.2 = bf16[] fusion(...)", 10, 10),
           _ev("%recv-done.3 = (f32[]) recv-done(...), "
               "is_host_transfer=true", 20, 60),
           _ev("%fusion.4 = bf16[] fusion(...)", 80, 10)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    r = trace.reduce_profile(NS(planes=[host, dev]))
    assert r.busy_ns == {0: 20}
    secs = dict(r.op_seconds())
    assert secs["wait:recv-done.3"] == 60e-9
    assert secs["while.1"] == 20e-9              # its own (self) time
    assert r.idle_gaps(1) == [("bench:store.pre_step", 60e-9)]
