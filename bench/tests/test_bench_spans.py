"""The program's ``dali:`` spans and the per-layer metrics that read them
(``bench/spans.py`` and the five metric files it serves): a recorded CPU
trace (``record_spans_trace.py``), a hand-built trace shaped like a TPU
profile with known answers, the counter readers, a traced tiny run of
each cell through ``run.run_cell``, and the existing reduction pinned to
what it read before these spans existed."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import run, spans, trace
from bench.peaks import peaks_for
from bench.tests import record_spans_trace, tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = {"mixtral-host-decode": ("miss_gather_ms_per_step.decode",
                               "miss_mb_per_token.decode",
                               "miss_transfer_ms_per_step.decode",
                               "staging_ms_per_step.decode"),
       "mixtral-hbm-chat": ("idle_sched_share.chat",)}


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def test_recorded_loop_spans_and_its_own_idle_time():
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "cpu_spans.xplane.pb")
    sp = spans.reduce_spans(ProfileData.from_file(path))
    red = trace.reduce_file(path)
    steps = [x for x in sp if x.name == spans.STEP]
    assert [x.args["step"] for x in steps] == [0, 1, 2]
    fetch = [x for x in sp if x.name == spans.FETCH]
    assert len(fetch) == 3 and all(x.args["layer"] == 0 for x in fetch)
    for name in ("dali:serve.decode", "dali:serve.tokens", spans.FETCH):
        assert all(any(s.start <= x.start and x.end <= s.end for s in steps)
                   for x in sp if x.name == name), name
    # the three host-only sleeps inside a step and in no child
    idle = spans.sched_idle_ns(red, sp) / 1e9
    assert idle == pytest.approx(3 * record_spans_trace.SLEEP_S, rel=0.1)
    # a CPU trace has no host-transfer ops: nothing waits on the host
    assert spans.host_wait_ns(red, sp) == (0.0, 0.0)


def _tpu_profile():
    """Window 0..200.  Loop thread: steps 0..100 and 100..200; children
    decode 10..40, tokens 40..60, decode 110..150.  Callback thread:
    fetch_weights 20..30 and 120..135.  Device: a while loop 10..60
    holding fusions 10..15 and 45..55 around a host-transfer wait
    15..45; a fusion 70..80; fusion 110..115, wait 115..145, fusion
    145..148."""
    loop = NS(name="python", events=[
        _ev("bench:window", 0, 200),
        _ev("dali:serve.step", 0, 100, step=0, live=2),
        _ev("dali:serve.decode", 10, 30), _ev("dali:serve.tokens", 40, 20),
        _ev("dali:serve.step", 100, 100, step=1, live=2),
        _ev("dali:serve.decode", 110, 40)])
    cb = NS(name="callback", events=[
        _ev("dali:store.fetch_weights", 20, 10, layer=0),
        _ev("dali:store.fetch_weights", 120, 15, layer=1)])
    wait = ", is_host_transfer=true"
    ops = [_ev("%while.1 = (s32[]) while(...)", 10, 50),
           _ev("%fusion.2 = bf16[] fusion(...)", 10, 5),
           _ev(f"%recv-done.3 = (f32[]) recv-done(...){wait}", 15, 30),
           _ev("%fusion.4 = bf16[] fusion(...)", 45, 10),
           _ev("%fusion.5 = bf16[] fusion(...)", 70, 10),
           _ev("%fusion.2 = bf16[] fusion(...)", 110, 5),
           _ev(f"%recv-done.3 = (f32[]) recv-done(...){wait}", 115, 30),
           _ev("%fusion.4 = bf16[] fusion(...)", 145, 3)]
    return NS(planes=[NS(name="/host:CPU", lines=[loop, cb]),
                      NS(name="/device:TPU:0",
                         lines=[NS(name="XLA Ops", events=ops)])])


def test_tpu_shaped_trace_splits_waits_and_finds_loop_idle():
    pd = _tpu_profile()
    red, sp = trace.reduce_profile(pd), spans.reduce_spans(pd)
    assert {x.line for x in sp if x.name == spans.FETCH} == \
        {("/host:CPU", 1)}
    assert [x.args["layer"] for x in sp if x.name == spans.FETCH] == [0, 1]
    # waits 15..45 + 115..145; fetch spans cover 20..30 and 120..135
    assert spans.host_wait_ns(red, sp) == (60.0, 35.0)
    # the loop's own code: 0..10, 60..110, 150..200 (the callback
    # thread's spans are not the loop's children); busy 70..80 inside
    assert spans.sched_idle_ns(red, sp) == 100.0
    assert trace.reduce_profile(pd).spans == [("bench:window", 0, 200)]


def _ctx(red, **delta):
    return NS(trace=red, delta=lambda k: delta.get(k), recs=[],
              in_window=lambda t: True)


def test_trace_readers_known_answers_and_silence(monkeypatch):
    pd = _tpu_profile()
    red, sp = trace.reduce_profile(pd), spans.reduce_spans(pd)
    monkeypatch.setattr(spans, "traced_spans", lambda ctx: sp)
    transfer = run.load_reader("miss_transfer_ms_per_step.decode")
    sched = run.load_reader("idle_sched_share.chat")
    assert transfer(_ctx(red, steps=2)) == pytest.approx(35 / 1e6 / 2)
    assert sched(_ctx(red, steps=2)) == pytest.approx(50.0)
    # a program without the spans (an older commit) reads nothing
    monkeypatch.setattr(spans, "traced_spans",
                        lambda ctx: [x for x in sp if x.name == spans.STEP])
    assert transfer(_ctx(red, steps=2)) is None
    monkeypatch.setattr(spans, "traced_spans", lambda ctx: [])
    assert sched(_ctx(red, steps=2)) is None
    # an untraced run reads nothing either
    monkeypatch.undo()
    assert transfer(_ctx(None, steps=2)) is None
    assert sched(_ctx(None, steps=2)) is None


def test_counter_readers_known_answers_and_silence():
    rec = NS(times=[1.0, 2.0, 3.0, 4.0])
    ctx = NS(recs=[rec], in_window=lambda t: True,
             delta={"steps": 2, "store.fetch_s": 3.0,
                    "store.fetch_bytes": 4_000_000_000,
                    "store.stage_s": 0.1, "store.commit_s": 0.3}.get)
    read = {n: run.load_reader(n) for n in NEW["mixtral-host-decode"]
            if n != "miss_transfer_ms_per_step.decode"}
    assert read["miss_gather_ms_per_step.decode"](ctx) == \
        pytest.approx(1500.0)
    assert read["miss_mb_per_token.decode"](ctx) == pytest.approx(1000.0)
    assert read["staging_ms_per_step.decode"](ctx) == pytest.approx(200.0)
    # a store without the counters (an older commit) reads nothing
    old = NS(recs=[rec], in_window=lambda t: True,
             delta={"steps": 2}.get)
    assert all(r(old) is None for r in read.values())


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_traced_tiny_run_reports_the_new_metrics(cell):
    mode, traffic = tiny.CELLS[cell]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        names = run.metric_names(json.load(fh), cell, True)
    assert set(NEW[cell]) <= {n for n, _ in names}
    r = run.run_cell(tiny.config(mode), tiny.traffic(traffic),
                     tiny.limits(cell), 2**31 + 5, 0, trace=True,
                     names=names, peak=peaks_for("TPU v5 lite"),
                     close_after=tiny.CLOSE_AFTER)
    assert r["correct"]
    for n in NEW[cell]:
        assert r["metrics"][n]["value"] >= 0, n
    if cell == "mixtral-hbm-chat":
        assert 0 < r["metrics"]["idle_sched_share.chat"]["value"] < \
            r["metrics"]["idle_share.chat"]["value"]


def test_existing_reduction_reads_what_it_read_before():
    """The two recorded traces reduce to the numbers the reduction gave
    before the program had spans of its own."""
    r = trace.reduce_file(os.path.join(DATA, "cpu_tiny.xplane.pb"))
    assert r.busy_s == 0.000920856
    assert r.op_seconds() == [
        ("dot_general.1", 0.000782739), ("wrapped_reduce-window", 8.1521e-05),
        ("wrapped_tanh", 5.2667e-05), ("wrapped_reduce", 3.929e-06)]
    assert r.idle_gaps(4) == [
        ("bench:host_wait", 0.02044616), ("bench:host_wait", 0.020441882),
        ("bench:host_wait", 0.020281113), ("bench:decode", 0.000147299)]
    r = trace.reduce_file(os.path.join(DATA, "tpu_tiny.xplane.pb"),
                          window=(0, 10**12))
    assert r.busy_s == 0.000424701
    assert r.op_seconds() == [
        ("fusion", 0.000270523), ("expert_ffn.1", 0.000113998),
        ("copy-done", 3.4515e-05), ("convert_element_type.0", 5.624e-06),
        ("copy-start", 4.1e-08)]
    assert r.idle_gaps(7) == [
        ("bench:sleep", 999.938034881), ("host:server-loop", 0.042634521),
        ("bench:sleep", 0.0042368), ("bench:sleep", 0.004215252),
        ("bench:kern", 0.003967641), ("bench:sleep", 0.003251726),
        ("bench:sleep", 0.003234467)]
