"""Host spans of the serving loop and the expert store (``dali:`` profiler
annotations, serving/scheduler.py and serving/expert_store.py): a tiny
continuous-server run with experts in the host store, recorded under the
JAX profiler on the CPU, holds the step, admission and miss-callback
spans with their identifiers, nested as the loop runs them."""
import dataclasses
import glob
import os

import numpy as np
import pytest

import jax

from repro.configs import get_config, make_smoke
from repro.models.model import init_model
from repro.serving.scheduler import Request
from repro.serving.spec import OffloadSpec, ServeSpec


def _spans(trace_dir):
    """Every ``dali:`` event of the recorded profile as (name, start, end,
    line, args); a line is a host thread, named by plane and position."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("dali:"):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                (plane.name, i), dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=4)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=16))
    params = init_model(jax.random.PRNGKey(0), cfg)
    srv = ServeSpec(cfg=cfg, server="continuous", policy="dali",
                    batch_size=2, max_len=32,
                    offload=OffloadSpec(mode="pipelined")
                    ).resolve(params).server()
    rng = np.random.default_rng(3)
    for i in range(3):
        srv.submit(Request(rid=100 + i,
                           prompt=rng.integers(1, cfg.vocab, 10).astype(
                               np.int32), max_new_tokens=5))
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    try:
        srv.run()
    finally:
        jax.profiler.stop_trace()
    return srv, _spans(tdir)


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_step_spans_hold_admissions_and_loop_children(traced_run):
    srv, spans = traced_run
    steps = [s for s in spans if s[0] == "dali:serve.step"]
    assert steps and all({"step", "live"} <= set(s[4]) for s in steps)
    assert [s[4]["step"] for s in steps] == list(range(len(steps)))
    admits = [s for s in spans if s[0] == "dali:serve.admit"]
    assert sorted(s[4]["rid"] for s in admits) == [100, 101, 102]
    assert all(s[4]["prompt_tokens"] == 10 for s in admits)
    decodes = [s for s in spans if s[0] == "dali:serve.decode"]
    assert len(decodes) == srv.metrics.steps
    for name in ("dali:serve.admit", "dali:serve.decode",
                 "dali:serve.tokens", "dali:serve.retire",
                 "dali:store.pre_step", "dali:store.stage",
                 "dali:store.next_target"):
        kids = [s for s in spans if s[0] == name]
        assert kids, name
        # each child runs on the loop's thread, inside one step span
        assert all(any(k[3] == st[3] and _inside(k, st) for st in steps)
                   for k in kids), name


def test_fetch_spans_carry_layer_and_bytes_inside_a_step(traced_run):
    srv, spans = traced_run
    steps = [s for s in spans if s[0] == "dali:serve.step"]
    fetch = [s for s in spans if s[0] == "dali:store.fetch_weights"]
    st = srv.store.stats()
    assert fetch and st["fetch_s"] > 0
    assert {s[4]["layer"] for s in fetch} <= set(range(srv.store.n_layers))
    # one call, and one span, per distinct missing expert of a layer:
    # each returns that expert's expert_bytes and serves >= 1 miss row
    E = srv.cfg.moe.n_routed
    assert all(s[4]["bytes"] == srv.store.expert_bytes for s in fetch)
    assert all(0 <= s[4]["expert"] < E and s[4]["miss_rows"] >= 1
               for s in fetch)
    assert st["fetch_bytes"] == sum(s[4]["bytes"] for s in fetch)
    assert st["fallback_fetches"] == len(fetch)
    assert sum(s[4]["miss_rows"] for s in fetch) == st["fallback_rows"]
    # the callback thread's span lies inside the step that dispatched it
    assert all(any(_inside(f, s) for s in steps) for f in fetch)
