"""Traffic generation and discovery by name; BENCHMARK.json against the
files the harness looks up."""
import json
import os
import re
from collections import Counter
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import check, generator, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CHAT = generator.load_traffic("lmsys-chat-poisson")
SEED = 2**31 + 4242


def test_same_seed_same_schedule_and_lengths():
    a = generator.open_schedule(CHAT, SEED, 60, 32000)
    b = generator.open_schedule(CHAT, SEED, 60, 32000)
    assert [(r.due, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_offer_the_same_work_in_another_order():
    a = generator.open_schedule(CHAT, 1, 60, 32000)
    b = generator.open_schedule(CHAT, 2, 60, 32000)
    assert len(a) == len(b) == round(CHAT["rate_per_s"] * 60)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_new_tokens for r in a) == \
        Counter(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the same arrival times for every seed
    assert [r.due for r in a] == [r.due for r in b]
    assert a[0].due == 0.0 and all(x.due < y.due for x, y in zip(a, a[1:]))
    gaps = np.diff([r.due for r in a])
    assert gaps.mean() == pytest.approx(1 / CHAT["rate_per_s"], rel=0.1)


def test_lengths_follow_the_mix_and_ids_avoid_special_tokens():
    s = generator.open_schedule(CHAT, 3, 200, 32000)
    p = np.array([len(r.prompt) for r in s])
    o = np.array([r.max_new_tokens for r in s])
    P, O = CHAT["prompt"], CHAT["output"]
    assert p.min() >= P["min"] and p.max() <= P["max"]
    assert o.min() >= O["min"] and o.max() <= O["max"]
    assert np.median(p) == pytest.approx(P["median"], rel=0.1)
    assert np.median(o) == pytest.approx(O["median"], rel=0.1)
    # the means that the medians were fitted to (LMSYS-Chat-1M, Table 1)
    assert p.mean() == pytest.approx(69.5, rel=0.05)
    assert o.mean() == pytest.approx(214.5, rel=0.05)
    assert min(r.prompt.min() for r in s) >= generator.FIRST_ID


def test_closed_stream_is_seeded_and_fixed_sizes_stay_fixed():
    t = generator.load_traffic("decode-closed")
    a, b = generator.closed_stream(t, SEED, 32000), \
        generator.closed_stream(t, SEED, 32000)
    for _ in range(70):
        x, y = next(a), next(b)
        assert np.array_equal(x.prompt, y.prompt)
        assert len(x.prompt) == 512 and x.max_new_tokens == 1536


def test_warm_requests_cover_every_bucket_once():
    w = generator.warm_requests(CHAT, 1, 32000)
    buckets = [generator.bucket_len(len(r.prompt), CHAT["min_bucket"],
                                    CHAT["max_len"]) for r in w]
    assert sorted(buckets) == [64, 128, 256, 512]
    assert len(w) == CHAT["warm"]["admissions"]


def test_ttft_counts_from_the_due_time():
    from bench import readers
    r = NS(due=10.0, times=[10.5, 10.6, 10.8], admit=(10.2, 10.5))
    ctx = NS(due_in_window=lambda: [r], recs=[r],
             in_window=lambda t: 10 <= t <= 11)
    assert readers.ttft_ms(ctx) == [pytest.approx(500.0)]
    assert readers.queue_wait_ms(ctx) == [pytest.approx(200.0)]
    assert readers.itl_ms(ctx) == [pytest.approx(100.0), pytest.approx(200.0)]
    # the earlier stderr line carries them, read and not judged
    line = run.lateness(ctx)
    assert line["ttft_p90_ms"] == pytest.approx(500.0)
    assert line["queue_wait_p90_ms"] == pytest.approx(200.0)


def test_every_cell_config_traffic_limit_and_metric_is_found_by_name():
    for c in BENCH["workloads"]:
        cell, m, t, lim = run.load_cell(BENCH, c["name"])
        assert m["name"] == c["config"]
        assert {"compare", "sample_tokens", "max_requests"} <= set(lim)
        assert "widest_gap" in lim["compare"]
        assert t["loop"] in ("open", "closed")
        check.reference(m)                   # the plain reference imports
        for trace in (False, True):
            names = run.metric_names(BENCH, c["name"], trace)
            assert names, (c["name"], trace)
            for n, _ in names:
                assert callable(run.load_reader(n))
        e2e = [n for n, _ in run.metric_names(BENCH, c["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    items = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + \
        BENCH["per_layer"]
    names = [x["name"] for x in items]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        m = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(c["reduced"]) == sorted(m["reduced"])
    for x in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", x["unit"])
        assert x["better"] in ("lower", "higher")
    e2e = {x["name"] for x in BENCH["end_to_end"]}
    layers = {x["layer"] for x in BENCH["per_layer"]}
    assert layers == {"scheduler", "offload", "model step", "kernel",
                      "device"}
    for x in BENCH["per_layer"]:
        assert x["moves"] in e2e
        for w in x["workloads"]:        # each cell reports what it moves
            assert x["moves"] in [n for n, _ in
                                  run.metric_names(BENCH, w, False)]
    for x in BENCH["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.25
    assert all(c["chips"] == 1 for c in BENCH["workloads"])
