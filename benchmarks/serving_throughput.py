"""Serving-throughput benchmark: continuous batching vs the wave preset
under a mixed-length Poisson workload.

Drives both servers (serving/scheduler.py) with the SAME arrival process —
exponential inter-arrival gaps at ``--rate`` req/s, prompt and output
lengths drawn uniformly from ``[--min-prompt, --max-prompt]`` /
``[--min-new, --max-new]`` — and reports per-server decode tok/s, total
generated tok/s, mean slot occupancy, and per-request latency / TTFT
percentiles.

  PYTHONPATH=src python -m benchmarks.serving_throughput \
      --arch mixtral-8x7b --requests 24 --batch 4 --rate 8

Reading the columns (also rendered into EXPERIMENTS.md by report_md.py):
  decode tok/s   emitted decode tokens / decode wall time — the headline
                 number; wave mode loses it to pad-and-lockstep dead slots
  TTFT p50/p99   arrival -> first token: admission latency; continuous
                 batching admits into freed slots instead of waiting for a
                 whole wave to drain
  lat p50/p99    arrival -> last token; p99 is the tail a production SLA
                 cares about
Each server is run once untimed to absorb jit compilation, then measured.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import load_model
from repro.serving.scheduler import SERVER_PRESETS, Request, make_server
from repro.serving.steps import default_dali_config

REPORT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "reports", "serving"))
BENCH_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "reports", "bench"))


def make_workload(bm, n: int, min_prompt: int, max_prompt: int,
                  min_new: int, max_new: int, rate: float, seed: int):
    """(prompt, max_new, arrival_offset) tuples; offsets are Poisson."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n)
    offsets = np.cumsum(gaps)
    out = []
    for i in range(n):
        plen = int(rng.integers(min_prompt, max_prompt + 1))
        out.append((bm.corpus.sample(rng, plen),
                    int(rng.integers(min_new, max_new + 1)),
                    float(offsets[i])))
    return out


def run_server(kind: str, bm, workload, batch: int, max_len: int,
               cache_ratio: float, timed: bool) -> Dict:
    dcfg = default_dali_config(bm.cfg, cache_ratio=cache_ratio)
    res_vecs = None
    if dcfg is not None:
        import jax.numpy as jnp
        res_vecs = jnp.asarray(np.stack(bm.res_vecs))
    # make_server is the legacy kwarg factory — this benchmark (like
    # examples/offload_ablation.py) DELIBERATELY stays on it as the
    # back-compat guard for the ServeSpec shims in serving/spec.py
    server = make_server(kind, bm.params, bm.cfg, batch_size=batch,
                         max_len=max_len, dali_cfg=dcfg, res_vecs=res_vecs)
    t0 = time.perf_counter()
    for i, (prompt, max_new, off) in enumerate(workload):
        at = t0 + (off if timed else 0.0)
        server.submit(Request(rid=i, prompt=prompt, max_new_tokens=max_new,
                              not_before=at, submitted_at=at))
    done = server.run()
    t1 = time.perf_counter()
    lat = np.array([r.latency for r in done])
    ttft = np.array([r.ttft for r in done if r.first_token_at])
    gen = sum(len(r.output) for r in done)
    m = server.metrics
    return {
        "server": kind,
        "requests": len(done),
        "generated_tokens": gen,
        "decode_tok_s": m.decode_tokens / m.decode_s if m.decode_s else 0.0,
        "total_tok_s": gen / (t1 - t0) if t1 > t0 else 0.0,
        "mean_occupancy": m.mean_occupancy(),
        "prefill_tok_s": (m.prefill_tokens / m.prefill_s
                          if m.prefill_s else 0.0),
        "lat_p50_s": float(np.percentile(lat, 50)),
        "lat_p99_s": float(np.percentile(lat, 99)),
        "ttft_p50_s": float(np.percentile(ttft, 50)) if len(ttft) else 0.0,
        "ttft_p99_s": float(np.percentile(ttft, 99)) if len(ttft) else 0.0,
        "dali_hit_rate": m.dali.hit_rate(),
        "dali_moe_time_est_s": m.dali.moe_time_est,
        "dali_link_time_est_s": m.dali.link_time_est,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--min-new", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--cache-ratio", type=float, default=0.5)
    ap.add_argument("--servers", default="both",
                    choices=["both"] + sorted(SERVER_PRESETS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workload + calibration training for CI "
                         "tier-2 (recorded in the trajectory JSON so a "
                         "smoke row is never diffed against a full run)")
    ap.add_argument("--json", default=None,
                    help="output path (default reports/serving/<arch>.json)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    if args.smoke:
        args.requests = min(args.requests, 8)
        args.max_prompt = min(args.max_prompt, 24)
        args.max_new = min(args.max_new, 12)

    bm = load_model(args.arch, train_steps=60 if args.smoke else 150)
    workload = make_workload(bm, args.requests, args.min_prompt,
                             args.max_prompt, args.min_new, args.max_new,
                             args.rate, args.seed)
    kinds = (sorted(SERVER_PRESETS) if args.servers == "both"
             else [args.servers])

    results: List[Dict] = []
    for kind in kinds:
        print(f"== {kind}: warmup (jit)")
        run_server(kind, bm, workload, args.batch, args.max_len,
                   args.cache_ratio, timed=False)
        print(f"== {kind}: measured run")
        r = run_server(kind, bm, workload, args.batch, args.max_len,
                       args.cache_ratio, timed=True)
        results.append(r)
        print(f"   decode={r['decode_tok_s']:.1f} tok/s "
              f"total={r['total_tok_s']:.1f} tok/s "
              f"occ={r['mean_occupancy']:.2f} "
              f"lat p50={r['lat_p50_s']:.2f}s p99={r['lat_p99_s']:.2f}s "
              f"ttft p50={r['ttft_p50_s']:.2f}s p99={r['ttft_p99_s']:.2f}s")

    hdr = ("| server | decode tok/s | total tok/s | occ | lat p50 | "
           "lat p99 | TTFT p50 | TTFT p99 | DALI hit% |")
    print("\n" + hdr)
    print("|" + "---|" * 9)
    for r in results:
        print(f"| {r['server']} | {r['decode_tok_s']:.1f} "
              f"| {r['total_tok_s']:.1f} | {r['mean_occupancy']:.2f} "
              f"| {r['lat_p50_s']:.2f}s | {r['lat_p99_s']:.2f}s "
              f"| {r['ttft_p50_s']:.2f}s | {r['ttft_p99_s']:.2f}s "
              f"| {100 * r['dali_hit_rate']:.1f} |")

    by_kind = {r["server"]: r for r in results}
    if {"continuous", "wave"} <= set(by_kind):
        c, w = by_kind["continuous"], by_kind["wave"]
        ratio = (c["decode_tok_s"] / w["decode_tok_s"]
                 if w["decode_tok_s"] else float("inf"))
        print(f"\ncontinuous/wave decode speedup: {ratio:.2f}x")

    out = args.json or os.path.join(REPORT_DIR, f"{args.arch}.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump({"arch": args.arch,
                   "workload": {"requests": args.requests,
                                "batch": args.batch, "rate": args.rate,
                                "prompt": [args.min_prompt, args.max_prompt],
                                "new": [args.min_new, args.max_new]},
                   "servers": by_kind}, f, indent=2)
    print(f"wrote {out}")

    # compact trajectory record (merged across archs): the numbers a later
    # PR diffs against to catch serving-throughput regressions
    bench = os.path.join(BENCH_DIR, "BENCH_serving.json")
    os.makedirs(BENCH_DIR, exist_ok=True)
    merged = {}
    if os.path.exists(bench):
        with open(bench) as f:
            merged = json.load(f)
    # per-server update so a single-server run never drops the other
    # server's recorded trajectory; each record carries ITS OWN workload
    # so a cross-PR diff can tell code deltas from workload deltas even
    # when servers were last measured under different workloads
    workload = {
        "requests": args.requests, "batch": args.batch, "rate": args.rate,
        "prompt": [args.min_prompt, args.max_prompt],
        "new": [args.min_new, args.max_new], "max_len": args.max_len,
        "smoke": bool(args.smoke)}
    merged.setdefault(args.arch, {}).update({
        k: {"decode_tok_s": round(r["decode_tok_s"], 2),
            "total_tok_s": round(r["total_tok_s"], 2),
            "ttft_p50_s": round(r["ttft_p50_s"], 4),
            "workload": workload}
        for k, r in by_kind.items()})
    with open(bench, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
    print(f"wrote {bench}")


if __name__ == "__main__":
    main()
