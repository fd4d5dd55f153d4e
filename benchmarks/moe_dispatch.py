"""MoE decode dispatch: dense capacity-bucket sweep vs the workload-aware
sparse fast path (DESIGN.md §4), measured µs/step on a single MoE layer.

The dense path computes all E capacity buckets every step — at decode
batch sizes that is ~E·C_min/(B·K)× the useful FFN rows.  The sparse path
gathers the activated experts' weight slices and computes exactly B·K
rows.  Both paths share the router/argsort front-end, so the measured gap
is the dispatch overcompute DALI's workload observable makes avoidable.

  PYTHONPATH=src python -m benchmarks.moe_dispatch            # full sweep
  PYTHONPATH=src python -m benchmarks.moe_dispatch --smoke    # CI tier-2

Emits the ``name,us_per_call,derived`` CSV contract on stdout and a
machine-readable ``reports/bench/BENCH_moe_dispatch.json`` so the perf
trajectory is tracked across PRs (rendered into EXPERIMENTS.md by
benchmarks/report_md.py)."""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import jax

from benchmarks.common import time_fn
from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import apply_moe, expert_capacity, init_moe

BENCH_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "reports", "bench"))

# decode-realistic layer proportions (reduced d for CPU timing sanity);
# E sweeps the paper's model range: Mixtral 8, DeepSeek-lite 64, Qwen3 128
EXPERT_COUNTS = (8, 64, 128)
BATCHES = (1, 4, 16)
D_MODEL, D_EXPERT, TOP_K = 256, 512, 2


def layer_cfg(E: int) -> ModelConfig:
    return ModelConfig(d_model=D_MODEL, d_ff=D_EXPERT, vocab=64,
                       dtype="float32", param_dtype="float32",
                       moe=MoEConfig(n_routed=E, top_k=TOP_K,
                                     d_expert=D_EXPERT))


def bench_one(E: int, B: int, reps: int) -> Dict:
    cfg = layer_cfg(E)
    params = init_moe(jax.random.PRNGKey(E), cfg)
    x = jax.random.normal(jax.random.PRNGKey(B), (B, 1, D_MODEL))
    dense = jax.jit(lambda p, x: apply_moe(p, x, cfg,
                                           force_path="dense")[0])
    sparse = jax.jit(lambda p, x: apply_moe(p, x, cfg,
                                            force_path="sparse")[0])
    t_dense = time_fn(dense, params, x, reps=reps)
    t_sparse = time_fn(sparse, params, x, reps=reps)
    C = expert_capacity(cfg.moe, B)
    return {
        "E": E, "batch": B, "top_k": TOP_K,
        "dense_rows": E * C, "sparse_rows": B * TOP_K,
        "dense_us": t_dense, "sparse_us": t_sparse,
        "speedup": t_dense / t_sparse if t_sparse else float("inf"),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep + reps for CI tier-2")
    ap.add_argument("--reps", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="output path (default reports/bench/"
                         "BENCH_moe_dispatch.json)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    experts = (8, 64) if args.smoke else EXPERT_COUNTS
    batches = (1, 4) if args.smoke else BATCHES
    reps = args.reps or (5 if args.smoke else 30)

    rows: List[Dict] = []
    print("name,us_per_call,derived")
    for E in experts:
        for B in batches:
            r = bench_one(E, B, reps)
            rows.append(r)
            print(f"moe_dispatch_dense_E{E}_B{B},{r['dense_us']:.2f},"
                  f"rows={r['dense_rows']}")
            print(f"moe_dispatch_sparse_E{E}_B{B},{r['sparse_us']:.2f},"
                  f"speedup={r['speedup']:.2f}x")

    from benchmarks.report_md import moe_dispatch_table
    print()
    for line in moe_dispatch_table(rows):
        print(line)

    out = args.json or os.path.join(BENCH_DIR, "BENCH_moe_dispatch.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        # smoke/reps recorded so a reduced CI sweep is never mistaken for
        # the full-fidelity trajectory record
        json.dump({"backend": jax.default_backend(),
                   "d_model": D_MODEL, "d_expert": D_EXPERT,
                   "smoke": bool(args.smoke), "reps": reps,
                   "rows": rows}, f, indent=2)
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
