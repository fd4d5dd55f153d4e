"""Expert-parallel exchange: workload-sized ragged all_to_all vs the
dense full-capacity exchange (models/moe_ep.py, DESIGN.md §6), on a
(1, n) mesh over every device JAX sees — the chips of a TPU host, or
host-platform CPU devices forced from outside with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

The dense path ships every (E/tp, C, d) capacity bucket through BOTH
all_to_alls regardless of how empty it is; the ragged path exchanges
per-device per-expert counts first (a (tp, E/tp) int32 all_to_all) and
ships only C_x = next_pow2(global max demand) rows per bucket, clamped
to C via a static capacity ladder.  Link bytes are computed analytically
from the shipped shapes (host CPU wall time does not model a real
interconnect — DESIGN.md §2 — but the per-step µs still tracks the
dispatch/compute savings on skewed traffic); bytes scale with the actual
workload, so uniform decode-like routing ships a small fraction of C and
Zipf(1.2)-skewed routing ships the hot expert's rung.

  PYTHONPATH=src python -m benchmarks.ep_exchange            # full sweep
  PYTHONPATH=src python -m benchmarks.ep_exchange --smoke    # CI tiers

It runs on whatever platform JAX selects; JAX_PLATFORMS and the forced
host-device count come from the caller's environment, never from here.

Emits the ``name,us_per_call,derived`` CSV contract on stdout and a
machine-readable ``reports/bench/BENCH_ep_exchange.json`` (rendered into
EXPERIMENTS.md by benchmarks/report_md.py)."""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn
from repro.launch import sharding as shd
from repro.launch.mesh import ep_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import apply_moe, init_moe
from repro.models.moe_ep import ep_applicable

BENCH_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "reports", "bench"))

E, K, D_MODEL, D_EXPERT = 64, 2, 128, 256
ATOL = {"float32": 2e-5, "bfloat16": 2e-2}
ROUTINGS = ("uniform", "zipf")


def make_cfg(dtype: str) -> ModelConfig:
    # capacity_factor=0 ("full", no drops) is the serving-realistic EP
    # regime: C = per-device tokens, so the dense exchange is maximally
    # workload-oblivious and the ragged saving is the honest number
    return ModelConfig(d_model=D_MODEL, d_ff=D_EXPERT, vocab=64,
                       dtype=dtype, param_dtype=dtype,
                       moe=MoEConfig(n_routed=E, top_k=K, d_expert=D_EXPERT,
                                     capacity_factor=0.0))


def routed_x(kind: str, B: int, S: int, dtype, seed: int = 0):
    """Tokens whose top-1 expert follows the requested distribution (the
    router below is 6*eye, so logit_e = 6*x[:, e])."""
    rng = np.random.default_rng(seed)
    T = B * S
    x = 0.05 * rng.standard_normal((T, D_MODEL))
    if kind == "uniform":
        tgt = rng.integers(0, E, T)
    else:                                   # zipf(1.2), paper-style skew
        p = 1.0 / np.arange(1, E + 1) ** 1.2
        tgt = rng.choice(E, size=T, p=p / p.sum())
    x[np.arange(T), tgt] += 3.0
    return jnp.asarray(x.reshape(B, S, D_MODEL), dtype)


def link_bytes(cap: int, itemsize: int, tp: int, with_counts: bool) -> int:
    """Per-device on-link bytes for one MoE layer step: two bucket
    all_to_alls of (E/tp rows per destination) x cap x d, of which
    (tp-1)/tp actually crosses the link, plus the (tp, E/tp) int32 count
    exchange for the ragged path."""
    bucket = 2 * E * cap * D_MODEL * itemsize * (tp - 1) // tp
    return bucket + (E * 4 * (tp - 1) // tp if with_counts else 0)


def bench_one(kind: str, dtype: str, B: int, S: int, reps: int,
              mesh, overlap: bool = True) -> Dict:
    cfg = make_cfg(dtype)
    dt = jnp.dtype(cfg.dtype)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    params = dict(params,
                  router=6.0 * jnp.eye(D_MODEL, E, dtype=jnp.float32))
    x = routed_x(kind, B, S, dt)
    tp = mesh.shape["model"]
    lmap = shd.logical_map_for(cfg, "prefill_32k", mesh)
    with mesh, shd.rules(mesh, lmap, "tp"):
        assert ep_applicable(cfg, B, S)
        ragged = jax.jit(lambda p, x: apply_moe(p, x, cfg,
                                                count_overlap=overlap))
        dense = jax.jit(lambda p, x: apply_moe(p, x, cfg,
                                               force_exchange="dense"))
        y_r, i_r = ragged(params, x)
        y_d, i_d = dense(params, x)
        # the overlapped count exchange must be a pure scheduling change:
        # same outputs, same shipped capacity (bit-identical, DESIGN.md §9)
        other = jax.jit(lambda p, x: apply_moe(p, x, cfg,
                                               count_overlap=not overlap))
        y_o, i_o = other(params, x)
        overlap_parity = (bool(np.array_equal(np.asarray(y_r),
                                              np.asarray(y_o)))
                          and int(i_r["ep_cx"]) == int(i_o["ep_cx"]))
        t_ragged = time_fn(lambda: ragged(params, x), reps=reps)
        t_dense = time_fn(lambda: dense(params, x), reps=reps)
    C, cx = int(i_d["ep_cx"]), int(i_r["ep_cx"])
    err = float(jnp.abs(y_r.astype(jnp.float32)
                        - y_d.astype(jnp.float32)).max())
    d_bytes = link_bytes(C, dt.itemsize, tp, with_counts=False)
    r_bytes = link_bytes(cx, dt.itemsize, tp, with_counts=True)
    return {
        "routing": kind, "dtype": dtype, "B": B, "S": S,
        "C": C, "cx": cx,
        "dense_link_bytes": d_bytes, "ragged_link_bytes": r_bytes,
        "byte_ratio": r_bytes / d_bytes,
        "dense_us": t_dense, "ragged_us": t_ragged,
        "count_overlap": overlap,
        "overlap_parity": overlap_parity,
        "parity_max_err": err, "atol": ATOL[dtype],
        "parity_ok": err < ATOL[dtype],
        "workload_equal": bool(np.array_equal(
            np.asarray(i_r["workload"]), np.asarray(i_d["workload"]))),
        "dropped_equal": int(i_r["dropped"]) == int(i_d["dropped"]),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes + reps for CI")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="hoist the count all_to_all ahead of the "
                         "dispatch math (attention-overlapped count "
                         "exchange, DESIGN.md §9); either way the "
                         "opposite setting is parity-checked")
    ap.add_argument("--reps", type=int, default=0)
    ap.add_argument("--resilience", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="also run the degraded-link EP trial "
                         "(repro/launch/ep_serve.py): healthy vs frozen "
                         "placement vs watchdog-driven re-route under an "
                         "injected slow link, asserting the bit-exact "
                         "re-route contract (DESIGN.md §13)")
    ap.add_argument("--json", default=None,
                    help="output path (default reports/bench/"
                         "BENCH_ep_exchange.json)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    mesh = ep_mesh(E)
    tp = mesh.shape["model"]
    B, S = (4, 160) if args.smoke else (8, 320)
    dtypes = ("float32",) if args.smoke else ("float32", "bfloat16")
    reps = args.reps or (5 if args.smoke else 20)

    rows: List[Dict] = []
    print("name,us_per_call,derived")
    for dtype in dtypes:
        for kind in ROUTINGS:
            r = bench_one(kind, dtype, B, S, reps, mesh,
                          overlap=args.overlap)
            rows.append(r)
            print(f"ep_exchange_dense_{kind}_{dtype},{r['dense_us']:.2f},"
                  f"C={r['C']}")
            print(f"ep_exchange_ragged_{kind}_{dtype},{r['ragged_us']:.2f},"
                  f"cx={r['cx']} bytes={100 * r['byte_ratio']:.0f}%")
            assert r["parity_ok"], (kind, dtype, r["parity_max_err"])
            assert r["workload_equal"] and r["dropped_equal"], (kind, dtype)
            assert r["overlap_parity"], (kind, dtype)

    from benchmarks.report_md import ep_exchange_table
    print()
    for line in ep_exchange_table(rows):
        print(line)
    skewed = [r for r in rows if r["routing"] == "zipf"]
    worst = max(r["byte_ratio"] for r in skewed)
    print(f"\nzipf worst-case ragged/dense link bytes: {100 * worst:.0f}%")

    resilience = None
    if args.resilience:
        from benchmarks.report_md import ep_resilience_table
        from repro.launch.ep_serve import run_resilience_trials
        resilience = run_resilience_trials(steps=20 if args.smoke else 26)
        print()
        for tr in resilience["trials"]:
            fm = tr["fault_ms_per_step"]
            fb = tr["fault_pair_bytes_per_step"]
            print(f"ep_resilience_{tr['name']},"
                  f"{1e3 * tr['ms_per_step']:.2f},"
                  + (f"fault_window_ms={fm:.1f}"
                     f" degraded_pair_kb={fb / 1e3:.1f}"
                     f" reroutes={tr['reroutes']}" if fm else "healthy"))
        print()
        for line in ep_resilience_table(resilience):
            print(line)
        assert resilience["ok"], resilience["verdicts"]

    out = args.json or os.path.join(BENCH_DIR, "BENCH_ep_exchange.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump({"backend": jax.default_backend(), "tp": tp,
                   "E": E, "top_k": K, "d_model": D_MODEL,
                   "d_expert": D_EXPERT, "smoke": bool(args.smoke),
                   "count_overlap": bool(args.overlap),
                   "reps": reps, "rows": rows,
                   "resilience": resilience}, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
