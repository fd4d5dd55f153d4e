"""Paper Fig. 19-style breakdown: Naive (all-CPU) -> +Greedy Assignment ->
+Residual Prefetching -> +Workload-Aware Cache, replayed over a real
routing trace of a trained smoke-scale MoE under the paper's local-PC cost
profile — then the same "dali" policy run PHYSICALLY: expert weights in a
host store, decode against a device slot pool, modeled vs blocking vs
overlapped vs pipelined H2D streaming side by side (DESIGN.md §8–§9).

  PYTHONPATH=src python examples/offload_ablation.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, make_smoke
from repro.core.cost_model import CostModel, LOCAL_PC
from repro.core.prefetch import (FeaturePrefetcher, ResidualPrefetcher)
from repro.core.residual import calibrate_residuals
from repro.core.simulator import FrameworkSpec, simulate
from repro.core.tracing import capture_decode_trace, gate_weights
from repro.data.pipeline import MarkovCorpus
from repro.launch.train import train_loop


def main():
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=4)
    corpus = MarkovCorpus(vocab=cfg.vocab, seed=0)
    params, _, _ = train_loop(cfg, 100, 8, 64, corpus=corpus)

    rng = np.random.default_rng(1)
    prompts = jnp.asarray(np.stack([corpus.sample(rng, 32)
                                    for _ in range(8)]))
    trace = capture_decode_trace(params, cfg, prompts, n_decode=32,
                                 greedy=False)
    calib = capture_decode_trace(
        params, cfg, jnp.asarray(np.stack([corpus.sample(rng, 32)
                                           for _ in range(8)])),
        n_decode=16, greedy=False, seed=7)
    res = calibrate_residuals([calib])
    gws = gate_weights(params, cfg)
    pfs = {"residual": ResidualPrefetcher(gws, res, cfg.moe),
           "feature": FeaturePrefetcher(gws, cfg.moe)}

    cm = CostModel.for_config(get_config("mixtral-8x7b"), LOCAL_PC)
    E = cfg.moe.n_routed
    steps = [
        FrameworkSpec("Naive (all CPU)", assignment="all_cpu"),
        FrameworkSpec("+Greedy Assignment", assignment="greedy"),
        FrameworkSpec("+Residual Prefetch", assignment="greedy",
                      prefetch="residual", prefetch_size=1),
        FrameworkSpec("+Workload Cache", assignment="greedy",
                      prefetch="residual", prefetch_size=1,
                      cache_policy="workload", cache_size=E // 4,
                      w_size=4, u_size=1),
    ]
    base = None
    print(f"{'config':26s} {'tok/s':>8s} {'speedup':>8s} {'hit%':>6s}")
    for spec in steps:
        r = simulate(trace, cfg, cm, spec, prefetchers=pfs, batch=8,
                     ctx_len=32)
        base = base or r.tokens_per_s
        print(f"{spec.name:26s} {r.tokens_per_s:8.2f} "
              f"{r.tokens_per_s/base:7.2f}x {100*r.cache_hit_rate:5.1f}")

    # the same comparison through the unified OffloadPolicy registry —
    # these are the IDENTICAL policy definitions the jitted serving path
    # runs (launch/serve.py --policy ...), replayed via their NumPy
    # mirrors (core/policy.py, DESIGN.md §7)
    from repro.core.policy import DaliConfig
    from repro.core.simulator import simulate_policy
    # cost constants from the FULL-size paper model (same cm as the table
    # above), not the smoke dims — geometry matches the +Workload row
    dcfg = DaliConfig.from_cost_model(
        cm, n_moe_layers=trace.n_moe_layers, n_experts=E,
        cache_size=E // 4, prefetch_size=1, w_size=4, u_size=1)
    print(f"\n{'--policy':26s} {'tok/s':>8s} {'hit%':>6s}")
    for name in ("none", "all_gpu", "static", "lru", "score", "dali"):
        r = simulate_policy(trace, cfg, cm, name, dcfg=dcfg, gate_ws=gws,
                            res_vecs=res, batch=8, ctx_len=32)
        print(f"{name:26s} {r.tokens_per_s:8.2f} "
              f"{100*r.cache_hit_rate:5.1f}")

    # the modeled rows above estimate offload cost; the physical rows
    # below MEASURE it — the identical "dali" policy drives a host
    # expert store + device slot pool through one B=1 decode loop per
    # --offload mode (serving/expert_store.py; wall time includes the
    # pool streaming each mode schedules differently)
    from repro.core.policy import make_policy
    from repro.serving.expert_store import strip_expert_params
    from repro.serving.steps import init_serve_state, make_decode_step
    from repro.serving.scheduler import make_store
    # DELIBERATELY on the legacy kwarg surface (make_store +
    # offload=/init_serve_state kwargs): this example and
    # benchmarks/serving_throughput.py are the back-compat proof that
    # the ServeSpec deprecation shims (serving/spec.py) keep old call
    # sites running — expect a one-time DeprecationWarning
    pol = make_policy("dali", dcfg, top_k=cfg.moe.top_k,
                      router_type=cfg.moe.router_type)
    rv = jnp.asarray(np.stack(res))
    warm, steps = 8, 20
    print(f"\n{'--offload':26s} {'wall µs/step':>12s} {'streamed MB':>12s}"
          f" {'miss rows':>10s}")
    for mode in ("modeled", "blocking", "overlap", "pipelined"):
        store = make_store(mode, params, cfg, pol)
        dparams = (params if store is None
                   else strip_expert_params(params, cfg))
        decode = jax.jit(make_decode_step(cfg, policy=pol, offload=store))
        state = init_serve_state(cfg, 1, 64, policy=pol, offload=store)
        target = None
        for t in range(warm + steps):
            if t == warm:
                t0 = time.perf_counter()
            # the store's hooks schedule the streaming around the
            # dispatch (blocking: on the critical path; overlap: commit
            # at the idle boundary, stage behind the in-flight step;
            # pipelined: per-layer inject buffers staged before the
            # dispatch, folded in-graph — DESIGN.md §9)
            if store is not None:
                state["offload"] = store.pre_step(state["offload"], mode,
                                                  target)
            state, _, tel = decode(dparams, state, rv)
            if store is not None:
                store.post_dispatch(mode, target)
            np.asarray(state["tokens"])
            if store is not None:
                target = store.next_target(state, tel)
        us = (time.perf_counter() - t0) / steps * 1e6
        mb = store.stats()["h2d_bytes"] / 1e6 if store is not None else 0.0
        miss = store.stats()["fallback_rows"] if store is not None else 0
        print(f"{mode:26s} {us:12.0f} {mb:12.2f} {miss:10d}")


if __name__ == "__main__":
    main()
