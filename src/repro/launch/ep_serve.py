"""Faulted expert-parallel serve: degraded-link detection + expert
re-route on a (1, n) mesh over every device JAX sees (DESIGN.md §13) —
on a CPU host, force the devices from outside with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Serves a cycle of zipf-routed requests through the jitted EP MoE step
(models/moe_ep.py) three times over the SAME inputs:

  healthy        — no faults, canonical expert layout
  fault_static   — an injected per-link slowdown
                   (``link_degrade[0>3]:x8@6-18``), placement frozen:
                   the no-re-route baseline that keeps paying the bad
                   link every step
  fault_reroute  — same fault, the :class:`EPResilience` controller
                   armed: per-link watchdogs detect the slow pair, the
                   placement re-solves against the refit topology, and
                   the victim devices' hot experts move to
                   well-connected hosts

and then asserts the re-route contract (exit non-zero on any failure):
every request's outputs are bit-identical across all three trials (a
re-route only moves WHERE experts compute), the re-route actually
engaged, and the re-routed trial beats the frozen baseline on ms/step
inside the fault window because the demand bytes crossing the degraded
pair collapsed.

The host CPU mesh has no real interconnect (DESIGN.md §2), so per-pair
transfer time is charged analytically from the modeled fabric constants
below and injected slowdowns pay their *extra* time as a real sleep —
wall-clock ms/step honestly reflects the fault and the saving.

  PYTHONPATH=src python -m repro.launch.ep_serve \
      --faults 'link_degrade[0>3]:x8@6-18' --steps 26
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import LOCAL_PC, LinkTopology, parse_topology
from repro.launch import sharding as shd
from repro.launch.mesh import ep_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.models.moe import init_moe
from repro.models.moe_ep import (apply_moe_ep, ep_applicable,
                                 permute_expert_params)
from repro.serving.ep_resilience import EPResilience
from repro.serving.faults import parse_faults

E, K, D_MODEL, D_EXPERT = 64, 2, 128, 256
DEFAULT_FAULTS = "link_degrade[0>3]:x8@6-18"
# Modeled fabric for the wall-clock charging: slow enough that one
# degraded pair's extra time dominates the toy step's compute jitter
# (~tens of KB/step on the hot pair -> tens of ms at x8).
BENCH_GBPS = 0.002
BENCH_LAT_S = 2e-4
BENCH_PROFILE = dataclasses.replace(LOCAL_PC, name="ep-bench-fabric",
                                    link_gbps=BENCH_GBPS,
                                    link_latency_s=BENCH_LAT_S)


def build_model(dtype: str = "float32", seed: int = 0):
    """The EP bench toy (benchmarks/ep_exchange.py geometry) with a
    deterministic 6*eye router so routing follows the input's argmax."""
    cfg = ModelConfig(d_model=D_MODEL, d_ff=D_EXPERT, vocab=64,
                      dtype=dtype, param_dtype=dtype,
                      moe=MoEConfig(n_routed=E, top_k=K,
                                    d_expert=D_EXPERT,
                                    capacity_factor=0.0))
    params = init_moe(jax.random.PRNGKey(seed), cfg)
    params = dict(params,
                  router=6.0 * jnp.eye(D_MODEL, E, dtype=jnp.float32))
    return cfg, params


def zipf_request(B: int, S: int, dtype, seed: int):
    """One request batch whose top-1 expert follows zipf(1.2) — the
    paper-style skew where moving hot experts off a bad link pays."""
    rng = np.random.default_rng(seed)
    T = B * S
    x = 0.05 * rng.standard_normal((T, D_MODEL))
    p = 1.0 / np.arange(1, E + 1) ** 1.2
    tgt = rng.choice(E, size=T, p=p / p.sum())
    x[np.arange(T), tgt] += 3.0
    return jnp.asarray(x.reshape(B, S, D_MODEL), dtype)


def run_resilience_trials(*, steps: int = 26, faults: str = DEFAULT_FAULTS,
                          topology=None, B: int = 4, S: int = 160,
                          n_requests: int = 4, seed: int = 0,
                          verbose: bool = False) -> Dict:
    """Healthy / fault-static / fault-reroute trials over one request
    cycle; returns the JSON-ready record with per-trial timings, the
    per-pair byte accounting and the verdicts."""
    cfg, params = build_model(seed=seed)
    mesh = ep_mesh(E)
    tp = mesh.shape["model"]
    topo = (topology if isinstance(topology, LinkTopology)
            else parse_topology(topology, tp, BENCH_PROFILE))
    specs = parse_faults(faults)
    link_specs = [s for s in specs if s.kind == "link_degrade"]
    if not link_specs:
        raise SystemExit(f"--faults {faults!r} has no link_degrade spec: "
                         "the resilience trial needs a slow link to "
                         "detect and route around")
    fault_pairs = [p for p in topo.pairs()
                   if any(s.matches_link(p) for s in link_specs)]
    dt = jnp.dtype(cfg.dtype)
    xs = [zipf_request(B, S, dt, seed + 10 + r) for r in range(n_requests)]
    lmap = shd.logical_map_for(cfg, "prefill_32k", mesh)

    with mesh, shd.rules(mesh, lmap, "tp"):
        if not ep_applicable(cfg, B, S):
            raise SystemExit(f"EP path not applicable at B={B}, S={S}")
        step_fn = jax.jit(
            lambda p, x, perm: apply_moe_ep(p, x, cfg, placement=perm,
                                            demand_view=True))
        # warm the compile cache so trial ms/step measures steps, not
        # the first trial's trace+compile
        jax.block_until_ready(step_fn(
            params, xs[0], jnp.arange(E, dtype=jnp.int32))[0])

        def run_trial(name: str, trial_faults: Optional[str],
                      reroute: bool) -> Dict:
            ctrl = EPResilience(topo, n_experts=E, d_model=D_MODEL,
                                itemsize=dt.itemsize, faults=trial_faults,
                                seed=seed, reroute=reroute)
            phys = permute_expert_params(params, ctrl.placement)
            outs, ms, fault_ms, fault_bytes = [], [], [], []
            for t in range(steps):
                x = xs[t % n_requests]
                t0 = time.perf_counter()
                y, info = step_fn(phys, x, jnp.asarray(ctrl.placement))
                jax.block_until_ready(y)
                rep = ctrl.step(np.asarray(info["ep_counts"]))
                dt_ms = (time.perf_counter() - t0) * 1e3
                if rep["placement_changed"]:
                    phys = permute_expert_params(params, ctrl.placement)
                    if verbose:
                        print(f"   [{name}] step {t}: re-route -> "
                              f"placement {ctrl.placement[:8].tolist()}...")
                ms.append(dt_ms)
                if trial_faults is not None and any(
                        s.active(t) for s in link_specs):
                    fault_ms.append(dt_ms)
                    fault_bytes.append(sum(
                        int(rep["pair_bytes"][i, j])
                        for i, j in fault_pairs))
                outs.append(np.asarray(y))
            return {
                "name": name,
                "ms_per_step": float(np.mean(ms)),
                "fault_ms_per_step": (float(np.mean(fault_ms))
                                      if fault_ms else None),
                "fault_pair_bytes_per_step": (float(np.mean(fault_bytes))
                                              if fault_bytes else None),
                "reroutes": ctrl.reroutes,
                "slept_s": ctrl.slept_s,
                "events": [list(e) for e in ctrl.events],
                "links": ctrl.link_report(),
                "_outputs": outs,
            }

        trials = [run_trial("healthy", None, False),
                  run_trial("fault_static", faults, False),
                  run_trial("fault_reroute", faults, True)]

    ref = trials[0]["_outputs"]

    def bit_equal(tr) -> Dict[int, bool]:
        eq = {}
        for t, y in enumerate(tr["_outputs"]):
            rid = t % n_requests
            eq[rid] = eq.get(rid, True) and bool(np.array_equal(y, ref[t]))
        return eq

    eq_static = bit_equal(trials[1])
    eq_reroute = bit_equal(trials[2])
    rr = trials[2]
    st = trials[1]
    verdicts = {
        "static_bit_exact": all(eq_static.values()),
        "reroute_bit_exact": all(eq_reroute.values()),
        "reroute_engaged": rr["reroutes"] >= 1 and any(
            e[3] == "degraded" for e in rr["events"]),
        "reroute_faster": (rr["fault_ms_per_step"] is not None
                           and st["fault_ms_per_step"] is not None
                           and rr["fault_ms_per_step"]
                           < st["fault_ms_per_step"]),
        "degraded_bytes_drop": (
            rr["fault_pair_bytes_per_step"] is not None
            and st["fault_pair_bytes_per_step"] is not None
            and rr["fault_pair_bytes_per_step"]
            < st["fault_pair_bytes_per_step"]),
    }
    for tr in trials:
        tr.pop("_outputs")
    return {
        "steps": steps, "B": B, "S": S, "n_requests": n_requests,
        "faults": str(faults), "fault_pairs": [f"{i}>{j}"
                                               for i, j in fault_pairs],
        "topology": topo.name, "tp": tp, "E": E,
        "bench_gbps": BENCH_GBPS, "bench_latency_s": BENCH_LAT_S,
        "per_request_bit_exact": {
            "fault_static": eq_static, "fault_reroute": eq_reroute},
        "trials": trials,
        "verdicts": verdicts,
        "ok": all(verdicts.values()),
    }


def main():
    ap = argparse.ArgumentParser(
        description="faulted EP serve: degraded-link re-route trial")
    ap.add_argument("--steps", type=int, default=26)
    ap.add_argument("--faults", default=DEFAULT_FAULTS,
                    help="fault schedule (serving/faults.py grammar); "
                         "must include a link_degrade, optionally "
                         "link-selected, e.g. 'link_degrade[0>3]:x8@6-18'")
    ap.add_argument("--topology", default=None,
                    help="fabric spec (core/cost_model.parse_topology): "
                         "'flat', 'island:K', plus 'SRC>DST:xF' "
                         "overrides; default = flat bench fabric")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seqlen", type=int, default=160)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, help="write the record here")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    res = run_resilience_trials(
        steps=args.steps, faults=args.faults, topology=args.topology,
        B=args.batch, S=args.seqlen, n_requests=args.requests,
        seed=args.seed, verbose=True)

    print(f"== EP resilience trial: {res['faults']} on "
          f"{res['topology']} fabric (tp={res['tp']})")
    for tr in res["trials"]:
        fm = tr["fault_ms_per_step"]
        fb = tr["fault_pair_bytes_per_step"]
        print(f"   {tr['name']:>14}: {tr['ms_per_step']:7.2f} ms/step"
              + (f" | fault window {fm:7.2f} ms/step" if fm else "")
              + (f" | degraded-pair {fb / 1e3:8.1f} KB/step" if fb else "")
              + (f" | reroutes={tr['reroutes']}" if tr['reroutes'] else ""))
    rr = res["trials"][2]
    bad_links = [(n, l) for n, l in rr["links"].items()
                 if l["degrade_events"] or l["refit_rejections"]]
    for name, l in bad_links:
        print(f"   link {name}: state={l['state']} "
              f"misses={l['deadline_misses']} refits={l['refits']} "
              f"refit_rej={l['refit_rejections']} "
              f"degr={l['degrade_events']}")
    print("   verdicts: " + " ".join(
        f"{k}={'PASS' if v else 'FAIL'}"
        for k, v in res["verdicts"].items()))

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")
    if not res["ok"]:
        raise SystemExit(1)
    print(f"   re-route contract verified: outputs bit-identical across "
          f"all trials, re-route engaged and beat the frozen baseline")


if __name__ == "__main__":
    main()
