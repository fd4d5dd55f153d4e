"""Serving launcher: continuous-batching (or wave compat) server with a
pluggable offloading policy.

Real run at smoke scale (CPU): trains briefly (or loads a checkpoint),
calibrates the residual vectors on Wikitext-stand-in synthetic data, then
serves a batch of requests with the selected in-graph policy and reports
scheduling telemetry, per-request latency and TTFT.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --requests 16 --max-new 32 --server continuous --policy dali \
      --offload overlap

``--policy`` picks any registered OffloadPolicy (core/policy.py):
dali | static | all_gpu | lru | score | statistical | random | none —
the paper's method and its ablation baselines run through the same
serving stack.  ``--offload`` picks how the policy's decisions reach the
hardware: "modeled" (telemetry only, every expert on device), "blocking"
or "overlap" (physical host store + device slot pool, copies on or off
the decode critical path — DESIGN.md §8), or "pipelined" (per-layer
inject streaming: copies off the critical path and decisions fresh at
t+1 — DESIGN.md §9).  ``--server wave`` selects the
historical wave scheduler (equal-padded waves, lockstep decode) — the
compat baseline the serving benchmark compares against; see DESIGN.md
§3/§7.

``--faults SPEC`` injects link/store faults into the physical offload
path (serving/faults.py; e.g. ``link_degrade:x12@8-26`` or the bare
preset name ``transient_stall``) and arms the watchdog + degradation
ladder (DESIGN.md §10).  ``--check-exact`` re-serves the same workload
against a reference configuration and exits non-zero unless every
request's token sequence matches: with ``--faults`` the reference is the
same run without faults (the recovery-is-exact contract); with a
faultless physical ``--offload`` the reference is the full-resident
"modeled" run (the prefill+decode slot-streaming bit-parity contract of
DESIGN.md §11 — the physical server runs with stripped expert params).

All flags construct one :class:`repro.serving.spec.ServeSpec` (1:1 flag
→ field mapping) resolved through ``ServeSpec.resolve(params)`` — the
launcher is the reference user of the canonical construction API.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np


def main():
    from repro.configs import get_config, make_smoke
    from repro.core.residual import calibrate_residuals
    from repro.core.tracing import capture_decode_trace
    from repro.data.pipeline import MarkovCorpus
    from repro.launch.train import train_loop
    from repro.serving.scheduler import SERVER_PRESETS, Request
    from repro.serving.spec import OffloadSpec, ServeSpec
    from repro.serving.steps import default_dali_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--server", default="continuous",
                    choices=sorted(SERVER_PRESETS))
    # no argparse choices=: the policy registry (core/policy.py) is the
    # single validation point — the server lists registered names on error
    ap.add_argument("--policy", default="dali",
                    help="offload policy: dali|static|all_gpu|lru|score|"
                         "statistical|random|none")
    ap.add_argument("--offload", default="modeled",
                    choices=["modeled", "blocking", "overlap",
                             "pipelined"],
                    help="physical expert residency: modeled (decisions "
                         "feed telemetry only), blocking / overlap "
                         "(host store + device slot pool; copies on / "
                         "off the decode critical path), pipelined "
                         "(per-layer inject streaming: copies off the "
                         "critical path AND t+1-fresh decisions)")
    ap.add_argument("--train-steps", type=int, default=120)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--cache-ratio", type=float, default=0.5)
    ap.add_argument("--no-dali", action="store_true")
    ap.add_argument("--faults", default=None,
                    help="fault schedule for the offload path: comma-"
                         "separated kind[SRC>DST][:xFACTOR][@START[-STOP]] "
                         "specs — kind in link_degrade|transient_stall|"
                         "read_error|corrupt_rows (bare kind = preset "
                         "defaults); the optional [SRC>DST] link selector "
                         "(link_degrade only) targets one directed fabric "
                         "pair, '*' wildcards a side, 'host' names the "
                         "host>device link, no selector = every link.  "
                         "e.g. 'link_degrade:x12@8-26', "
                         "'link_degrade[0>3]:x8@6-18,read_error@30'; "
                         "requires a physical --offload")
    ap.add_argument("--topology", default=None,
                    help="per-link fabric spec "
                         "(core/cost_model.parse_topology): 'flat', "
                         "'island:K' (K-device NVLink-style islands), "
                         "plus comma-separated 'SRC>DST:xF' slow-link or "
                         "'SRC>DST:gGBPS[:lLAT]' absolute overrides, "
                         "e.g. 'island:4,0>3:x8'; attaches per-link "
                         "constants to the offload cost model")
    ap.add_argument("--check-exact", action="store_true",
                    help="re-serve the same workload without faults and "
                         "exit non-zero unless outputs are identical")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    cfg = make_smoke(get_config(args.arch)).replace(n_layers=4)
    corpus = MarkovCorpus(vocab=cfg.vocab, seed=args.seed)
    print(f"== training {cfg.name} for {args.train_steps} steps (so routing "
          "has real structure)")
    params, _, hist = train_loop(cfg, args.train_steps, 8, 64,
                                 corpus=corpus, seed=args.seed)
    print(f"   ce {hist[0]:.2f} -> {hist[-1]:.2f}")

    policy = "none" if args.no_dali else args.policy
    dali_cfg = None
    res_vecs = None
    if cfg.moe is not None and policy != "none":
        print("== calibrating residual vectors (paper Eq. 11)")
        rng = np.random.default_rng(args.seed + 1)
        calib_prompt = jnp.asarray(np.stack(
            [corpus.sample(rng, args.prompt_len) for _ in range(8)]))
        tr = capture_decode_trace(params, cfg, calib_prompt, n_decode=16)
        res = calibrate_residuals([tr])
        res_vecs = jnp.asarray(np.stack(res))
        dali_cfg = default_dali_config(cfg, cache_ratio=args.cache_ratio)

    def serve_once(offload, faults):
        # flags → spec fields 1:1; resolve() validates the offload↔policy
        # contract, builds the store and strips expert params for
        # physical modes (spec.py)
        spec = ServeSpec(
            cfg=cfg, server=args.server, policy=policy, dali_cfg=dali_cfg,
            batch_size=args.batch,
            max_len=args.prompt_len + args.max_new + 2,
            offload=OffloadSpec(mode=offload, faults=faults,
                                topology=args.topology))
        server = spec.resolve(params).server(res_vecs=res_vecs)
        rng = np.random.default_rng(args.seed + 2)
        for i in range(args.requests):
            server.submit(Request(rid=i,
                                  prompt=corpus.sample(rng, args.prompt_len),
                                  max_new_tokens=args.max_new))
        return server, server.run()

    server, done = serve_once(args.offload, args.faults)
    lat = [r.latency for r in done]
    ttft = [r.ttft for r in done if r.first_token_at]
    print(f"== served {len(done)} requests via {args.server} "
          f"(policy={policy}, offload={args.offload}"
          + (f", faults={args.faults}" if args.faults else "") + ") | "
          f"{server.metrics.summary()}")
    if server.store is not None:
        st = server.store.stats()
        print(f"   physical offload: streamed {st['h2d_rows']} experts "
              f"({st['h2d_bytes']/1e6:.1f} MB) | miss fallback "
              f"{st['fallback_rows']} (token,k) slots | "
              f"fb_rows/req={server.metrics.fallback_rate():.2f}")
        if args.faults:
            h = server.store.health()
            trans = ", ".join(f"step {s}: {a}->{b}"
                              for s, a, b in h.get("transitions", []))
            print(f"   resilience: state={h['ladder_state']} "
                  f"retries={st.get('retries', 0)} "
                  f"stalls={st.get('stalls', 0)} "
                  f"read_errors={st.get('read_errors', 0)} "
                  f"corrupt_caught={st.get('corrupt_caught', 0)} "
                  f"restaged={st.get('restaged_rows', 0)} "
                  f"little_steps={st.get('little_steps', 0)}"
                  + (f" | transitions: {trans}" if trans else ""))
            for name, lr in sorted(server.metrics.links.items()):
                print(f"   link {name}: misses={lr['deadline_misses']} "
                      f"refits={lr['refits']} "
                      f"refit_rej={lr['refit_rejections']} "
                      f"degrade_events={lr['degrade_events']} "
                      f"gbps={lr['gbps']:.3g}")
    print(f"   latency p50={np.percentile(lat, 50):.2f}s "
          f"p95={np.percentile(lat, 95):.2f}s"
          + (f" | ttft p50={np.percentile(ttft, 50):.2f}s" if ttft else ""))

    if args.check_exact:
        if args.faults:
            ref_offload, ref_name = args.offload, "fault-free"
        elif args.offload != "modeled":
            # faultless physical mode: the reference is the full-resident
            # modeled run — checks the whole slot-streaming path
            # (prefill waves + decode pool, stripped params) bit-exact
            ref_offload, ref_name = "modeled", "full-resident (modeled)"
        else:
            raise SystemExit("--check-exact needs --faults or a physical "
                             "--offload (it compares the run against a "
                             "fault-free / full-resident reference)")
        print(f"== --check-exact: re-serving the same workload against "
              f"the {ref_name} reference")
        _, clean = serve_once(ref_offload, None)
        by_rid = {r.rid: r.output for r in clean}
        bad = [r.rid for r in done if r.output != by_rid.get(r.rid)]
        if bad:
            print(f"   MISMATCH: requests {bad} diverged from the "
                  f"{ref_name} run")
            raise SystemExit(1)
        print(f"   exact-output parity verified: all {len(done)} "
              f"requests bit-identical to the {ref_name} run")


if __name__ == "__main__":
    main()
