# Multi-pod dry-run: lower + compile every (architecture x input-shape x
# mesh) combination with ShapeDtypeStruct inputs (no allocation), print
# memory/cost analysis, and derive the three roofline terms:
#
#   compute    = FLOPs        / (chips x 197 TFLOP/s)
#   memory     = HBM bytes    / (chips x 819 GB/s)
#   collective = link bytes   / (chips x 50 GB/s)
#
# FLOPs / HBM bytes come from the closed-form model in launch/costs.py
# (validated against compiled.cost_analysis() on this module's 1-super-block
# calibration variant — XLA's analysis counts scan bodies once, so the raw
# number is recorded but NOT used for scanned stacks; see EXPERIMENTS.md).
# Collective bytes come from the post-SPMD HLO with scan trip-count
# expansion (launch/hloparse.py).
#
# The production meshes need 256 (512 multi-pod) devices; on a CPU host
# they come from the caller's environment:
#   export JAX_PLATFORMS=cpu \
#          XLA_FLAGS=--xla_force_host_platform_device_count=512
#   python -m repro.launch.dryrun --arch mixtral-8x7b --shape decode_32k
#   python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
#   python -m repro.launch.dryrun --all [--force]    # subprocess per combo
# The --all parent imports no JAX (the shape table lives in the JAX-free
# launch/costs.py), so each child is the only process holding it.
# Results accumulate in reports/dryrun/<arch>__<shape>__<mesh>.json.

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

PEAK_FLOPS = 197e12        # bf16/chip, TPU v5e
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link

REPORT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun"))


def model_flops(cfg, spec) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference) with N = active
    params (MoE: top_k experts + shared, not all routed)."""
    from repro.launch.sharding import estimate_params
    from repro.models.config import layer_pattern
    n = estimate_params(cfg)
    if cfg.moe is not None:
        m = cfg.moe
        de = m.d_expert or cfg.d_ff
        per_layer_all = m.n_routed * 3 * cfg.d_model * de
        per_layer_act = m.top_k * 3 * cfg.d_model * de
        n_moe = sum(1 for _, mlp in layer_pattern(cfg) if mlp == "moe")
        n = n - n_moe * (per_layer_all - per_layer_act)
    tokens = spec.batch * (spec.seq if spec.kind != "decode" else 1)
    mult = 6.0 if spec.kind == "train" else 2.0
    return mult * n * tokens


def run_one(arch: str, shape: str, multi_pod: bool) -> dict:
    import jax
    from repro.launch import sharding as shd
    from repro.launch.costs import SHAPES, skip_reason, step_cost
    from repro.launch.hloparse import collective_traffic
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import build

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "status": "ok", "time_s": 0.0}
    reason = skip_reason(arch, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(mesh.devices.size)
    spec = SHAPES[shape]
    cfg, fn, args, donate, wmode = build(arch, shape, mesh)
    rec["weight_mode"] = wmode

    lmap = shd.logical_map_for(cfg, shape, mesh)
    with mesh:
        with shd.rules(mesh, lmap, wmode):
            jitted = jax.jit(fn, donate_argnums=donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    rec["lower_compile_s"] = round(time.time() - t0, 1)

    mem = compiled.memory_analysis()           # per-device quantities
    rec["memory"] = {
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        "peak_per_device_gb": (mem.argument_size_in_bytes
                               + mem.temp_size_in_bytes
                               + mem.output_size_in_bytes
                               - mem.alias_size_in_bytes) / 1e9,
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis_raw"] = {"flops": float(ca.get("flops", 0.0)),
                                "bytes": float(ca.get("bytes accessed", 0.0)),
                                "note": "scan bodies counted once by XLA"}

    hlo = compiled.as_text()
    coll = collective_traffic(hlo)
    rec["collectives"] = {k: v for k, v in coll.items()}
    rec["hlo_chars"] = len(hlo)

    sc = step_cost(cfg, spec.kind, spec.seq, spec.batch)
    mf = model_flops(cfg, spec)
    coll_per_dev = coll.get("total", 0.0)
    rec["roofline"] = {
        "n_chips": n_chips,
        "flops_global": sc.flops,
        "hbm_bytes_global": sc.hbm_bytes,
        "collective_bytes_global": coll_per_dev * n_chips,
        "compute_s": sc.flops / (n_chips * PEAK_FLOPS),
        "memory_s": sc.hbm_bytes / (n_chips * HBM_BW),
        "collective_s": coll_per_dev / LINK_BW,
        "model_flops": mf,
        "useful_flops_ratio": mf / sc.flops if sc.flops else 0.0,
        "kv_bytes": sc.kv_bytes,
        "param_bytes": sc.param_bytes,
    }
    terms = rec["roofline"]
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    rec["roofline"]["dominant"] = dom
    rec["time_s"] = round(time.time() - t0, 1)
    return rec


def report_path(arch, shape, multi_pod):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(REPORT_DIR, exist_ok=True)
    return os.path.join(REPORT_DIR, f"{arch}__{shape}__{mesh_name}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    if args.all:
        from repro.configs import ARCHS
        from repro.launch.costs import SHAPES
        combos = [(a, s, mp) for a in ARCHS for s in SHAPES
                  for mp in (False, True)]
        failures = []
        for arch, shape, mp in combos:
            path = report_path(arch, shape, mp)
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    st = json.load(f).get("status")
                if st in ("ok", "skipped"):
                    print(f"cached   {arch} {shape} "
                          f"{'multi' if mp else 'single'} [{st}]")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if mp:
                cmd.append("--multi-pod")
            print(f"running  {arch} {shape} {'multi' if mp else 'single'} ...",
                  flush=True)
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout)
                print(r.stdout.strip()[-500:])
                if r.returncode != 0:
                    failures.append((arch, shape, mp))
                    print(r.stderr[-3000:])
            except subprocess.TimeoutExpired:
                failures.append((arch, shape, mp))
                print("TIMEOUT")
        print(f"done; failures={len(failures)} {failures}")
        sys.exit(1 if failures else 0)

    try:
        rec = run_one(args.arch, args.shape, args.multi_pod)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
               "status": "error", "error": traceback.format_exc()}
    path = report_path(args.arch, args.shape, args.multi_pod)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(f"{args.arch} {args.shape} {rec['mesh']}: OK "
              f"({rec['lower_compile_s']}s compile, wmode={rec['weight_mode']})")
        print(f"  per-device: args={rec['memory']['argument_gb']:.2f}GB "
              f"temp={rec['memory']['temp_gb']:.2f}GB "
              f"peak~{rec['memory']['peak_per_device_gb']:.2f}GB")
        print(f"  roofline: compute={r['compute_s']*1e3:.3f}ms "
              f"memory={r['memory_s']*1e3:.3f}ms "
              f"collective={r['collective_s']*1e3:.3f}ms "
              f"dominant={r['dominant']} useful={r['useful_flops_ratio']:.2f}")
    elif rec["status"] == "skipped":
        print(f"{args.arch} {args.shape}: SKIPPED — {rec['reason']}")
    else:
        print(rec["error"])
        sys.exit(1)


if __name__ == "__main__":
    main()
