"""Smoke run of the serving path on a TPU, at Mixtral-8x7B's published
widths with seeded random weights.  It exits non-zero, and prints no
result, when JAX finds no TPU: there is no CPU fallback.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # expert parallelism, 4 chips

One chip (the default) serves the same 4 requests twice through
``ServeSpec`` -> continuous server -> DALI policy:

  A  ``offload="modeled"``: every expert resident on the chip — the
     reference run;
  B  ``offload="pipelined"``: the device copies of the expert stacks are
     freed, the routed experts live in the host store, the chip holds a
     slot pool plus inject buffers, and misses are demand-fetched.

B must reproduce A: identical tokens and bit-equal logits (both runs
gather the same weight bytes into the same kernels and einsums).

``--four-chips`` runs only the expert-parallel layer: one Mixtral-width
MoE layer through ``apply_moe_ep`` on a (1, 4) mesh — ragged exchange,
grouped Pallas kernel, 2 experts per chip — against single-device
``apply_moe`` on chip 0.

Every line before the last states a fact of the run (sizes, bytes,
compile seconds, counts); the last line is one JSON object naming the
device.  Depth is the only cut: 32 -> 4 layers (one period of the layer
pattern is one layer).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "mixtral-8x7b"
N_LAYERS = 4
BATCH, PROMPT, NEW, N_REQUESTS = 2, 128, 16, 4
CACHE_RATIO = 0.25
GB = 1e9


def say(*parts):
    print(*parts, flush=True)


def fail(msg: str):
    say(f"FAIL: {msg}")
    raise SystemExit(1)


class CompileClock:
    """Sums XLA backend-compile seconds (JAX's own monitoring event) so
    each phase can report its compile time apart from its run time."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        self.cumulative = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration
            self.cumulative += duration

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


def device_info():
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device: {json.dumps(info)}")
    if info["platform"] != "tpu":
        say("no TPU found: this smoke run needs the chip and does not "
            "fall back to the CPU")
        raise SystemExit(2)
    return info


def mem(dev) -> dict:
    """Device bytes (JAX's memory_stats) and this process's host
    resident set, now and at its peak (/proc/self/status)."""
    st = dev.memory_stats() or {}
    out = {k: st.get(k, 0) for k in ("bytes_in_use", "peak_bytes_in_use",
                                     "bytes_limit")}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(("VmRSS:", "VmHWM:")):
                    key = "host_rss" if line.startswith("VmRSS") else \
                        "host_rss_peak"
                    out[key] = int(line.split()[1]) * 1024
    except OSError:
        pass
    return out


def watch_memory(dev, clock, every_s: float = 20.0):
    """Print device and host memory, and the compile seconds so far,
    every ``every_s`` seconds from a daemon thread, so a run the machine
    kills for memory still shows where it stood."""
    t0 = time.perf_counter()

    def loop():
        while True:
            time.sleep(every_s)
            say(f"[memory at {time.perf_counter() - t0:.0f}s, compiled "
                f"{clock.cumulative:.0f}s] {mem(dev)}")

    threading.Thread(target=loop, daemon=True).start()


def smoke_config(cfg=None):
    from repro.configs import get_config
    full = get_config(ARCH)
    cfg = cfg or full.replace(n_layers=N_LAYERS)
    reduced = {"n_layers": [full.n_layers, cfg.n_layers]}
    return cfg, reduced


def prompts(cfg, seed: int, n: int = N_REQUESTS, length: int = PROMPT):
    import numpy as np
    rng = np.random.default_rng(seed)
    # ids 0 and 1 (pad, EOS) never appear in a prompt
    return [rng.integers(2, cfg.vocab, length).astype(np.int32)
            for _ in range(n)]


def serve(params, cfg, mode: str, reqs, dali_cfg):
    """Serve ``reqs`` (prompt arrays) through the normal construction
    path.  Returns (finished requests by rid, resolved serve, whether the
    compiled admission prefill calls the Pallas kernel)."""
    import jax
    from repro.serving.scheduler import Request
    from repro.serving.spec import OffloadSpec, ServeSpec
    spec = ServeSpec(cfg=cfg, server="continuous", policy="dali",
                     dali_cfg=dali_cfg, batch_size=BATCH,
                     max_len=PROMPT + NEW,
                     offload=OffloadSpec(mode=mode))
    t0 = time.perf_counter()
    rs = spec.resolve(params)
    t1 = time.perf_counter()
    kernel = prefill_has_kernel(rs, cfg)
    say(f"  {mode}: resolved in {t1 - t0:.1f}s, prefill compiled for the "
        f"kernel check in {time.perf_counter() - t1:.1f}s | "
        f"{mem(jax.devices()[0])}")
    srv = rs.server()
    for i, p in enumerate(reqs):
        srv.submit(Request(rid=i, prompt=p, max_new_tokens=NEW, logits=[]))
    t0 = time.perf_counter()
    done = srv.run()
    m = srv.metrics
    say(f"  {mode}: served in {time.perf_counter() - t0:.1f}s (prefill "
        f"{m.prefill_s:.1f}s, decode {m.decode_s:.1f}s over {m.steps} "
        f"steps)")
    return {r.rid: r for r in done}, rs, kernel


def prefill_has_kernel(rs, cfg) -> bool:
    """Whether the admission prefill the server jits, compiled at the
    bucket the smoke prompts use, calls a Pallas kernel.  Lowered from
    shapes only, so nothing is allocated."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import init_caches

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    args = [shapes(rs.params),
            jax.ShapeDtypeStruct((1, PROMPT), jnp.int32),
            shapes(jax.eval_shape(lambda: init_caches(cfg, 1,
                                                      PROMPT + NEW))),
            jax.ShapeDtypeStruct((), jnp.int32)]
    if rs.store is not None:
        args.append(rs.store.state_shapes())
    fn = jax.jit(rs.admit_prefill())
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def compare(ref, got) -> None:
    """Tokens must be identical and logits bit-equal: phase B gathers the
    same bf16 weight bytes (pool, inject and fetched rows) into the same
    kernel sweep and einsum bodies as phase A's resident stacks, so no
    tolerance is granted.  Row 0 of each request's logits comes from its
    admission prefill, the rest from decode steps."""
    import numpy as np
    if sorted(ref) != sorted(got):
        fail(f"served request ids differ: {sorted(ref)} vs {sorted(got)}")
    d_pre = d_dec = 0.0
    tokens_equal = True
    for rid in sorted(ref):
        a, b = ref[rid], got[rid]
        la, lb = np.stack(a.logits), np.stack(b.logits)
        if la.shape != (len(a.output), la.shape[1]):
            fail(f"request {rid}: {la.shape[0]} logits rows for "
                 f"{len(a.output)} tokens")
        if not (np.isfinite(la).all() and np.isfinite(lb).all()):
            fail(f"request {rid}: non-finite logits")
        n = min(len(la), len(lb))
        diff = np.abs(la[:n] - lb[:n])
        d_pre = max(d_pre, float(diff[0].max()))
        if n > 1:
            d_dec = max(d_dec, float(diff[1:].max()))
        tokens_equal &= a.output == b.output
    say(f"compare A vs B: tokens identical={tokens_equal} | max |delta "
        f"logit| prefill={d_pre!r} decode={d_dec!r} (bit-equal expected)")
    if not tokens_equal:
        for rid in sorted(ref):
            a, b = ref[rid], got[rid]
            la, lb = np.stack(a.logits), np.stack(b.logits)
            i = next((j for j, (x, y) in enumerate(zip(a.output, b.output))
                      if x != y), len(a.output))
            # logits up to and including the first differing token were
            # computed from the same inputs in both runs
            same = np.abs(la[:i + 1] - lb[:i + 1]).max(axis=1)
            top2 = (np.sort(la[i])[-2:] if i < len(la) else [0.0, 0.0])
            say(f"  request {rid}: first differing token {i} | per-token "
                f"max |delta logit| up to it {same.tolist()} | A's top-2 "
                f"gap there {float(top2[1] - top2[0])!r}\n"
                f"    A {a.output}\n    B {b.output}")
        fail("tokens differ between A and B")
    if d_pre or d_dec:
        fail("logits differ between A and B")


def one_chip(seed: int, cfg=None) -> None:
    import jax
    import numpy as np
    from repro.models.model import init_model
    from repro.serving.expert_store import host_expert_params
    from repro.serving.steps import default_dali_config

    dev = jax.devices()[0]
    clock = CompileClock()
    watch_memory(dev, clock)
    cfg, reduced = smoke_config(cfg)
    m, a = cfg.moe, cfg.attn
    say(f"config: {cfg.name} d_model={cfg.d_model} heads={a.n_heads}/"
        f"{a.n_kv_heads} head_dim={a.head_dim} d_expert={m.d_expert} "
        f"experts={m.n_routed} top_k={m.top_k} vocab={cfg.vocab} "
        f"dtype={cfg.param_dtype}")
    say(f"reduced: {json.dumps(reduced)}")
    dali_cfg = default_dali_config(cfg, cache_ratio=CACHE_RATIO)
    reqs = prompts(cfg, seed)

    t0 = time.perf_counter()
    params = jax.jit(init_model, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    say(f"init: {time.perf_counter() - t0:.1f}s "
        f"(compile {clock.lap():.1f}s) | device {mem(dev)}")

    # -- phase A: every expert resident -----------------------------------
    t0 = time.perf_counter()
    ref, rs_a, kernel_a = serve(params, cfg, "modeled", reqs, dali_cfg)
    wall_a = time.perf_counter() - t0
    say(f"phase A (modeled, all experts resident): served {len(ref)} "
        f"requests, {sum(len(r.output) for r in ref.values())} tokens | "
        f"wall {wall_a:.1f}s, compile {clock.lap():.1f}s | prefill "
        f"tpu_custom_call={kernel_a} | device {mem(dev)}")
    del rs_a
    gc.collect()

    # -- release every device copy of the expert stacks -------------------
    params = host_expert_params(params, cfg)
    gc.collect()
    say(f"expert stacks moved to host, device copies freed | device "
        f"{mem(dev)}")

    # -- phase B: host store + slot pool ----------------------------------
    t0 = time.perf_counter()
    got, rs_b, kernel_b = serve(params, cfg, "pipelined", reqs, dali_cfg)
    wall_b = time.perf_counter() - t0
    store = rs_b.store
    stripped = all("gate" not in b["mlp"] for b in rs_b.params["scan"])
    if not stripped:
        fail("phase B served params still hold expert stacks")
    lay = store.memory_layout()
    host_bytes = sum(int(np.asarray(v).nbytes) for v in store.host.values())
    st = store.stats()
    say(f"phase B (pipelined, experts in host store): served {len(got)} "
        f"requests, {sum(len(r.output) for r in got.values())} tokens | "
        f"wall {wall_b:.1f}s, compile {clock.lap():.1f}s | prefill "
        f"tpu_custom_call={kernel_b} | stripped params={stripped}")
    say(f"phase B store: host {host_bytes / GB:.2f} GB, pool "
        f"{store.n_slots} slots/layer {lay['pool_bytes'] / GB:.2f} GB, "
        f"inject {store.max_moves} rows | decode H2D rows="
        f"{st['h2d_rows']} bytes={st['h2d_bytes']} | prefill H2D rows="
        f"{st['prefill_fetch_rows']} bytes={st['prefill_h2d_bytes']} "
        f"waves={st['prefill_waves']} | fallback rows="
        f"{st['fallback_rows']} fetches={st['fallback_fetches']} | host "
        f"seconds stage={st['stage_s']:.1f} commit={st['commit_s']:.1f} "
        f"prefill gathers={st['prefill_stage_s']:.1f} | device {mem(dev)}")
    if len(got) != N_REQUESTS:
        fail(f"phase B served {len(got)} of {N_REQUESTS} requests")
    if st["h2d_rows"] + st["prefill_fetch_rows"] + st["fallback_rows"] == 0:
        fail("phase B moved no expert rows host->device")

    compare(ref, got)
    if not (kernel_a and kernel_b):
        fail("the compiled prefill does not call the Pallas kernel")


def zipf_tokens(cfg, router, B: int, S: int, seed: int):
    """(B, S, d) activations whose top-1 expert follows Zipf(1.2): each
    token leans on its target expert's router column."""
    import numpy as np
    rng = np.random.default_rng(seed)
    E, T = cfg.moe.n_routed, B * S
    p = 1.0 / np.arange(1, E + 1) ** 1.2
    tgt = rng.choice(E, size=T, p=p / p.sum())
    r = np.asarray(router, np.float32)
    cols = r[:, tgt].T / np.linalg.norm(r[:, tgt].T, axis=1, keepdims=True)
    x = 0.5 * rng.standard_normal((T, cfg.d_model)) + 8.0 * cols
    return x.reshape(B, S, cfg.d_model).astype(np.float32), tgt


def four_chips(seed: int, cfg=None, B: int = 2, S: int = 256) -> None:
    """One Mixtral-width MoE layer, expert-parallel over 4 devices, vs the
    single-device layer on device 0."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import sharding as shd
    from repro.launch.mesh import ep_mesh
    from repro.models.moe import apply_moe, init_moe
    from repro.models.moe_ep import apply_moe_ep, ep_applicable

    clock = CompileClock()
    devs = jax.devices()[:4]
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    cfg, _ = smoke_config(cfg)
    # capacity_factor 0 = no drops, so per-chip and single-device buckets
    # keep the same tokens and the outputs are comparable row for row
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=0.0))
    E = cfg.moe.n_routed
    say(f"config: one {cfg.name} MoE layer d_model={cfg.d_model} "
        f"d_expert={cfg.moe.d_expert} experts={E} top_k={cfg.moe.top_k} "
        f"| tokens {B}x{S} | {E // len(devs)} experts per chip")
    with jax.default_device(devs[0]):
        params = jax.jit(init_moe, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)
    x_np, tgt = zipf_tokens(cfg, params["router"], B, S, seed)
    x = jax.device_put(jnp.asarray(x_np, jnp.dtype(cfg.dtype)), devs[0])
    say(f"zipf routing: tokens per target expert "
        f"{np.bincount(tgt, minlength=E).tolist()}")

    t0 = time.perf_counter()
    y_ref, i_ref = jax.jit(lambda p, x: apply_moe(p, x, cfg))(params, x)
    y_ref = np.asarray(y_ref.astype(jnp.float32))
    say(f"single device: {time.perf_counter() - t0:.1f}s "
        f"(compile {clock.lap():.1f}s) on {devs[0]}")

    mesh = ep_mesh(E, devs)
    lmap = shd.logical_map_for(cfg, "prefill_32k", mesh)
    rep = NamedSharding(mesh, P())
    ep_params = {
        "router": jax.device_put(params["router"], rep),
        "gate": jax.device_put(params["gate"],
                               NamedSharding(mesh, P("model", None, None))),
        "up": jax.device_put(params["up"],
                             NamedSharding(mesh, P("model", None, None))),
        "down": jax.device_put(params["down"],
                               NamedSharding(mesh, P("model", None, None))),
    }
    del params
    gc.collect()
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "model", None)))
    per_dev = {d.id: 0 for d in devs}
    for k in ("gate", "up", "down"):
        for sh in ep_params[k].addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    say(f"expert bytes per device: {json.dumps(per_dev)}")
    if len(set(per_dev.values())) != 1 or 0 in per_dev.values():
        fail(f"experts not spread evenly over the chips: {per_dev}")

    t0 = time.perf_counter()
    with mesh, shd.rules(mesh, lmap, "tp"):
        if not ep_applicable(cfg, B, S):
            fail(f"the EP path does not apply at B={B}, S={S}")
        fn = jax.jit(lambda p, x: apply_moe_ep(p, x, cfg,
                                               force_exchange="ragged"))
        compiled = fn.lower(ep_params, xs).compile()
        text = compiled.as_text()
        y_ep, i_ep = compiled(ep_params, xs)
        y_ep = np.asarray(y_ep.astype(jnp.float32))
    say(f"expert parallel: {time.perf_counter() - t0:.1f}s "
        f"(compile {clock.lap():.1f}s) | all-to-all="
        f"{'all-to-all' in text} tpu_custom_call="
        f"{'tpu_custom_call' in text} | shipped capacity "
        f"{int(i_ep['ep_cx'])} rows per bucket")
    if "tpu_custom_call" not in text:
        fail("the EP layer does not call the grouped Pallas kernel")
    if not np.array_equal(np.asarray(i_ref["workload"]),
                          np.asarray(i_ep["workload"])):
        fail("per-expert workloads differ between EP and single device")
    if not np.isfinite(y_ep).all():
        fail("non-finite EP output")
    err = float(np.max(np.abs(y_ep - y_ref)))
    # the two paths pad their capacity buckets differently (per chip vs
    # one global bucket) and add the top-k expert outputs back in a
    # different program, so bit-equality is not required.  2**-8 of the
    # largest |y_ref| lies between half and one bf16 ulp at that
    # magnitude: a rounding difference of the bf16 output, not a wrong row
    tol = 2.0 ** -8 * float(np.max(np.abs(y_ref)))
    say(f"compare EP vs single device: max |delta| = {err!r} "
        f"(tolerance {tol!r}) | workloads equal")
    if err > tol:
        fail(f"EP output differs from single device by {err!r} > {tol!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel phase on 4 chips")
    args = ap.parse_args(argv)
    import jax
    info = device_info()
    from repro.launch.compile_cache import enable_compilation_cache
    say(f"compilation cache: {enable_compilation_cache()}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    info["count"] = len(jax.devices())
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
