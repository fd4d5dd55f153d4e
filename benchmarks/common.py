"""Shared benchmark substrate.

Benchmarks measure *real* routing behaviour: each evaluation model is a
reduced same-family variant of one of the paper's models, briefly trained
on the synthetic Markov corpus (so the residual stream and router develop
the structure DALI exploits — random-init models route near-uniformly and
show none of the paper's dynamics).  Trained params and traces are cached
under reports/bench_cache/.

Modeled step times come from the cost model of the device the run is on
(``cost_model.DEVICE_PROFILES``: the paper's RTX 3090 platform on the
CPU backend) — estimates, not measurements; prefetch accuracy / cache
hit rate / cosine similarity are measured quantities (see DESIGN.md §2).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import restore, save
from repro.configs import get_config, make_smoke
from repro.core.cost_model import CostModel
from repro.core.prefetch import (FeaturePrefetcher, RandomPrefetcher,
                                 ResidualPrefetcher, StatisticalPrefetcher)
from repro.core.residual import calibrate_residuals
from repro.core.tracing import capture_decode_trace, capture_prefill_trace, \
    gate_weights
from repro.data.pipeline import MarkovCorpus
from repro.launch.train import train_loop
from repro.models.model import init_model

CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "reports", "bench_cache"))

# --------------------------------------------------------------------------
# Suite registry (single source for benchmarks/run.py — new benchmarks
# register here instead of editing the runner's import list)
# --------------------------------------------------------------------------

# name -> (module under benchmarks/, entry attr).  "run" entries are the
# legacy Csv-collector suites; "main" entries are CLI benchmarks
# (argparse, --smoke preset, machine-readable reports/bench/*.json) whose
# ``main(argv)`` the runner calls with ``["--smoke"]``.  Both run in the
# runner's own process: a parent that has touched JAX holds the chip, so
# a child could not reach it.  ep_exchange needs several devices — on a
# CPU host, start the runner with
# XLA_FLAGS=--xla_force_host_platform_device_count=8.
SUITE_SPECS = {
    "speed": ("speed_vs_frameworks", "run"),        # Figs 12, 13
    "prefetch_acc": ("prefetch_accuracy", "run"),   # Table 2, Fig 16b
    "cache": ("cache_hitrate", "run"),              # Figs 7, 17b, 18d
    "assignment": ("assignment_quality", "run"),    # Figs 14, 15, 20; Tab 4
    "prefetch_speed": ("prefetch_speed", "run"),    # Fig 16a
    "sensitivity": ("sensitivity", "run"),          # Fig 18a-c, Table 9
    "breakdown": ("breakdown", "run"),              # Figs 19, 5
    "cosine": ("cosine_similarity", "run"),         # Table 8, App A.5
    "roofline": ("roofline", "run"),                # deliverable (g)
    "moe_dispatch": ("moe_dispatch", "main"),       # DESIGN.md §4
    "ep_exchange": ("ep_exchange", "main"),         # DESIGN.md §6
    "serving": ("serving_throughput", "main"),      # DESIGN.md §3
    "policy_ablation": ("policy_ablation", "main"),  # DESIGN.md §7
    "offload_stream": ("offload_stream", "main"),   # DESIGN.md §8
}


def load_suite(name: str):
    """Resolve a registered suite to a ``fn(csv)`` callable, run in this
    process."""
    import importlib
    mod_name, attr = SUITE_SPECS[name]
    entry = getattr(importlib.import_module(f"benchmarks.{mod_name}"), attr)
    if attr == "main":
        return lambda csv: entry(["--smoke"])
    return entry

# the paper's evaluation models (Table 3), reduced same-family
BENCH_MODELS = ["mixtral-8x7b", "deepseek-v2-lite-16b", "qwen3-30b-a3b"]
SHORT = {"mixtral-8x7b": "Mixtral", "deepseek-v2-lite-16b": "DeepSeek",
         "qwen3-30b-a3b": "Qwen"}


def bench_cfg(arch: str):
    cfg = make_smoke(get_config(arch))
    return cfg.replace(n_layers=max(cfg.n_layers, 4) if cfg.moe is None
                       else (4 + (cfg.moe.first_dense or 0)))


@dataclass
class BenchModel:
    arch: str
    cfg: object
    params: object
    corpus: MarkovCorpus
    res_vecs: List[np.ndarray]
    gate_ws: List[np.ndarray]
    cost: CostModel

    def prefetchers(self, seed: int = 0) -> Dict[str, object]:
        m = self.cfg.moe
        L = len(self.gate_ws)
        return {
            "residual": ResidualPrefetcher(self.gate_ws, self.res_vecs, m),
            "feature": FeaturePrefetcher(self.gate_ws, m),
            "statistical": StatisticalPrefetcher(L, m.n_routed),
            "random": RandomPrefetcher(m.n_routed, seed),
        }

    def prompts(self, batch: int, length: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(np.stack(
            [self.corpus.sample(rng, length) for _ in range(batch)]))

    def decode_trace(self, batch: int, n_decode: int, prompt_len: int = 32,
                     seed: int = 0):
        return capture_decode_trace(
            self.params, self.cfg, self.prompts(batch, prompt_len, seed),
            n_decode=n_decode, greedy=False, seed=seed)

    def prefill_trace(self, batch: int, seq: int, seed: int = 0):
        return capture_prefill_trace(self.params, self.cfg,
                                     self.prompts(batch, seq, seed))


_MODELS: Dict[str, BenchModel] = {}


def load_model(arch: str, train_steps: int = 150, seed: int = 0,
               cfg_transform=None, tag: str = "") -> BenchModel:
    """``cfg_transform``/``tag`` build a named variant of the bench model
    (e.g. policy_ablation widens the expert count so cache policies are
    compared in the paper's E >> cache_size regime) — trained and cached
    separately under ``{arch}{tag}.ckpt``."""
    key = arch + tag
    if key in _MODELS:
        return _MODELS[key]
    cfg = bench_cfg(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    corpus = MarkovCorpus(vocab=cfg.vocab, seed=seed)
    os.makedirs(CACHE_DIR, exist_ok=True)
    ck = os.path.join(CACHE_DIR, f"{key}.ckpt")
    template = init_model(jax.random.PRNGKey(seed), cfg)
    if os.path.exists(ck):
        params = jax.tree.map(jnp.asarray, restore(ck, template))
    else:
        t0 = time.time()
        params, _, hist = train_loop(cfg, train_steps, 8, 64, corpus=corpus,
                                     seed=seed, log_every=50)
        print(f"[common] trained {arch} ce {hist[0]:.2f}->{hist[-1]:.2f} "
              f"in {time.time()-t0:.0f}s")
        save(ck, params)
    # calibration trace (Wikitext stand-in: held-out Markov samples)
    calib = capture_decode_trace(params, cfg,
                                 jnp.asarray(np.stack(
                                     [corpus.sample(
                                         np.random.default_rng(seed + 100 + i),
                                         32) for i in range(8)])),
                                 n_decode=24, greedy=False, seed=seed + 1)
    res_vecs = calibrate_residuals([calib])
    bm = BenchModel(arch=arch, cfg=cfg, params=params, corpus=corpus,
                    res_vecs=res_vecs, gate_ws=gate_weights(params, cfg),
                    cost=CostModel.for_config(get_config(arch)))
    _MODELS[key] = bm
    return bm


def time_fn(fn, *args, reps: int = 30, warmup: int = 3) -> float:
    """Median wall µs/call, jit-warmed, device-synchronised (the shared
    timer for the µs/step benchmarks — moe_dispatch, ep_exchange)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


class Csv:
    """Collector for the ``name,us_per_call,derived`` contract."""

    def __init__(self):
        self.rows: List[tuple] = []

    def add(self, name: str, us_per_call: float, derived: str):
        self.rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.2f},{derived}")

    def extend(self, other: "Csv"):
        self.rows.extend(other.rows)
