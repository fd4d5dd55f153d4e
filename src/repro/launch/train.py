"""Training launcher.

Two modes:
  * real run (default): trains a smoke/small-scale model on this host's
    devices with the synthetic Markov corpus — used by examples and the
    benchmark suite (residual-vector calibration requires a *trained*
    model; see DESIGN.md §6).
  * --production: builds the pjit train step against the production mesh
    (requires enough devices; on CPU use dryrun.py instead).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
      --smoke --steps 200 --batch 8 --seq 128 --ckpt /tmp/run1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def train_loop(cfg, steps: int, batch: int, seq: int, lr: float = 1e-3,
               seed: int = 0, ckpt_dir: str | None = None,
               log_every: int = 20, corpus=None):
    from repro.checkpoint.store import CheckpointManager
    from repro.data.pipeline import MarkovCorpus, batches
    from repro.models.model import init_model
    from repro.training.optimizer import OptConfig, init_adamw
    from repro.training.train_step import make_train_step

    params = init_model(jax.random.PRNGKey(seed), cfg)
    oc = OptConfig(lr=lr, warmup_steps=min(50, steps // 10 + 1),
                   total_steps=steps)
    opt = init_adamw(params)
    step_fn = jax.jit(make_train_step(cfg, oc), donate_argnums=(0, 1))
    corpus = corpus or MarkovCorpus(vocab=cfg.vocab, seed=seed)
    cm = CheckpointManager(ckpt_dir) if ckpt_dir else None

    history = []
    t0 = time.time()
    for i, b in enumerate(batches(corpus, batch, seq, steps, seed=seed)):
        if cfg.family in ("vlm", "audio"):
            T = 16 if cfg.family == "audio" else min(cfg.n_vision_tokens, 16)
            b = dict(b, cross_src=np.full((batch, T, cfg.d_model), 0.02,
                                          np.float32))
        params, opt, m = step_fn(params, opt,
                                 {k: jnp.asarray(v) for k, v in b.items()})
        history.append(float(m["ce"]))
        if (i + 1) % log_every == 0:
            print(f"step {i+1:5d} ce={history[-1]:.4f} "
                  f"lr={float(m['lr']):.2e} gnorm={float(m['grad_norm']):.2f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    if cm:
        cm.save(steps, {"params": params, "opt": opt})
    return params, opt, history


def main():
    from repro.configs import get_config, make_smoke

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = make_smoke(cfg)
    print(f"training {cfg.name}: {args.steps} steps, "
          f"batch={args.batch} seq={args.seq}")
    _, _, hist = train_loop(cfg, args.steps, args.batch, args.seq,
                            lr=args.lr, seed=args.seed, ckpt_dir=args.ckpt)
    print(f"ce: {hist[0]:.3f} -> {hist[-1]:.3f}")


if __name__ == "__main__":
    main()
