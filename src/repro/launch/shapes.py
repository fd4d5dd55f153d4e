"""Assigned input shapes and ShapeDtypeStruct builders for the dry-run.

Four shapes (assignment spec):
  train_4k     seq=4096    global_batch=256   (training:  train_step)
  prefill_32k  seq=32768   global_batch=32    (inference: prefill_step)
  decode_32k   seq=32768   global_batch=128   (inference: decode_step,
                                               ONE token + 32k KV cache)
  long_500k    seq=524288  global_batch=1     (long-context decode_step)

``long_500k`` requires sub-quadratic attention: it runs for SSM (mamba2),
hybrid (jamba) and gemma2 (native sliding-window local layers; global
layers decode with a sequence-sharded KV).  Pure full-attention archs skip
it (DESIGN.md §4).  ``input_specs`` returns sharding-annotated
ShapeDtypeStructs — no device allocation ever happens for full configs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as shd
from repro.launch.costs import SHAPES, ShapeSpec
from repro.models.config import ModelConfig
from repro.models.model import init_caches, init_model
from repro.models.moe import expert_capacity
from repro.serving.steps import (default_dali_config, init_serve_state,
                                 make_decode_step, make_prefill_step)
from repro.training.optimizer import OptConfig, init_adamw
from repro.training.train_step import make_train_step


# --------------------------------------------------------------------------
# SDS helpers
# --------------------------------------------------------------------------

def _with_sharding(sds_tree, pspec_tree, mesh):
    def attach(s, p):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, p))
    return jax.tree.map(attach, sds_tree, pspec_tree)


def _replicated(sds_tree, mesh):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, P(*([None] * len(s.shape))))),
        sds_tree)


def params_sds(cfg: ModelConfig, mesh, mode: str):
    sds = jax.eval_shape(functools.partial(init_model, cfg=cfg),
                         jax.random.PRNGKey(0))
    specs = shd.param_pspecs(cfg, sds, mode=mode, mesh=mesh)
    return _with_sharding(sds, specs, mesh)


def n_cross_for(cfg: ModelConfig, spec: ShapeSpec) -> Optional[int]:
    if cfg.family == "vlm":
        return cfg.n_vision_tokens
    if cfg.family == "audio":
        # encoder frames: decode against an encoder memory of seq length
        return min(spec.seq, 4096) if spec.kind != "train" else None
    return None


def cross_src_sds(cfg: ModelConfig, spec: ShapeSpec, mesh, batch_spec):
    if cfg.family == "vlm":
        T = cfg.n_vision_tokens
    elif cfg.family == "audio":
        T = min(spec.seq, 4096)
    else:
        return None
    return jax.ShapeDtypeStruct(
        (spec.batch, T, cfg.d_model), jnp.dtype(cfg.dtype),
        sharding=NamedSharding(mesh, P(batch_spec, None, None)))


# --------------------------------------------------------------------------
# step + SDS-args builders (one per shape kind)
# --------------------------------------------------------------------------

def build_train(cfg: ModelConfig, spec: ShapeSpec, mesh, wmode: str):
    cfg = cfg.replace(remat=True)
    B, S = spec.batch, spec.seq
    bspec = shd.batch_pspec(mesh, B)
    p_sds = params_sds(cfg, mesh, wmode)
    opt_sds = jax.eval_shape(init_adamw, p_sds)
    opt_specs = {"mu": shd.param_pspecs(cfg, opt_sds["mu"], mode=wmode,
                                        mesh=mesh),
                 "nu": shd.param_pspecs(cfg, opt_sds["nu"], mode=wmode,
                                        mesh=mesh),
                 "step": P()}
    opt_sds = _with_sharding(opt_sds, opt_specs, mesh)
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                       sharding=NamedSharding(mesh, bspec)),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                       sharding=NamedSharding(mesh, bspec)),
    }
    cs = cross_src_sds(cfg, spec, mesh, bspec[0])
    if cs is not None:
        batch["cross_src"] = cs
    oc = OptConfig()
    cap = expert_capacity(cfg.moe, B * S) if cfg.moe else None
    fn = make_train_step(cfg, oc, moe_capacity=cap)
    return cfg, fn, (p_sds, opt_sds, batch), (0, 1)


def build_prefill(cfg: ModelConfig, spec: ShapeSpec, mesh, wmode: str):
    B, S = spec.batch, spec.seq
    bspec = shd.batch_pspec(mesh, B)
    p_sds = params_sds(cfg, mesh, wmode)
    caches_sds = jax.eval_shape(
        functools.partial(init_caches, cfg, B, S,
                          dtype=cfg.dtype, n_cross=n_cross_for(cfg, spec)))
    c_specs = shd.cache_pspecs(cfg, caches_sds, spec.name, mesh)
    caches_sds = _with_sharding(caches_sds, c_specs, mesh)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32,
                                  sharding=NamedSharding(mesh, bspec))
    cs = cross_src_sds(cfg, spec, mesh, bspec[0])
    cap = expert_capacity(cfg.moe, B * S) if cfg.moe else None
    fn = make_prefill_step(cfg, S, moe_capacity=cap)
    args = (p_sds, tokens, caches_sds) + ((cs,) if cs is not None else ())
    return cfg, fn, args, (2,)


def build_decode(cfg: ModelConfig, spec: ShapeSpec, mesh, wmode: str):
    B, S = spec.batch, spec.seq
    p_sds = params_sds(cfg, mesh, wmode)
    dali_cfg = default_dali_config(cfg) if cfg.moe is not None else None
    state_sds = jax.eval_shape(
        functools.partial(init_serve_state, cfg, B, S, dali_cfg=dali_cfg,
                          dtype=cfg.dtype, n_cross=n_cross_for(cfg, spec)))
    # shardings: caches per policy; rest replicated / batch-sharded
    bspec = shd.batch_pspec(mesh, B)
    c_specs = shd.cache_pspecs(cfg, state_sds["caches"], spec.name, mesh)
    state_specs = {
        "tokens": P(bspec[0], None),
        "pos": P(),
        "caches": c_specs,
        "rng": P(None),
    }
    if "dali" in state_sds:
        state_specs["dali"] = jax.tree.map(
            lambda s: P(*([None] * len(s.shape))), state_sds["dali"])
    state_sds = _with_sharding(state_sds, state_specs, mesh)
    cap = expert_capacity(cfg.moe, B) if cfg.moe else None
    fn = make_decode_step(cfg, dali_cfg, moe_capacity=cap)
    args = (p_sds, state_sds)
    if dali_cfg is not None:
        L = dali_cfg.n_moe_layers
        res_sds = jax.ShapeDtypeStruct(
            (L, cfg.d_model), jnp.float32,
            sharding=NamedSharding(mesh, P(None, None)))
        args = args + (res_sds,)
    return cfg, fn, args, (1,)


def build(arch: str, shape: str, mesh, wmode: Optional[str] = None):
    """Returns (cfg, fn, sds_args, donate) for jit(...).lower(*sds_args)."""
    cfg = get_config(arch)
    spec = SHAPES[shape]
    if wmode is None:
        wmode = "fsdp" if shd.weights_need_fsdp(
            cfg, mesh, train=(spec.kind == "train")) else "tp"
    builder = {"train": build_train, "prefill": build_prefill,
               "decode": build_decode}[spec.kind]
    return builder(cfg, spec, mesh, wmode) + (wmode,)
