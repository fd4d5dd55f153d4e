"""Transformer/SSD block assembly.

One block = mixer (attention variant or mamba) + MLP (dense or MoE), with
pre-norms (and gemma2-style post-norms when ``cfg.post_block_norm``).

``apply_block(params, x, cfg, kinds, ...) -> (y, new_cache, moe_info)``
where ``kinds = (mixer_kind, mlp_kind)`` from ``config.layer_pattern``.

Cache pytrees per mixer kind:
  attn*:       {"k","v","pos"}
  mamba:       {"ssm","conv"}
  cross:       {"xk","xv"}              (static cross K/V, built at prefill)
  self_cross:  {"k","v","pos","xk","xv"}
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .attention import (build_cross_kv, cross_attention, gqa_attention,
                        init_attention, mla_attention)
from .config import ModelConfig
from .layers import apply_mlp, apply_norm, init_mlp, init_norm
from .mamba import apply_mamba, init_mamba, init_mamba_cache
from .moe import apply_moe, init_moe


def init_block(key, cfg: ModelConfig, kinds):
    mixer_kind, mlp_kind = kinds
    ks = jax.random.split(key, 6)
    p = {"norm1": init_norm(ks[0], cfg)}
    if mixer_kind == "mamba":
        p["mixer"] = init_mamba(ks[1], cfg)
    elif mixer_kind == "cross":
        p["mixer"] = init_attention(ks[1], cfg, kind="cross")
        p["mlp_gate"] = jnp.zeros((), jnp.dtype(cfg.param_dtype))
    elif mixer_kind == "self_cross":
        p["mixer"] = init_attention(ks[1], cfg, kind="attn")
        p["cross"] = init_attention(ks[2], cfg, kind="cross")
        p["norm_cross"] = init_norm(ks[3], cfg)
    else:
        p["mixer"] = init_attention(ks[1], cfg, kind=mixer_kind)
    if cfg.post_block_norm:
        p["norm1_post"] = init_norm(ks[4], cfg)

    if mlp_kind != "none":
        p["norm2"] = init_norm(ks[4], cfg)
        p["mlp"] = (init_moe(ks[5], cfg) if mlp_kind == "moe"
                    else init_mlp(ks[5], cfg))
        if cfg.post_block_norm:
            p["norm2_post"] = init_norm(ks[3], cfg)
    return p


def apply_block(params, x, cfg: ModelConfig, kinds, *, positions,
                cache=None, cross_src=None, causal: bool = True,
                moe_capacity: Optional[int] = None,
                count_overlap: Optional[bool] = None,
                slots=None, slot_fetch=None, slot_live=None,
                slot_inject=None, slot_little=None,
                slot_phase: str = "decode", expert_layer=None):
    """``expert_layer`` (a scanned resident MoE layer's index): the MLP's
    routed-expert gate/up/down are the whole scan stacks, and apply_moe
    uses that layer's (models/model.py)."""
    mixer_kind, mlp_kind = kinds
    moe_info = None
    new_cache = cache

    h = apply_norm(params["norm1"], x, cfg)
    if mixer_kind == "mamba":
        y, new_cache = apply_mamba(params["mixer"], h, cfg, cache)
    elif mixer_kind == "cross":
        if cache is not None and "xk" in cache and cross_src is None:
            ckv = {"k": cache["xk"], "v": cache["xv"]}
        else:
            ckv = build_cross_kv(params["mixer"], cross_src, cfg)
            if cache is not None:
                new_cache = {"xk": ckv["k"], "xv": ckv["v"]}
        y = cross_attention(params["mixer"], h, cfg, ckv)
    elif mixer_kind == "self_cross":
        self_cache = None
        if cache is not None:
            self_cache = {k: cache[k] for k in ("k", "v", "pos")}
        y, self_cache = gqa_attention(params["mixer"], h, cfg, kind="attn",
                                      positions=positions, cache=self_cache,
                                      causal=causal)
        if cache is not None and cross_src is None:
            ckv = {"k": cache["xk"], "v": cache["xv"]}
        else:
            ckv = build_cross_kv(params["cross"], cross_src, cfg)
        if cache is not None:
            new_cache = dict(self_cache or {}, xk=ckv["k"], xv=ckv["v"])
        x = x + y
        h = apply_norm(params["norm_cross"], x, cfg)
        y = cross_attention(params["cross"], h, cfg, ckv)
    elif cfg.attn is not None and cfg.attn.mla is not None:
        y, new_cache = mla_attention(params["mixer"], h, cfg,
                                     positions=positions, cache=cache)
    else:
        y, new_cache = gqa_attention(params["mixer"], h, cfg, kind=mixer_kind,
                                     positions=positions, cache=cache,
                                     causal=causal)
    if cfg.post_block_norm:
        y = apply_norm(params["norm1_post"], y, cfg)
    x = x + y

    if mlp_kind != "none":
        h = apply_norm(params["norm2"], x, cfg)
        if mlp_kind == "moe":
            # routing dispatches straight off the norm2 output — under
            # EP, apply_moe's count exchange therefore overlaps the
            # attention epilogue above (count_overlap, DESIGN.md §9)
            y, moe_info = apply_moe(params["mlp"], h, cfg,
                                    capacity=moe_capacity,
                                    count_overlap=count_overlap,
                                    slots=slots, slot_fetch=slot_fetch,
                                    slot_live=slot_live,
                                    slot_inject=slot_inject,
                                    slot_little=slot_little,
                                    slot_phase=slot_phase,
                                    layer=expert_layer)
        else:
            y = apply_mlp(params["mlp"], h, cfg)
            if mixer_kind == "cross":   # gated FFN on VLM cross layers
                y = jnp.tanh(params["mlp_gate"].astype(jnp.float32)) \
                    .astype(y.dtype) * y
        if cfg.post_block_norm:
            y = apply_norm(params["norm2_post"], y, cfg)
        x = x + y
    return x, new_cache, moe_info


def init_block_cache(cfg: ModelConfig, kinds, batch: int, max_len: int,
                     dtype=None, n_cross: Optional[int] = None):
    """Allocate an empty cache for one block (None if the block is
    cache-free, e.g. training mode handles caches as None)."""
    mixer_kind, _ = kinds
    dt = jnp.dtype(dtype or cfg.dtype)
    if mixer_kind == "mamba":
        return init_mamba_cache(cfg, batch, dt)
    a = cfg.attn
    hd = cfg.head_dim()
    if mixer_kind == "cross":
        T = n_cross or cfg.n_vision_tokens
        return {"xk": jnp.zeros((batch, T, a.n_heads, hd), dt),
                "xv": jnp.zeros((batch, T, a.n_heads, hd), dt)}
    # self-attention caches
    S_c = max_len
    if mixer_kind == "attn_local" and a.sliding_window:
        S_c = min(max_len, a.sliding_window)
    if a.mla is not None:
        m = a.mla
        c = {"ckv": jnp.zeros((batch, S_c, m.kv_lora_rank), dt),
             "kpe": jnp.zeros((batch, S_c, m.qk_rope_head_dim), dt),
             "pos": jnp.full((batch, S_c), -1, jnp.int32)}
    else:
        c = {"k": jnp.zeros((batch, S_c, a.n_kv_heads, hd), dt),
             "v": jnp.zeros((batch, S_c, a.n_kv_heads, hd), dt),
             "pos": jnp.full((batch, S_c), -1, jnp.int32)}
    if mixer_kind == "self_cross":
        T = n_cross or max_len
        c["xk"] = jnp.zeros((batch, T, a.n_heads, hd), dt)
        c["xv"] = jnp.zeros((batch, T, a.n_heads, hd), dt)
    return c
