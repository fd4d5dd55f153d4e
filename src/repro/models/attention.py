"""Attention variants: GQA (rope, qk-norm, sliding window, softcap), MLA
(DeepSeek-V2 multi-head latent attention), and cross-attention (VLM /
encoder-decoder).

Cache convention
----------------
A cache is a dict pytree per layer slot:
  GQA:   {"k": (B, S_c, Hkv, D), "v": (B, S_c, Hkv, D), "pos": (B, S_c)}
  MLA:   {"ckv": (B, S_c, R), "kpe": (B, S_c, Dr), "pos": (B, S_c)}
  cross: {"k": (B, T_src, Hkv, D), "v": ...}   (static; built at prefill)
``pos`` holds, per batch row, the absolute token position stored in each
slot (-1 = empty); sliding-window layers use a rolling buffer (slot =
pos % window) and the mask is derived purely from ``pos``, so one code
path serves full, rolling, prefill and decode cases.

Position convention (continuous batching, DESIGN.md §3)
-------------------------------------------------------
``positions`` is either ``(S,)`` — shared across the batch (training,
prefill, wave-synchronised decode) — or ``(B, S)`` — per-slot offsets, the
continuous-batching decode case where every slot sits at its own sequence
position.  Shared positions keep the cheap contiguous
``dynamic_update_slice`` cache-write path; per-slot positions use a per-row
scatter.  Masks are always computed per batch row from the cache's ``pos``
rows, so both conventions share one attention code path.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (apply_rope, dense_init, rms_norm_vec, rope_table,
                     score_divisor)

NEG_INF = -1e30


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, kind: str = "attn"):
    a = cfg.attn
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    if a.mla is not None and kind != "cross":
        m = a.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq": dense_init(ks[0], (d, a.n_heads * qk_dim), dt),
            "wdkv": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
            "ckv_norm": jnp.zeros((m.kv_lora_rank,), dt),
            "wuk": dense_init(ks[2], (m.kv_lora_rank, a.n_heads * m.qk_nope_head_dim), dt),
            "wuv": dense_init(ks[3], (m.kv_lora_rank, a.n_heads * m.v_head_dim), dt),
            "wo": dense_init(ks[4], (a.n_heads * m.v_head_dim, d), dt),
        }
        if m.q_lora_rank:
            p["wdq"] = dense_init(ks[5], (d, m.q_lora_rank), dt)
            p["q_norm"] = jnp.zeros((m.q_lora_rank,), dt)
            p["wq"] = dense_init(ks[0], (m.q_lora_rank, a.n_heads * qk_dim), dt)
        return p
    hd = cfg.head_dim()
    n_kv = a.n_heads if kind == "cross" else a.n_kv_heads
    p = {
        "wq": dense_init(ks[0], (d, a.n_heads * hd), dt),
        "wk": dense_init(ks[1], (d, n_kv * hd), dt),
        "wv": dense_init(ks[2], (d, n_kv * hd), dt),
        "wo": dense_init(ks[3], (a.n_heads * hd, d), dt),
    }
    if a.qk_norm or kind == "cross":
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    if kind == "cross":
        p["gate"] = jnp.zeros((), dt)   # tanh-gated cross-attn (llama3.2-v)
    return p


# --------------------------------------------------------------------------
# core masked attention
# --------------------------------------------------------------------------

# KV lengths at or above this use the memory-bounded blockwise path
BLOCKWISE_KV_THRESHOLD = 4096
BLOCKWISE_KV_BLOCK = 1024


def _pos_rows(pos):
    """Normalise a position vector to per-row form (Bm, S), Bm in {1, B}."""
    return pos if pos.ndim == 2 else pos[None]


def _attn_mask(q_pos, k_pos, *, causal: bool, window: int):
    """Validity mask (Bm, Sq, Sk) from per-row positions; Bm broadcasts."""
    qp = _pos_rows(q_pos)[:, :, None]          # (Bq, Sq, 1)
    kp = _pos_rows(k_pos)[:, None, :]          # (Bk, 1, Sk)
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window:
        valid = valid & (kp > qp - window)
    return valid


def _mha(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
         softcap: float, scale: float):
    """q: (B,Sq,Hq,D)  k/v: (B,Sk,Hkv,D)  pos: (Sq,)|(B,Sq), (Sk,)|(B,Sk)."""
    # blockwise only pays when Sq x Sk scores would blow memory; decode
    # (Sq==1) keeps the dense path, which cooperates with sequence-sharded
    # KV (softmax over the sharded axis -> GSPMD all-reduce).
    if q.shape[1] > 1 and k.shape[1] >= BLOCKWISE_KV_THRESHOLD:
        return _mha_blockwise(q, k, v, q_pos, k_pos, causal=causal,
                              window=window, softcap=softcap, scale=scale)
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = _attn_mask(q_pos, k_pos, causal=causal, window=window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    Dv = v.shape[-1]            # may differ from q head_dim (MLA)
    return o.reshape(B, Sq, Hq * Dv).astype(q.dtype)


BLOCKWISE_Q_CHUNK = 2048


def _mha_blockwise(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                   softcap: float, scale: float,
                   block: int = BLOCKWISE_KV_BLOCK):
    """Online-softmax (flash-style) attention scanning KV blocks: O(q_chunk x
    block) transient memory instead of O(Sq·Sk).  Long query runs are also
    chunked (lax.map over independent query slabs).  Numerically matches
    _mha; the Pallas flash_attention kernel implements the same recurrence
    with VMEM tiling for TPU."""
    from repro.launch.sharding import hint
    # pin K/V layout before the q-chunk loop: otherwise GSPMD re-gathers
    # them over 'model' inside every loop iteration (measured: 16x the
    # traffic on 32k prefill — EXPERIMENTS.md §Perf/qwen3-30b iteration 3)
    k = hint(k, "batch", "seq", "kv_heads", "head_dim")
    v = hint(v, "batch", "seq", "kv_heads", "head_dim")
    q_pos, k_pos = _pos_rows(q_pos), _pos_rows(k_pos)
    Sq_full = q.shape[1]
    qc = BLOCKWISE_Q_CHUNK
    if Sq_full > qc and Sq_full % qc == 0:
        nq = Sq_full // qc
        qs = q.reshape(q.shape[0], nq, qc, *q.shape[2:]).transpose(
            1, 0, 2, 3, 4)
        qps = q_pos.reshape(q_pos.shape[0], nq, qc).transpose(1, 0, 2)
        out = jax.lax.map(
            lambda args: _mha_blockwise_inner(
                args[0], k, v, args[1], k_pos, causal=causal, window=window,
                softcap=softcap, scale=scale, block=block),
            (qs, qps))
        return out.transpose(1, 0, 2, 3).reshape(q.shape[0], Sq_full, -1)
    return _mha_blockwise_inner(q, k, v, q_pos, k_pos, causal=causal,
                                window=window, softcap=softcap, scale=scale,
                                block=block)


def _mha_blockwise_inner(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                         softcap: float, scale: float,
                         block: int = BLOCKWISE_KV_BLOCK):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    f32 = jnp.float32
    qg = q.reshape(B, Sq, Hkv, G, D).astype(f32)

    q_pos, k_pos = _pos_rows(q_pos), _pos_rows(k_pos)
    pad = (-Sk) % block
    if pad:
        zp = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k, v = zp(k), zp(v)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nb = (Sk + pad) // block
    kb = k.reshape(B, nb, block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(k_pos.shape[0], nb, block).transpose(1, 0, 2)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp = blk
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kblk.astype(f32)) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        valid = _attn_mask(q_pos, kp, causal=causal, window=window)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vblk.astype(f32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, f32)
    l0 = jnp.zeros((B, Hkv, G, Sq), f32)
    a0 = jnp.zeros((B, Hkv, G, Sq, Dv), f32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    o = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,K,G,Sq,Dv)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq * Dv)
    return o.astype(q.dtype)


def _write_buf(buf, new, start):
    """Contiguous (rolling) write of `new` (B,S,...) into buf at slot
    start % S_c via dynamic_update_slice — cheaper to lower than scatter."""
    S_c = buf.shape[1]
    idx = (jnp.zeros((), jnp.int32), start % S_c) + \
        tuple(jnp.zeros((), jnp.int32) for _ in buf.shape[2:])
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), idx)


def _update_pos_rows(pos_buf, positions, start):
    """Write shared ``positions`` (S,) into every row of (B, S_c) ``pos_buf``
    from rolling slot ``start % S_c``."""
    B, S_c = pos_buf.shape
    rows = jnp.broadcast_to(positions[None], (B, positions.shape[0]))
    return jax.lax.dynamic_update_slice(
        pos_buf, rows.astype(pos_buf.dtype),
        (jnp.zeros((), jnp.int32), start % S_c))


def _update_cache(cache, new_k, new_v, positions):
    """Write new tokens into the cache.

    Shared positions (S,): writes are contiguous from positions[0]; slot =
    pos % S_c (identity for full-size caches, rolling buffer for
    sliding-window caches allocated at window size).  Assumes the new chunk
    does not itself wrap around the rolling buffer (true for decode S=1 and
    for prefill into full-size caches).  A prefill longer than a rolling
    buffer keeps only its last S_c tokens (sliding-window semantics).

    Per-slot positions (B, S): each row writes at its own rolling offset
    via a per-row scatter (continuous-batching decode; S is small)."""
    S_cache = cache["k"].shape[1]
    if positions.ndim == 2:
        B = positions.shape[0]
        slot = (positions % S_cache).astype(jnp.int32)          # (B, S)
        b_idx = jnp.arange(B)[:, None]
        k = cache["k"].at[b_idx, slot].set(new_k.astype(cache["k"].dtype))
        v = cache["v"].at[b_idx, slot].set(new_v.astype(cache["v"].dtype))
        pos = cache["pos"].at[b_idx, slot].set(
            positions.astype(cache["pos"].dtype))
        return {"k": k, "v": v, "pos": pos}
    if new_k.shape[1] > S_cache:
        new_k = new_k[:, -S_cache:]
        new_v = new_v[:, -S_cache:]
        positions = positions[-S_cache:]
    start = positions[0].astype(jnp.int32)
    k = _write_buf(cache["k"], new_k, start)
    v = _write_buf(cache["v"], new_v, start)
    pos = _update_pos_rows(cache["pos"], positions, start)
    return {"k": k, "v": v, "pos": pos}


# --------------------------------------------------------------------------
# GQA self-attention
# --------------------------------------------------------------------------

def gqa_attention(params, x, cfg: ModelConfig, *, kind: str,
                  positions, cache=None, causal: bool = True):
    """kind in {"attn", "attn_local", "attn_global"}.  Returns (y, cache')."""
    a = cfg.attn
    hd = cfg.head_dim()
    B, S, _ = x.shape
    from repro.launch.sharding import hint
    q = hint((x @ params["wq"]).reshape(B, S, a.n_heads, hd),
             "batch", "seq", "heads", "head_dim")
    k = hint((x @ params["wk"]).reshape(B, S, a.n_kv_heads, hd),
             "batch", "seq", "kv_heads", "head_dim")
    v = hint((x @ params["wv"]).reshape(B, S, a.n_kv_heads, hd),
             "batch", "seq", "kv_heads", "head_dim")
    if a.qk_norm:
        q = rms_norm_vec(params["q_norm"], q)
        k = rms_norm_vec(params["k_norm"], k)
    # (Bm, S, D/2) tables: Bm=1 broadcasts for shared positions, Bm=B gives
    # every slot its own rotary phase (continuous batching)
    cos, sin = rope_table(_pos_rows(positions), hd, a.rope_theta, a.yarn)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    window = a.sliding_window if kind == "attn_local" else 0
    scale = 1.0 / score_divisor(a.yarn, hd)
    if cache is None:
        y = _mha(q, k, v, positions, positions, causal=causal,
                 window=window, softcap=a.attn_softcap, scale=scale)
        new_cache = None
    elif S > 1:
        # prefill: the cache was empty, so fresh K/V == cache content;
        # attending over the fresh tensors keeps the math independent of
        # the cache's (possibly sequence-sharded) storage layout.
        new_cache = _update_cache(cache, k, v, positions)
        y = _mha(q, k, v, positions, positions, causal=causal,
                 window=window, softcap=a.attn_softcap, scale=scale)
    else:
        new_cache = _update_cache(cache, k, v, positions)
        y = _mha(q, new_cache["k"], new_cache["v"], positions,
                 new_cache["pos"], causal=causal, window=window,
                 softcap=a.attn_softcap, scale=scale)
    return y @ params["wo"], new_cache


# --------------------------------------------------------------------------
# MLA self-attention (DeepSeek-V2)
# --------------------------------------------------------------------------

def mla_attention(params, x, cfg: ModelConfig, *, positions, cache=None):
    a, m = cfg.attn, cfg.attn.mla
    B, S, _ = x.shape
    H = a.n_heads
    nope, rp, vd, R = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                       m.v_head_dim, m.kv_lora_rank)
    if m.q_lora_rank:
        cq = rms_norm_vec(params["q_norm"], x @ params["wdq"])
        q = (cq @ params["wq"]).reshape(B, S, H, nope + rp)
    else:
        q = (x @ params["wq"]).reshape(B, S, H, nope + rp)
    q_nope, q_pe = q[..., :nope], q[..., nope:]

    dkv = x @ params["wdkv"]
    ckv = rms_norm_vec(params["ckv_norm"], dkv[..., :R])       # (B,S,R)
    kpe = dkv[..., R:][:, :, None, :]                          # (B,S,1,rp)

    cos, sin = rope_table(_pos_rows(positions), rp, a.rope_theta, a.yarn)
    q_pe = apply_rope(q_pe, cos, sin)
    kpe = apply_rope(kpe, cos, sin)

    if cache is not None:
        S_c = cache["ckv"].shape[1]
        if positions.ndim == 2:        # per-slot offsets: per-row scatter
            slot = (positions % S_c).astype(jnp.int32)
            b_idx = jnp.arange(B)[:, None]
            ckv_b = cache["ckv"].at[b_idx, slot].set(
                ckv.astype(cache["ckv"].dtype))
            kpe_b = cache["kpe"].at[b_idx, slot].set(
                kpe[:, :, 0].astype(cache["kpe"].dtype))
            pos_b = cache["pos"].at[b_idx, slot].set(
                positions.astype(cache["pos"].dtype))
        else:
            start = positions[0].astype(jnp.int32)
            ckv_b = _write_buf(cache["ckv"], ckv, start)
            kpe_b = _write_buf(cache["kpe"], kpe[:, :, 0], start)
            pos_b = _update_pos_rows(cache["pos"], positions, start)
        cache = {"ckv": ckv_b, "kpe": kpe_b, "pos": pos_b}
        if S > 1:   # prefill: attend over fresh latents (see gqa_attention)
            ckv_all, kpe_all, k_pos = ckv, kpe, positions
        else:
            ckv_all, kpe_all, k_pos = ckv_b, kpe_b[:, :, None], pos_b
    else:
        ckv_all, kpe_all, k_pos = ckv, kpe, positions

    if m.absorbed_decode and S == 1 and cache is not None:
        # absorbed decode (EXPERIMENTS.md §Perf/deepseek): attend in the
        # compressed latent space — W_uk absorbed into the query, W_uv
        # applied to the latent attention output.  Avoids decompressing
        # the whole (S, R) cache to (S, H, nope+v) every step.
        f32 = jnp.float32
        wuk = params["wuk"].reshape(R, H, nope)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(f32),
                           wuk.astype(f32))                    # (B,1,H,R)
        ckv_f = ckv_all.astype(f32)                            # (B,S,R)
        s = jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv_f)
        s = s + jnp.einsum("bqhp,bsp->bhqs", q_pe.astype(f32),
                           kpe_all[:, :, 0].astype(f32)
                           if kpe_all.ndim == 4 else kpe_all.astype(f32))
        s = s / score_divisor(a.yarn, nope + rp)
        valid = _attn_mask(positions, k_pos, causal=True, window=0)
        s = jnp.where(valid[:, None], s, NEG_INF)   # (Bm,1,Sq,S) vs (B,H,Sq,S)
        p = jax.nn.softmax(s, axis=-1)                         # (B,H,1,S)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", p, ckv_f)         # (B,1,H,R)
        wuv = params["wuv"].reshape(R, H, vd)
        y = jnp.einsum("bqhr,rhv->bqhv", o_lat, wuv.astype(f32))
        y = y.reshape(B, S, H * vd).astype(x.dtype)
        return y @ params["wo"], cache

    # decompress cached latents to per-head K/V ("naive" MLA baseline)
    Sk = ckv_all.shape[1]
    k_nope = (ckv_all @ params["wuk"]).reshape(B, Sk, H, nope)
    vv = (ckv_all @ params["wuv"]).reshape(B, Sk, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kpe_all, (B, Sk, H, rp)).astype(k_nope.dtype)], axis=-1)
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    y = _mha(q_full, k, vv, positions, k_pos, causal=True, window=0,
             softcap=0.0, scale=1.0 / score_divisor(a.yarn, nope + rp))
    return y @ params["wo"], cache


# --------------------------------------------------------------------------
# Cross-attention (VLM cross layers / enc-dec decoder)
# --------------------------------------------------------------------------

def build_cross_kv(params, src, cfg: ModelConfig):
    """Precompute K/V from encoder/vision embeddings src (B, T, d)."""
    a = cfg.attn
    hd = cfg.head_dim()
    B, T, _ = src.shape
    k = (src @ params["wk"]).reshape(B, T, a.n_heads, hd)
    v = (src @ params["wv"]).reshape(B, T, a.n_heads, hd)
    if "k_norm" in params:
        k = rms_norm_vec(params["k_norm"], k)
    return {"k": k, "v": v}


def cross_attention(params, x, cfg: ModelConfig, cross_kv):
    a = cfg.attn
    hd = cfg.head_dim()
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, a.n_heads, hd)
    if "q_norm" in params:
        q = rms_norm_vec(params["q_norm"], q)
    T = cross_kv["k"].shape[1]
    y = _mha(q, cross_kv["k"], cross_kv["v"],
             jnp.zeros((S,), jnp.int32), jnp.zeros((T,), jnp.int32),
             causal=False, window=0, softcap=0.0, scale=1.0 / np.sqrt(hd))
    y = y @ params["wo"]
    if "gate" in params:
        y = jnp.tanh(params["gate"].astype(jnp.float32)).astype(y.dtype) * y
    return y
