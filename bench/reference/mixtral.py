"""Plain float32 Mixtral forward pass, from the published equations
(arXiv:2401.04088; the Hugging Face ``MixtralForCausalLM`` config keys),
written without any of the program's code.

    x_0     = E[tokens]
    h       = RMSNorm(x) * g_attn                 (eps = rms_norm_eps)
    q, k, v = h Wq, h Wk, h Wv                    (GQA: head i reads kv head
                                                   i // (H / Hkv))
    q, k    = RoPE(q), RoPE(k)                    (theta = rope_theta,
                                                   rotate-half pairing)
    x       = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    h       = RMSNorm(x) * g_ffn
    r       = h Wr;  (i_1, i_2) = top-2(r);  w = softmax(r_i1, r_i2)
    x       = x + sum_j w_j * (silu(h Wg_ij) * (h Wu_ij)) Wd_ij
    logits  = (RMSNorm(x_L) * g_final) W_head

Weights come from ``bench.weights`` (the same seeded values the program is
given), one layer and one expert at a time, so the whole model never
has to be resident.  Every matrix product runs at
``Precision.HIGHEST`` in float32.  ``precision="fp8"`` is the control:
the same pass with every matrix product's operands rounded to
float8_e4m3fn (weights scaled per matrix, activations per row, to the
format's largest finite value), the step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.families.mixtral import EXPERT, LAYER

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
ROW_BLOCK = 2048       # expert FFN rows per block (bounds the (rows, f) temp)


def _q8(a, axis):
    """Round ``a`` to float8_e4m3fn with an absmax scale over ``axis``
    (None: the whole tensor) and return it as float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    s = jnp.where(s > 0, s / F8_MAX, 1.0)
    return (a / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32 at HIGHEST precision."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, delta, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * W.norm_scale(delta)


def rope(x, theta):
    """x (S, heads, hd) at positions 0..S-1; rotate-half pairing."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _dims(m):
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // H
    return H, Hkv, hd


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(mkey, fp8, lo, hi, tokens):
    m = dict(mkey)
    return W.make_leaf(W.base_key(lo, hi), m, "embed")[tokens].astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention_and_router(mkey, fp8, lo, hi, layer, x):
    """x (N, S, d) -> (x after attention, ffn input h, top-2 ids, gates)."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    H, Hkv, hd = _dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    leaf = {n: W.make_leaf(base, m, n, layer).astype(jnp.float32)
            for n in LAYER}

    def one(xs):                                     # (S, d)
        S = xs.shape[0]
        h = rms_norm(xs, leaf["attn_norm"], eps)
        q = rope(mm(h, leaf["wq"], fp8).reshape(S, H, hd), theta)
        k = rope(mm(h, leaf["wk"], fp8).reshape(S, Hkv, hd), theta)
        v = mm(h, leaf["wv"], fp8).reshape(S, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)          # head i -> kv i//G
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, -1)
        return xs + mm(o, leaf["wo"], fp8)

    x = jax.lax.map(one, x)
    h = rms_norm(x, leaf["ffn_norm"], eps)
    r = mm(h, leaf["router"], fp8)
    top, ids = jax.lax.top_k(r, m["num_experts_per_tok"])
    return x, h, ids, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _expert(mkey, fp8, lo, hi, layer, expert, y, h, ids, gates):
    """y += (gate weight of ``expert``) * FFN_expert(h), row by row."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    wg, wu, wd = (W.make_leaf(base, m, n, layer, expert).astype(jnp.float32)
                  for n in EXPERT)
    w = jnp.sum(jnp.where(ids == expert, gates, 0.0), axis=-1)  # (N, S)
    shape = h.shape
    rows = shape[0] * shape[1]
    pad = (-rows) % ROW_BLOCK
    hf = jnp.pad(h.reshape(rows, -1), ((0, pad), (0, 0)))
    wf = jnp.pad(w.reshape(rows, 1), ((0, pad), (0, 0)))
    hf = hf.reshape(-1, ROW_BLOCK, shape[-1])
    wf = wf.reshape(-1, ROW_BLOCK, 1)

    def block(args):
        hb, wb = args
        a = jax.nn.silu(mm(hb, wg, fp8)) * mm(hb, wu, fp8)
        return wb * mm(a, wd, fp8)

    out = jax.lax.map(block, (hf, wf)).reshape(-1, shape[-1])[:rows]
    return y + out.reshape(shape)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(mkey, fp8, lo, hi, x_rows):
    m = dict(mkey)
    base = W.base_key(lo, hi)
    h = rms_norm(x_rows, W.make_leaf(base, m, "final_norm"),
                 m["rms_norm_eps"])
    return mm(h, W.make_leaf(base, m, "head").astype(jnp.float32), fp8)


def _pad_len(n: int) -> int:
    """Sequence length bucket: the next multiple of 256."""
    return max(256, -(-n // 256) * 256)


def config_key(m: dict):
    """Hashable view of the numbers the reference reads (and its family,
    which lays out the seeded weights)."""
    keys = ("reference", "hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_local_experts",
            "num_experts_per_tok", "num_hidden_layers", "rms_norm_eps",
            "rope_theta", "vocab_size")
    return tuple((k, m.get(k)) for k in keys)


def logits_at(m: dict, seed: int, seqs, rows, precision: str = "f32"):
    """Reference logits of sequences ``seqs`` (each an int array of token
    ids) at positions ``rows[i]`` of sequence i: a list of (len(rows[i]),
    vocab) float32 numpy arrays.  The whole batch runs layer by layer,
    expert by expert."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision must be f32 or fp8, got {precision!r}")
    fp8 = precision == "fp8"
    mkey = config_key(m)
    lo, hi = W.seed_halves(seed)
    n = len(seqs)
    N = 1 << (n - 1).bit_length()                    # power of two
    S = _pad_len(max(len(s) for s in seqs))
    toks = np.zeros((N, S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = _embed(mkey, fp8, lo, hi, jnp.asarray(toks))
    for layer in range(m["num_hidden_layers"]):
        x, h, ids, gates = _attention_and_router(mkey, fp8, lo, hi,
                                                 jnp.int32(layer), x)
        y = jnp.zeros_like(x)
        for e in range(m["num_local_experts"]):
            y = _expert(mkey, fp8, lo, hi, jnp.int32(layer), jnp.int32(e),
                        y, h, ids, gates)
        x = x + y
        del h, y
    flat = np.concatenate([i * S + np.asarray(r, np.int64)
                           for i, r in enumerate(rows)])
    out = np.asarray(_head(mkey, fp8, lo, hi,
                           x.reshape(N * S, -1)[jnp.asarray(flat)]))
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)
