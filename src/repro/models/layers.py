"""Shared primitive layers: norms, rotary embeddings, dense FFN, embeddings.

All layers follow the same pure-functional convention:
  ``init_xxx(key, cfg, ...) -> params``   (nested dict pytree)
  ``xxx(params, x, ...) -> y``
Params are created in ``cfg.param_dtype``; math runs in float32 where it
matters for stability (norms, softmax) and ``cfg.dtype`` elsewhere.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init (matches common LLM practice)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_norm(key, cfg: ModelConfig, d: int | None = None):
    if cfg.norm == "nonparam_ln":          # OLMo: no learned scale/bias
        return {}
    return {"w": jnp.zeros((d or cfg.d_model,), _dt(cfg))}


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "nonparam_ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return y.astype(x.dtype)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    # (1 + w) parameterisation (llama/gemma style, zero-init friendly)
    return (y * (1.0 + params["w"].astype(jnp.float32))).astype(x.dtype)


def rms_norm_vec(w, x, eps: float = 1e-6):
    """Headwise RMSNorm used by qk-norm (Qwen3)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 1 + 0.1 * mscale * ln(factor)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, head_dim: int, theta: float):
    """(low, high): the frequency pairs that turn ``beta_fast`` and
    ``beta_slow`` times over the original context, clipped to the pairs."""
    def pair(turns):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(yarn.beta_fast)), 0),
            min(math.ceil(pair(yarn.beta_slow)), head_dim - 1))


def yarn_inv_freq(yarn, head_dim: int, theta: float) -> np.ndarray:
    """Per-pair rotary frequencies under YaRN: unscaled below the
    correction range, divided by ``factor`` above it, a linear ramp
    between."""
    extra = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    low, high = yarn_correction_range(yarn, head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def score_divisor(yarn, qk_dim: int) -> float:
    """What attention scores are divided by: sqrt(qk_dim), over YaRN's
    mscale(factor, mscale_all_dim)^2 where the model has it."""
    if yarn is None:
        return np.sqrt(qk_dim)
    return np.sqrt(qk_dim) / yarn_mscale(yarn.factor,
                                         yarn.mscale_all_dim) ** 2


def rope_table(positions, head_dim: int, theta: float, yarn=None):
    """cos/sin tables for given integer positions -> (..., head_dim//2);
    ``yarn`` (a ``YarnConfig``) rescales the frequencies and magnitudes."""
    if yarn is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    else:
        inv_freq = yarn_inv_freq(yarn, head_dim, theta)
    ang = positions[..., None].astype(jnp.float32) * inv_freq[None, :]
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    mag = (yarn_mscale(yarn.factor, yarn.mscale)
           / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def apply_rope(x, cos, sin):
    """x: (..., S, H, D); cos/sin: (..., S, D//2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# Dense FFN (SwiGLU / GeGLU / plain)
# --------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], (d, f), _dt(cfg)),
         "down": dense_init(ks[1], (f, d), _dt(cfg))}
    if cfg.glu:
        p["gate"] = dense_init(ks[2], (d, f), _dt(cfg))
    return p


def apply_mlp(params, x, cfg: ModelConfig):
    act = _ACTS[cfg.act]
    up = x @ params["up"]
    h = act(x @ params["gate"]) * up if cfg.glu else act(up)
    return h @ params["down"]


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p = {"tok": dense_init(ks[0], (cfg.vocab, cfg.d_model), _dt(cfg), 1.0)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab), _dt(cfg))
    return p


def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["tok"], tokens, axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params, x, cfg: ModelConfig):
    w = params["tok"].T if cfg.tie_embeddings else params["head"]
    # pad vocab to a shardable multiple so logits can split over 'model'
    # (padded columns forced to -inf: never sampled, zero softmax mass)
    V = w.shape[-1]
    pad = (-V) % 256
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    if pad:
        col = jnp.arange(V + pad)
        logits = jnp.where(col[None, None, :] < V, logits, -1e30)
    return logits
