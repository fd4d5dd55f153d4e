"""Plain float32 DeepSeek-V2 forward pass, from the published equations
(arXiv:2405.04434; the Hugging Face ``DeepseekV2ForCausalLM`` config keys
and modelling code), written without any of the program's code.

    x_0      = E[tokens]
    h        = RMSNorm(x) * g_attn                      (eps = rms_norm_eps)
    q        = h Wq                  per head [q_nope (128) | q_pe (64)]
    [c, kp]  = h Wkv_a               c: the 512-wide latent, kp: the shared
                                     64-wide rope key
    c        = RMSNorm(c) * g_kv
    [k, v]   = c Wkv_b               per head [k_nope (128) | v (128)]
    q_pe, kp = RoPE(deinterleave(q_pe)), RoPE(deinterleave(kp))
    x        = x + softmax([q_nope|q_pe] [k|kp]^T * scale + causal) v Wo
    h        = RMSNorm(x) * g_ffn
    layers < first_k_dense_replace:
      x      = x + (silu(h Wg) * (h Wu)) Wd                   (width 10944)
    the others:
      p      = softmax(h Wr);  (i_1..i_6) = top-6(p);  w_j = p_ij
               (norm_topk_prob false: not renormalised; scaling factor 1)
      x      = x + sum_j w_j FFN_ij(h) + FFN_shared(h)   (shared width 2816)
    logits   = (RMSNorm(x_L) * g_final) W_head

``deinterleave`` is Hugging Face's ``view(d/2, 2).transpose`` of the rope
dims (even dims, then odd) before its rotate-half.  RoPE is YaRN
(``rope_scaling``): pair i of theta^(-2i/64) is kept below the
correction range, divided by ``factor`` above it, with a linear ramp
between; cos and sin are scaled by mscale(factor, mscale) /
mscale(factor, mscale_all_dim), and ``scale`` is 1/sqrt(192) times
mscale(factor, mscale_all_dim)^2, mscale(s, m) = 1 + 0.1 m ln s.

Weights come from ``bench.weights`` (the same seeded values the program
is given, in Hugging Face's layout), one layer and one expert at a time,
so the whole model never has to be resident.  Every matrix product runs
at ``Precision.HIGHEST`` in float32.  ``precision="fp8"`` is the control:
the same pass with every matrix product's operands rounded to
float8_e4m3fn (weights scaled per matrix, activations per row), the step
below the configuration's bfloat16.

Departures from the published model: seeded weights; the norm scales are
1 + delta (the program's parameterisation) where the checkpoint stores
the scale itself, the same values either way.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference.mixtral import _pad_len, mm, rms_norm

HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 2048       # FFN rows per block (bounds the (rows, f) temp)


def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """Hugging Face's ``DeepseekV2YarnRotaryEmbedding`` frequencies
    (``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``)."""
    def correction_dim(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** pos
    freq_inter = 1.0 / (factor * base ** pos)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope_consts(m):
    """(inv_freq, cos/sin magnitude, softmax scale)."""
    rp = m["qk_rope_head_dim"]
    scale = (m["qk_nope_head_dim"] + rp) ** -0.5
    rs = m.get("rope_scaling")
    if rs is None:
        inv = 1.0 / m["rope_theta"] ** (np.arange(0, rp, 2) / rp)
        return inv, 1.0, scale
    f = rs["factor"]
    inv = yarn_inv_freq(rp, m["rope_theta"], f,
                        rs["original_max_position_embeddings"],
                        rs["beta_fast"], rs["beta_slow"])
    mag = yarn_get_mscale(f, rs["mscale"]) / yarn_get_mscale(
        f, rs["mscale_all_dim"])
    if rs.get("mscale_all_dim"):
        scale = scale * yarn_get_mscale(f, rs["mscale_all_dim"]) ** 2
    return inv, mag, scale


def rope(x, inv, mag):
    """x (S, heads, rp) at positions 0..S-1, rope dims interleaved as the
    projection gives them: de-interleaved, then rotate-half."""
    S, _, rp = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    c, s = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    rot = jnp.concatenate([-x[..., rp // 2:], x[..., : rp // 2]], axis=-1)
    return x * c + rot * s


def _config(mkey):
    """``config_key``'s view back as a configuration dict."""
    m = dict(mkey)
    if m["rope_scaling"] is not None:
        m["rope_scaling"] = dict(m["rope_scaling"])
    return m


def _leaf(base, m, name, layer=-1, expert=0):
    return W.make_leaf(base, m, name, layer, expert).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(mkey, fp8, lo, hi, tokens):
    m = dict(mkey)
    return W.make_leaf(W.base_key(lo, hi), m, "embed")[tokens].astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(mkey, fp8, lo, hi, layer, x):
    """x (N, S, d) -> (x after attention, the FFN input h)."""
    m = _config(mkey)
    base = W.base_key(lo, hi)
    H, nope, rp = (m["num_attention_heads"], m["qk_nope_head_dim"],
                   m["qk_rope_head_dim"])
    vd, R, eps = m["v_head_dim"], m["kv_lora_rank"], m["rms_norm_eps"]
    inv, mag, scale = _rope_consts(m)
    leaf = {n: _leaf(base, m, n, layer) for n in (
        "attn_norm", "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
        "kv_b_proj", "o_proj", "ffn_norm")}

    def one(xs):                                     # (S, d)
        S = xs.shape[0]
        h = rms_norm(xs, leaf["attn_norm"], eps)
        q = mm(h, leaf["q_proj"], fp8).reshape(S, H, nope + rp)
        ckv = mm(h, leaf["kv_a_proj_with_mqa"], fp8)
        c = rms_norm(ckv[:, :R], leaf["kv_a_layernorm"], eps)
        kv = mm(c, leaf["kv_b_proj"], fp8).reshape(S, H, nope + vd)
        q_pe = rope(q[..., nope:], inv, mag)
        k_pe = rope(ckv[:, None, R:], inv, mag)
        qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        kf = jnp.concatenate([kv[..., :nope],
                              jnp.broadcast_to(k_pe, (S, H, rp))], axis=-1)
        s = jnp.einsum("qhd,khd->hqk", qf, kf, precision=HI) * scale
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:],
                       precision=HI).reshape(S, -1)
        return xs + mm(o, leaf["o_proj"], fp8)

    x = jax.lax.map(one, x)
    return x, rms_norm(x, leaf["ffn_norm"], eps)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _router(mkey, fp8, lo, hi, layer, h):
    """Top-k ids and their softmax probabilities (not renormalised)."""
    m = dict(mkey)
    r = mm(h, _leaf(W.base_key(lo, hi), m, "router", layer), fp8)
    p = jax.nn.softmax(r, axis=-1)
    gates, ids = jax.lax.top_k(p, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return ids, gates * m["routed_scaling_factor"]


def _ffn_rows(h, w, wg, wu, wd, fp8):
    """sum of w * (silu(h Wg) * (h Wu)) Wd over (N, S) rows, in blocks."""
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
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _ffn(mkey, fp8, kind, lo, hi, layer, y, h):
    """y += FFN(h) for every row: ``kind`` "dense" (the leading dense
    layers) or "shared" (the shared experts, one FFN of their summed
    width)."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    wg, wu, wd = (_leaf(base, m, f"{kind}_{n}", layer)
                  for n in ("gate", "up", "down"))
    return y + _ffn_rows(h, jnp.ones(h.shape[:2]), wg, wu, wd, fp8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _expert(mkey, fp8, lo, hi, layer, expert, y, h, ids, gates):
    """y += (gate weight of ``expert``) * FFN_expert(h), row by row."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    wg, wu, wd = (_leaf(base, m, n, layer, expert)
                  for n in ("w_gate", "w_up", "w_down"))
    w = jnp.sum(jnp.where(ids == expert, gates, 0.0), axis=-1)  # (N, S)
    return y + _ffn_rows(h, w, wg, wu, wd, fp8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(mkey, fp8, lo, hi, x_rows):
    m = dict(mkey)
    base = W.base_key(lo, hi)
    h = rms_norm(x_rows, W.make_leaf(base, m, "final_norm"),
                 m["rms_norm_eps"])
    return mm(h, _leaf(base, m, "head"), fp8)


def config_key(m: dict):
    """Hashable view of the numbers the reference reads (and its family,
    which lays out the seeded weights)."""
    keys = ("reference", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "first_k_dense_replace", "num_hidden_layers", "rms_norm_eps",
            "rope_theta", "vocab_size")
    key = tuple((k, m.get(k)) for k in keys)
    rs = m.get("rope_scaling")
    return key + (("rope_scaling",
                   None if rs is None else tuple(sorted(rs.items()))),)


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
        lid = jnp.int32(layer)
        x, h = _attention(mkey, fp8, lo, hi, lid, x)
        y = jnp.zeros_like(x)
        if layer < m["first_k_dense_replace"]:
            y = _ffn(mkey, fp8, "dense", lo, hi, lid, y, h)
        else:
            ids, gates = _router(mkey, fp8, lo, hi, lid, h)
            for e in range(m["n_routed_experts"]):
                y = _expert(mkey, fp8, lo, hi, lid, jnp.int32(e), y, h,
                            ids, gates)
            y = _ffn(mkey, fp8, "shared", lo, hi, lid, y, h)
        x = x + y
        del h, y
    flat = np.concatenate([i * S + np.asarray(r, np.int64)
                           for i, r in enumerate(rows)])
    out = np.asarray(_head(mkey, fp8, lo, hi,
                           x.reshape(N * S, -1)[jnp.asarray(flat)]))
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)
