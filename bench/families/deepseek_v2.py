"""The DeepSeek-V2 family (Hugging Face ``DeepseekV2ForCausalLM`` config
keys): the program's ``ModelConfig``, the layout of the seeded weights,
the program's parameter tree made from them, and the model FLOPs a token
needs.  Chosen by ``"reference": "deepseek_v2"`` in a configuration file.

The seeded leaves are Hugging Face's, in (in, out) layout:
``q_proj`` (d, H*(nope+rope)), ``kv_a_proj_with_mqa`` (d, R+rope),
``kv_a_layernorm`` (R,), ``kv_b_proj`` (R, H*(nope+v)) with per-head
[k_nope | v] columns, ``o_proj`` (H*v, d).  They map onto the program's
``wq``, ``wdkv``, ``ckv_norm``, ``wuk``, ``wuv``, ``wo``.  Hugging Face
de-interleaves the rope dims of q and k (``view(d/2, 2).transpose``)
before its rotate-half; the program rotates halves as they come, so the
rope columns of ``q_proj`` (each head's) and of ``kv_a_proj_with_mqa``
are laid out even dims first, then odd.  A change of layout with no
arithmetic: every program leaf is bit-equal to a slice of a seeded leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

# leaf ids: part of the key, never renumber
LEAVES = {"embed": 0, "head": 1, "final_norm": 2, "attn_norm": 3,
          "q_proj": 4, "kv_a_proj_with_mqa": 5, "kv_a_layernorm": 6,
          "kv_b_proj": 7, "o_proj": 8, "ffn_norm": 9, "router": 10,
          "w_gate": 11, "w_up": 12, "w_down": 13, "shared_gate": 14,
          "shared_up": 15, "shared_down": 16, "dense_gate": 17,
          "dense_up": 18, "dense_down": 19}
ATTN = ("attn_norm", "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
        "kv_b_proj", "o_proj", "ffn_norm")
EXPERT = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
DENSE = ("dense_gate", "dense_up", "dense_down")


def dims(m):
    """(d, H, nope, rope, v, R)."""
    return (m["hidden_size"], m["num_attention_heads"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["kv_lora_rank"])


def n_dense(m) -> int:
    return m["first_k_dense_replace"]


def shapes(m: dict) -> dict:
    """Shape and a_exp of every leaf."""
    d, H, nope, rp, vd, R = dims(m)
    V, E = m["vocab_size"], m["n_routed_experts"]
    f, fd = m["moe_intermediate_size"], m["intermediate_size"]
    fs = m["n_shared_experts"] * f
    fan = W.fan_in_exp
    return {
        "embed": ((V, d), W.EMBED_EXP),
        "head": ((d, V), fan(d)),
        "final_norm": ((d,), W.NORM_EXP),
        "attn_norm": ((d,), W.NORM_EXP),
        "q_proj": ((d, H * (nope + rp)), fan(d)),
        "kv_a_proj_with_mqa": ((d, R + rp), fan(d)),
        "kv_a_layernorm": ((R,), W.NORM_EXP),
        "kv_b_proj": ((R, H * (nope + vd)), fan(R)),
        "o_proj": ((H * vd, d), fan(H * vd)),
        "ffn_norm": ((d,), W.NORM_EXP),
        "router": ((d, E), fan(d)),
        "w_gate": ((d, f), fan(d)),
        "w_up": ((d, f), fan(d)),
        "w_down": ((f, d), fan(f)),
        "shared_gate": ((d, fs), fan(d)),
        "shared_up": ((d, fs), fan(d)),
        "shared_down": ((fs, d), fan(fs)),
        "dense_gate": ((d, fd), fan(d)),
        "dense_up": ((d, fd), fan(d)),
        "dense_down": ((fd, d), fan(fd)),
    }


def model_config(m: dict):
    """The program's ModelConfig for a configuration file ``m``."""
    from repro.models.config import (AttentionConfig, MLAConfig, ModelConfig,
                                     MoEConfig, YarnConfig)
    dep = m["deployment"]
    d, H, nope, rp, vd, R = dims(m)
    rs = m.get("rope_scaling")
    yarn = None
    if rs is not None:
        if rs["type"] != "yarn":
            raise ValueError(f"rope_scaling type {rs['type']!r}: only yarn")
        yarn = YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]))
    if (m["scoring_func"], m["topk_method"]) != ("softmax", "greedy") \
            or m["routed_scaling_factor"] != 1 or m["moe_layer_freq"] != 1:
        raise ValueError("the program's router is softmax, greedy top-k, "
                         "scaling 1, an MoE layer after every dense prefix")
    return ModelConfig(
        name=m.get("name", "bench"), family="moe", source=m["source"],
        n_layers=m["num_hidden_layers"], d_model=d,
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        attn=AttentionConfig(
            n_heads=H, n_kv_heads=m["num_key_value_heads"],
            rope_theta=float(m["rope_theta"]),
            mla=MLAConfig(kv_lora_rank=R, q_lora_rank=m["q_lora_rank"] or 0,
                          qk_nope_head_dim=nope, qk_rope_head_dim=rp,
                          v_head_dim=vd),
            yarn=yarn),
        moe=MoEConfig(n_routed=m["n_routed_experts"],
                      top_k=m["num_experts_per_tok"],
                      d_expert=m["moe_intermediate_size"],
                      n_shared=m["n_shared_experts"],
                      d_shared=m["n_shared_experts"]
                      * m["moe_intermediate_size"],
                      router_type="softmax_topk",
                      renormalize=bool(m["norm_topk_prob"]),
                      first_dense=n_dense(m),
                      capacity_factor=dep["capacity_factor"]),
        norm="rmsnorm", act=m["hidden_act"], glu=True,
        tie_embeddings=m.get("tie_word_embeddings", False),
        dtype=m["torch_dtype"], param_dtype=m["torch_dtype"])


def rope_order(rp: int) -> np.ndarray:
    """Hugging Face's de-interleave of the rope dims as a column order:
    the even dims, then the odd ones."""
    return np.concatenate([np.arange(0, rp, 2), np.arange(1, rp, 2)])


def mla_leaves(m: dict, leaf):
    """The program's MLA leaves from Hugging Face's (``leaf(name)``
    gives one layer's leaf): column slices and orders, no arithmetic."""
    d, H, nope, rp, vd, R = dims(m)
    order = rope_order(rp)
    q = leaf("q_proj").reshape(d, H, nope + rp)
    q = jnp.concatenate([q[..., :nope], q[..., nope:][..., order]], -1)
    dkv = leaf("kv_a_proj_with_mqa")
    kvb = leaf("kv_b_proj").reshape(R, H, nope + vd)
    return {"wq": q.reshape(d, H * (nope + rp)),
            "wdkv": jnp.concatenate([dkv[:, :R], dkv[:, R:][:, order]], -1),
            "ckv_norm": leaf("kv_a_layernorm"),
            "wuk": kvb[..., :nope].reshape(R, H * nope),
            "wuv": kvb[..., nope:].reshape(R, H * vd),
            "wo": leaf("o_proj")}


@functools.partial(jax.jit, static_argnums=0)
def params(mkey, lo, hi):
    """The program's parameter tree, every leaf from ``bench.weights``,
    made on the device in one call: ``prefix`` the dense layers, ``scan``
    the MoE layers stacked.  Expert stacks are written one (layer,
    expert) block at a time into their (L, E, ...) buffers."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    P, L = n_dense(m), m["num_hidden_layers"]
    E = m["n_routed_experts"]
    sh = shapes(m)

    def block(l, mlp):
        leaf = functools.partial(W.make_leaf, base, m, layer=l)
        return {"norm1": {"w": leaf("attn_norm")},
                "mixer": mla_leaves(m, leaf),
                "norm2": {"w": leaf("ffn_norm")}, "mlp": mlp(leaf)}

    def dense(leaf):
        return {k: leaf(f"dense_{k}") for k in ("gate", "up", "down")}

    def moe(leaf):
        return {"router": leaf("router").astype(jnp.float32),
                "shared": {k: leaf(f"shared_{k}")
                           for k in ("gate", "up", "down")}}

    def experts(name):
        shape, a = sh[name]

        def body(i, out):
            blk = W.leaf(base, P + i // E, LEAVES[name], i % E, shape, a)
            return jax.lax.dynamic_update_slice(
                out, blk[None, None], (i // E, i % E, 0, 0))

        return jax.lax.fori_loop(0, (L - P) * E, body,
                                 jnp.zeros((L - P, E) + shape, jnp.bfloat16))

    scan = jax.tree.map(lambda *a: jnp.stack(a),
                        *[block(l, moe) for l in range(P, L)])
    scan["mlp"].update(gate=experts("w_gate"), up=experts("w_up"),
                       down=experts("w_down"))
    return {"embed": {"tok": W.make_leaf(base, m, "embed"),
                      "head": W.make_leaf(base, m, "head")},
            "final_norm": {"w": W.make_leaf(base, m, "final_norm")},
            "prefix": tuple(block(l, dense) for l in range(P)),
            "scan": (scan,)}


def moe_dims(m: dict):
    """(d_model, d_expert, experts, top_k, MoE layers)."""
    return (m["hidden_size"], m["moe_intermediate_size"],
            m["n_routed_experts"], m["num_experts_per_tok"],
            m["num_hidden_layers"] - n_dense(m))


def _attn_flops(m: dict, context: int) -> float:
    """One token's MLA, as published (keys and values decompressed from
    the latent): projections, scores over ``context`` keys, values."""
    d, H, nope, rp, vd, R = dims(m)
    return (2 * d * H * (nope + rp) + 2 * d * (R + rp)
            + 2 * R * H * (nope + vd) + 2 * H * vd * d
            + 2 * H * (nope + rp + vd) * context)


def token_flops(m: dict, context: int, logits: bool = True) -> float:
    """Model FLOPs one token needs at sequence position ``context - 1``:
    MLA in every layer, the dense FFN in the leading dense layers, and in
    the MoE layers the router, top-k experts and the shared experts, plus
    the LM head when the token's logits are needed."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    E, K = m["n_routed_experts"], m["num_experts_per_tok"]
    P, L = n_dense(m), m["num_hidden_layers"]
    moe = 2 * d * E + (K + m["n_shared_experts"]) * 6 * d * f
    return (L * _attn_flops(m, context) + P * 6 * d * m["intermediate_size"]
            + (L - P) * moe + (2 * d * m["vocab_size"] if logits else 0))


def prefill_flops(m: dict, length: int) -> float:
    """A prompt of ``length`` tokens: causal attention (token i sees i + 1
    keys) and the LM head at the last position only."""
    _, H, nope, rp, vd, _ = dims(m)
    keys = length * (length + 1) // 2
    return (length * token_flops(m, 0, logits=False)
            + m["num_hidden_layers"] * 2 * H * (nope + rp + vd) * keys
            + 2 * m["hidden_size"] * m["vocab_size"])
