"""The Mixtral family (Hugging Face ``MixtralForCausalLM`` config keys):
the program's ``ModelConfig``, the layout of the seeded weights, the
program's parameter tree made from them, and the model FLOPs a token
needs.  Chosen by ``"reference": "mixtral"`` in a configuration file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

# leaf ids: part of the key, never renumber
LEAVES = {"embed": 0, "head": 1, "final_norm": 2, "attn_norm": 3, "wq": 4,
          "wk": 5, "wv": 6, "wo": 7, "ffn_norm": 8, "router": 9,
          "w_gate": 10, "w_up": 11, "w_down": 12}
LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "router")
EXPERT = ("w_gate", "w_up", "w_down")


def _dims(m):
    d, f = m["hidden_size"], m["intermediate_size"]
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // H
    return d, f, H, Hkv, hd


def shapes(m: dict) -> dict:
    """Shape and a_exp of every leaf."""
    d, f, H, Hkv, hd = _dims(m)
    V, E = m["vocab_size"], m["num_local_experts"]
    fan = W.fan_in_exp
    return {
        "embed": ((V, d), W.EMBED_EXP),
        "head": ((d, V), fan(d)),
        "final_norm": ((d,), W.NORM_EXP),
        "attn_norm": ((d,), W.NORM_EXP),
        "wq": ((d, H * hd), fan(d)),
        "wk": ((d, Hkv * hd), fan(d)),
        "wv": ((d, Hkv * hd), fan(d)),
        "wo": ((H * hd, d), fan(H * hd)),
        "ffn_norm": ((d,), W.NORM_EXP),
        "router": ((d, E), fan(d)),
        "w_gate": ((d, f), fan(d)),
        "w_up": ((d, f), fan(d)),
        "w_down": ((f, d), fan(f)),
    }


def model_config(m: dict):
    """The program's ModelConfig for a configuration file ``m``."""
    from repro.models.config import AttentionConfig, ModelConfig, MoEConfig
    dep = m["deployment"]
    d, f, H, Hkv, hd = _dims(m)
    return ModelConfig(
        name=m.get("name", "bench"), family="moe", source=m["source"],
        n_layers=m["num_hidden_layers"], d_model=d, d_ff=f,
        vocab=m["vocab_size"],
        attn=AttentionConfig(
            n_heads=H, n_kv_heads=Hkv, head_dim=hd,
            rope_theta=m["rope_theta"],
            sliding_window=m.get("sliding_window") or 0),
        moe=MoEConfig(n_routed=m["num_local_experts"],
                      top_k=m["num_experts_per_tok"], d_expert=f,
                      router_type="topk_softmax",
                      capacity_factor=dep["capacity_factor"]),
        norm="rmsnorm", act=m["hidden_act"], glu=True,
        tie_embeddings=m.get("tie_word_embeddings", False),
        dtype=m["torch_dtype"], param_dtype=m["torch_dtype"])


@functools.partial(jax.jit, static_argnums=0)
def params(mkey, lo, hi):
    """The program's parameter tree, every leaf from ``bench.weights``,
    made on the device in one call.  Expert stacks are written one
    (layer, expert) block at a time into their (L, E, ...) buffers."""
    m = dict(mkey)
    base = W.base_key(lo, hi)
    L, E = m["num_hidden_layers"], m["num_local_experts"]
    sh = shapes(m)

    def layers(name, dtype=jnp.bfloat16):
        return jnp.stack([W.make_leaf(base, m, name, l).astype(dtype)
                          for l in range(L)])

    def experts(name):
        shape, a = sh[name]

        def body(i, out):
            blk = W.leaf(base, i // E, LEAVES[name], i % E, shape, a)
            return jax.lax.dynamic_update_slice(
                out, blk[None, None], (i // E, i % E, 0, 0))

        return jax.lax.fori_loop(0, L * E, body,
                                 jnp.zeros((L, E) + shape, jnp.bfloat16))

    block = {
        "norm1": {"w": layers("attn_norm")},
        "mixer": {k: layers(k) for k in ("wq", "wk", "wv", "wo")},
        "norm2": {"w": layers("ffn_norm")},
        "mlp": {"router": layers("router", jnp.float32),
                "gate": experts("w_gate"), "up": experts("w_up"),
                "down": experts("w_down")},
    }
    return {"embed": {"tok": W.make_leaf(base, m, "embed"),
                      "head": W.make_leaf(base, m, "head")},
            "final_norm": {"w": W.make_leaf(base, m, "final_norm")},
            "prefix": (), "scan": (block,)}


def moe_dims(m: dict):
    """(d_model, d_expert, experts, top_k, layers)."""
    return (m["hidden_size"], m["intermediate_size"],
            m["num_local_experts"], m["num_experts_per_tok"],
            m["num_hidden_layers"])


def token_flops(m: dict, context: int, logits: bool = True) -> float:
    """Model FLOPs one token needs at sequence position ``context - 1``
    (it attends to ``context`` keys): attention projections, scores and
    values, router, its top-k experts, and the LM head when the token's
    logits are needed."""
    d, f, H, Hkv, hd = _dims(m)
    E, K, V = (m["num_local_experts"], m["num_experts_per_tok"],
               m["vocab_size"])
    per_layer = (2 * d * (H * hd + 2 * Hkv * hd) + 2 * H * hd * d
                 + 4 * H * hd * context + 2 * d * E + K * 6 * d * f)
    return m["num_hidden_layers"] * per_layer + (2 * d * V if logits else 0)


def prefill_flops(m: dict, length: int) -> float:
    """A prompt of ``length`` tokens: causal attention (token i sees i + 1
    keys) and the LM head at the last position only."""
    d, f, H, Hkv, hd = _dims(m)
    keys = length * (length + 1) // 2
    return (length * token_flops(m, 0, logits=False)
            + m["num_hidden_layers"] * 4 * H * hd * keys
            + 2 * d * m["vocab_size"])
