"""Model-substrate correctness: MoE dispatch vs dense oracle, Mamba2 SSD
chunked vs sequential recurrence, blockwise vs dense attention (property
tests via hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.models.attention import _mha, _mha_blockwise
from repro.models.config import (MambaConfig, ModelConfig, MoEConfig,
                                 layer_pattern, scan_pattern)
from repro.models.mamba import apply_mamba, init_mamba, init_mamba_cache
from repro.models.moe import apply_moe, init_moe, route


# --------------------------------------------------------------------------
# MoE dispatch == dense oracle
# --------------------------------------------------------------------------

def _dense_moe_oracle(params, x, cfg):
    """Direct per-token expert evaluation (no dispatch machinery)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    m = cfg.moe
    gates, idx, _, _ = route(params, xf, m)
    y = np.zeros_like(np.asarray(xf), np.float32)
    g_np, i_np, x_np = map(np.asarray, (gates, idx, xf))
    wg, wu, wd = (np.asarray(params[k], np.float32)
                  for k in ("gate", "up", "down"))
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    for t in range(x_np.shape[0]):
        for j in range(m.top_k):
            e = int(i_np[t, j])
            h = np.asarray(act(x_np[t] @ wg[e])) * (x_np[t] @ wu[e])
            y[t] += g_np[t, j] * (h @ wd[e])
    if m.n_shared:
        from repro.models.layers import apply_mlp
        y += np.asarray(apply_mlp(params["shared"], xf, cfg),
                        np.float32)
    return y.reshape(B, S, d)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 100), st.sampled_from([(8, 2), (4, 1), (16, 4)]),
       st.booleans())
def test_moe_dispatch_matches_oracle(seed, ek, shared):
    E, K = ek
    cfg = ModelConfig(
        d_model=32, d_ff=64, vocab=64, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(n_routed=E, top_k=K, d_expert=48,
                      n_shared=1 if shared else 0, d_shared=48,
                      capacity_factor=0.0))   # full capacity: no drops
    key = jax.random.PRNGKey(seed)
    params = init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 5, 32))
    y, info = apply_moe(params, x, cfg)
    ref = _dense_moe_oracle(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)
    assert int(info["dropped"]) == 0
    # workload conservation: counts sum to T*K
    assert int(info["workload"].sum()) == 2 * 5 * K


def test_moe_capacity_drops_accounted():
    cfg = ModelConfig(d_model=16, d_ff=32, dtype="float32",
                      param_dtype="float32",
                      moe=MoEConfig(n_routed=4, top_k=2, d_expert=32,
                                    capacity_factor=0.26))
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
    y, info = apply_moe(params, x, cfg)
    assert np.isfinite(np.asarray(y)).all()
    # capacity 8 < average load 32 -> some drops must occur
    assert int(info["dropped"]) > 0


# --------------------------------------------------------------------------
# Mamba2: chunked SSD == token-by-token recurrence
# --------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 50), st.sampled_from([5, 8, 13]))
def test_ssd_chunked_equals_recurrent(seed, S):
    cfg = ModelConfig(
        d_model=32, d_ff=0, family="ssm", attn=None, dtype="float32",
        param_dtype="float32",
        mamba=MambaConfig(d_state=8, d_conv=3, expand=2, head_dim=16,
                          chunk_size=4))
    key = jax.random.PRNGKey(seed)
    params = init_mamba(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, S, 32)) * 0.5
    # full-sequence chunked
    y_full, _ = apply_mamba(params, x, cfg, cache=None)
    # token-by-token recurrent decode
    cache = init_mamba_cache(cfg, 2)
    ys = []
    for t in range(S):
        y_t, cache = apply_mamba(params, x[:, t:t + 1], cfg, cache)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_seq),
                               rtol=2e-3, atol=2e-3)


def test_ssd_prefill_then_decode_state_consistent():
    cfg = ModelConfig(d_model=32, d_ff=0, family="ssm", attn=None,
                      dtype="float32", param_dtype="float32",
                      mamba=MambaConfig(d_state=8, d_conv=3, expand=2,
                                        head_dim=16, chunk_size=4))
    params = init_mamba(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 32)) * 0.5
    cache = init_mamba_cache(cfg, 1)
    _, cache = apply_mamba(params, x[:, :8], cfg, cache)   # prefill
    y_dec, _ = apply_mamba(params, x[:, 8:9], cfg, cache)  # decode
    y_full, _ = apply_mamba(params, x, cfg, None)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_full[:, 8]),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# blockwise attention == dense softmax attention
# --------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([(8, 64), (64, 64), (1, 96)]),
       st.booleans(), st.sampled_from([0, 16]),
       st.sampled_from([0.0, 30.0]))
def test_blockwise_matches_dense(seed, sqk, causal, window, softcap):
    Sq, Sk = sqk
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, D = 2, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), jnp.float32)
    qp = jnp.arange(Sk - Sq, Sk)
    kp = jnp.arange(Sk)
    dense = _mha(q, k, v, qp, kp, causal=causal, window=window,
                 softcap=softcap, scale=0.25)
    blk = _mha_blockwise(q, k, v, qp, kp, causal=causal, window=window,
                         softcap=softcap, scale=0.25, block=32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(blk),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# layer patterns
# --------------------------------------------------------------------------

def test_scan_pattern_factorisation():
    for arch in ("jamba_1_5_large_398b", "gemma2_9b",
                 "llama_3_2_vision_11b", "deepseek_v2_lite_16b"):
        cfg = get_config(arch)
        prefix, period, n_super = scan_pattern(cfg)
        rebuilt = list(prefix) + list(period) * n_super
        assert tuple(rebuilt) == layer_pattern(cfg)


def test_jamba_pattern_ratios():
    cfg = get_config("jamba_1_5_large_398b")
    pat = layer_pattern(cfg)
    attn = sum(1 for m, _ in pat if m == "attn")
    mamba = sum(1 for m, _ in pat if m == "mamba")
    moe = sum(1 for _, ml in pat if ml == "moe")
    assert attn * 7 == mamba            # 1:7 interleave
    assert moe == cfg.n_layers // 2     # MoE every other layer


# --------------------------------------------------------------------------
# resident expert stacks: scan constants read in place by the kernel
# --------------------------------------------------------------------------

def _resident_model(arch, n_super=3):
    from repro.configs import make_smoke
    from repro.models.model import init_model
    cfg = make_smoke(get_config(arch))
    prefix, period, _ = scan_pattern(cfg)
    cfg = cfg.replace(n_layers=len(prefix) + n_super * len(period))
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _kernel_logits(arch, sliced):
    """Prefill then one decode step of ``apply_model`` with the MoE layers
    on the expert-FFN kernel (interpret mode, as the TPU branch takes it).
    ``sliced`` hands the kernel each layer's (E, ...) slice, as the layer
    scan did when it carried the stacks in its xs; otherwise the kernel
    reads the whole stacks at the layer index."""
    from repro.kernels.expert_ffn import ops as ffn_ops
    from repro.models.model import apply_model, init_caches
    cfg, params = _resident_model(arch)
    orig = ffn_ops.expert_ffn_op
    layered = []

    def op(xe, wg, wu, wd, layer=None, **kw):
        if sliced and layer is not None:
            wg, wu, wd = wg[layer], wu[layer], wd[layer]
            layer = None
        layered.append(layer is not None)
        return orig(xe, wg, wu, wd, layer=layer, interpret=True, **kw)

    B, S = 3, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    caches = init_caches(cfg, B, S + 4, dtype="float32")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setattr(ffn_ops, "expert_ffn_op", op)
        pre, caches, _ = apply_model(
            params, toks, cfg, positions=jnp.arange(S, dtype=jnp.int32),
            caches=caches)
        nxt = jnp.argmax(pre[:, -1:], -1).astype(jnp.int32)
        dec, caches, _ = apply_model(
            params, nxt, cfg, positions=jnp.full((B, 1), S, jnp.int32),
            caches=caches)
    return pre, dec, caches, layered


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite-16b"])
def test_stacked_expert_kernel_logits_bit_identical(arch):
    pre, dec, caches, layered = _kernel_logits(arch, False)
    pre_s, dec_s, caches_s, layered_s = _kernel_logits(arch, True)
    # every scanned MoE layer took the stacked path (prefill and decode)
    assert layered and all(layered) and not any(layered_s)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(pre_s))
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(dec_s))
    for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(caches_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resident_expert_stacks_are_scan_constants():
    """The layer scan takes the routed-expert stacks whole, as constants,
    and slices nothing of them through its xs; the attention and router
    leaves still ride the xs."""
    from repro.models.model import apply_model
    cfg, params = _resident_model("mixtral-8x7b")
    toks = jnp.zeros((2, 4), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p: apply_model(p, toks, cfg)[0])(params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    eqn = scans[0]
    n_c, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    consts = [v.aval.shape for v in eqn.invars[:n_c]]
    xs = [v.aval.shape for v in eqn.invars[n_c + n_carry:]]
    stack = params["scan"][0]["mlp"]
    for k in ("gate", "up", "down"):
        assert stack[k].shape in consts
        assert stack[k].shape not in xs
    assert stack["router"].shape in xs
