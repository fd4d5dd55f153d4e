"""YaRN rope scaling and DeepSeek-V2-Lite's router, against direct
transcriptions of the published formulas (Hugging Face
``DeepseekV2YarnRotaryEmbedding`` and ``MoEGate``; arXiv:2405.04434), and
the guarantee that a model without the field computes what it did."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_smoke
from repro.models.attention import mla_attention
from repro.models.config import YarnConfig
from repro.models.layers import (rope_table, score_divisor,
                                 yarn_correction_range, yarn_inv_freq,
                                 yarn_mscale)
from repro.models.model import apply_model, init_caches, init_model
from repro.models.moe import route

DSV2 = get_config("deepseek_v2_lite_16b")


def _published_inv_freq(dim, base, factor, original_max, beta_fast,
                        beta_slow):
    """``_set_cos_sin_cache`` of DeepseekV2YarnRotaryEmbedding, line by
    line (float64)."""
    def find_dim(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    lin = (np.arange(dim // 2, dtype=np.float64) - low) / (
        (high + 0.001 if low == high else high) - low)
    inv_freq_mask = 1.0 - np.clip(lin, 0, 1)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask), low, high


def test_published_config_carries_yarn_and_no_renormalisation():
    a, m = DSV2.attn, DSV2.moe
    assert a.yarn == YarnConfig(factor=40.0,
                                original_max_position_embeddings=4096,
                                beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                                mscale_all_dim=0.707)
    assert not m.renormalize and m.router_type == "softmax_topk"
    assert (m.n_routed, m.top_k, m.n_shared, m.d_shared, m.first_dense) == \
        (64, 6, 2, 2816, 1)
    # the CPU smoke variant keeps the rope scaling
    assert make_smoke(DSV2).attn.yarn == a.yarn


def test_yarn_correction_range_and_scale_at_published_widths():
    y, rp = DSV2.attn.yarn, DSV2.attn.mla.qk_rope_head_dim
    assert yarn_correction_range(y, rp, DSV2.attn.rope_theta) == (10, 23)
    assert yarn_mscale(y.factor, y.mscale_all_dim) ** 2 == \
        pytest.approx(1.58963, abs=1e-5)
    qk = DSV2.attn.head_dim_of(DSV2.d_model)
    assert qk == 192
    assert 1.0 / score_divisor(y, qk) == pytest.approx(0.114721, abs=1e-6)
    assert 1.0 / score_divisor(None, qk) == pytest.approx(0.072169, abs=1e-6)


@pytest.mark.parametrize("dim,factor,original", [(64, 40.0, 4096),
                                                 (16, 40.0, 4096),
                                                 (64, 4.0, 512)])
def test_yarn_table_matches_the_published_formulas(dim, factor, original):
    y = YarnConfig(factor=factor, original_max_position_embeddings=original,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)
    want, low, high = _published_inv_freq(dim, 10000.0, factor, original,
                                          32.0, 1.0)
    assert yarn_correction_range(y, dim, 10000.0) == (low, high)
    np.testing.assert_allclose(yarn_inv_freq(y, dim, 10000.0), want,
                               rtol=1e-12)
    # unscaled below the range, divided by the factor above it
    plain = 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim)
    np.testing.assert_array_equal(want[:low], plain[:low])
    np.testing.assert_allclose(want[high:], plain[high:] / factor,
                               rtol=1e-12)
    pos = jnp.array([0, 1, 7, 4095, 70000], jnp.int32)
    cos, sin = rope_table(pos, dim, 10000.0, y)
    ang = np.asarray(pos, np.float32)[:, None] * want.astype(np.float32)
    mag = (yarn_mscale(factor, 0.707) / yarn_mscale(factor, 0.707))
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * mag,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * mag,
                               atol=2e-6)


def test_rope_without_yarn_is_the_plain_table():
    pos = jnp.arange(300, dtype=jnp.int32)
    cos, sin = rope_table(pos, 128, 1e6)
    inv = 1.0 / (1e6 ** (np.arange(0, 128, 2) / 128))
    ang = pos[..., None].astype(jnp.float32) * inv[None, :]
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(jnp.cos(ang)))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(jnp.sin(ang)))
    assert score_divisor(None, 128) == np.sqrt(128)


def test_router_gates_are_the_unrenormalised_top6_softmax():
    m = DSV2.moe
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (10, 32), jnp.float32)
    params = {"router": jax.random.normal(k2, (32, m.n_routed)) * 0.5}
    gates, idx, probs, logits = route(params, x, m)
    p = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    want_idx = np.argsort(-p, axis=-1)[:, :m.top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    np.testing.assert_allclose(np.asarray(gates),
                               np.take_along_axis(p, np.asarray(idx), -1),
                               rtol=1e-6)
    assert (np.asarray(gates).sum(-1) < 0.999).all()


def _smoke_mla(yarn):
    cfg = make_smoke(DSV2)
    return cfg.replace(attn=dataclasses.replace(cfg.attn, yarn=yarn))


def test_mla_decode_paths_agree_under_yarn():
    """Prefill then one decode step through the latent cache: the
    absorbed and the decompressed decode give the same output, each with
    YaRN's softmax scale."""
    cfg = _smoke_mla(DSV2.attn.yarn)
    params = init_model(jax.random.PRNGKey(0), cfg)["prefix"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    cache = init_caches(cfg, 2, 16)["prefix"][0]
    _, cache = mla_attention(params, x[:, :8], cfg,
                             positions=jnp.arange(8), cache=cache)
    outs = []
    for absorbed in (True, False):
        c = cfg.replace(attn=dataclasses.replace(
            cfg.attn, mla=dataclasses.replace(cfg.attn.mla,
                                              absorbed_decode=absorbed)))
        y, _ = mla_attention(params, x[:, 8:], c,
                             positions=jnp.array([8]), cache=cache)
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4, rtol=1e-4)
    full, _ = mla_attention(params, x, cfg, positions=jnp.arange(9))
    np.testing.assert_allclose(outs[0], np.asarray(full[:, 8:]), atol=1e-4,
                               rtol=1e-4)


def test_yarn_changes_what_the_model_computes():
    """The field is not inert: the same weights give other logits with
    and without it (the parent's rope computed the second)."""
    cfg = _smoke_mla(DSV2.attn.yarn)
    params = init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 3, cfg.vocab)
    a = apply_model(params, toks, cfg)[0]
    b = apply_model(params, toks, _smoke_mla(None))[0]
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-2
