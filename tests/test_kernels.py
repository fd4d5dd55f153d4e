"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
shape/dtype sweeps per the deliverable."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.expert_ffn.kernel import expert_ffn
from repro.kernels.expert_ffn.ref import expert_ffn_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.gating.kernel import gating
from repro.kernels.gating.ref import gating_ref

RNG = np.random.default_rng(0)


def _tol(dt):
    return 3e-2 if dt == jnp.bfloat16 else 3e-5


@pytest.mark.parametrize("E,C,d,f", [
    (2, 128, 128, 256), (4, 256, 64, 512), (8, 128, 256, 1024),
    (1, 384, 128, 384),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_expert_ffn(E, C, d, f, dt, act):
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), dt)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, dt)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, dt)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.05, dt)
    y = expert_ffn(xe, wg, wu, wd, act=act, block_c=128, block_f=128,
                   interpret=True)
    r = expert_ffn_ref(xe, wg, wu, wd, act=act)
    scale = float(jnp.abs(r.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32)
                        - r.astype(jnp.float32)).max()) / scale
    assert err < _tol(dt), err


def _stacks(L, E, d, f, dt):
    w = lambda *shape: jnp.asarray(RNG.standard_normal(shape) * 0.05, dt)
    return w(L, E, d, f), w(L, E, d, f), w(L, E, f, d)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_expert_ffn_stacked_layer_bit_identical(layer, ragged, dt):
    """Reading layer ``layer`` in place from (L, E, ...) stacks computes
    bit for bit what the call on the layer's slice computes."""
    L, E, C, d, f = 3, 4, 40, 128, 256
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), dt)
    wg, wu, wd = _stacks(L, E, d, f, dt)
    # some experts empty, some partial blocks, one full
    counts = jnp.array([0, 17, C, 5], jnp.int32) if ragged else None
    kw = dict(counts=counts, block_c=16, block_f=128, interpret=True)
    sliced = expert_ffn(xe, wg[layer], wu[layer], wd[layer], **kw)
    stacked = expert_ffn(xe, wg, wu, wd, layer=jnp.int32(layer), **kw)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))


def test_expert_ffn_stacked_off_tile_f_raises():
    """The stacked path never pads (copies) the stacks: f = 200 is off the
    bf16 sublane tile of 16, so the kernel refuses it and the op hands
    the kernel the layer's slice instead."""
    from repro.kernels.expert_ffn.ops import expert_ffn_op
    wg, wu, wd = _stacks(2, 2, 128, 200, jnp.bfloat16)
    xe = jnp.asarray(RNG.standard_normal((2, 16, 128)), jnp.bfloat16)
    with pytest.raises(ValueError, match="sublane"):
        expert_ffn(xe, wg, wu, wd, layer=jnp.int32(1), interpret=True)
    kw = dict(force_kernel=True, interpret=True)
    y = expert_ffn_op(xe, wg, wu, wd, layer=jnp.int32(1), **kw)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(expert_ffn_op(xe, wg[1], wu[1], wd[1], **kw)))


@pytest.mark.parametrize("layer", [0, 2])
def test_expert_ffn_op_stacked_grad(layer):
    """The op's backward through a stacked call is the sliced call's
    gradient, placed at ``layer`` and zero at every other layer."""
    import jax
    from repro.kernels.expert_ffn.ops import expert_ffn_op
    L, E, C, d, f = 3, 2, 16, 128, 128
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), jnp.float32)
    ws = _stacks(L, E, d, f, jnp.float32)
    counts = jnp.array([16, 9], jnp.int32)

    def loss(x, *w, layer=None):
        y = expert_ffn_op(x, *w, counts=counts, layer=layer,
                          force_kernel=True, interpret=True)
        return jnp.sum(y ** 2)

    g = jax.grad(lambda x, *w: loss(x, *w, layer=jnp.int32(layer)),
                 argnums=(0, 1, 2, 3))(xe, *ws)
    g_s = jax.grad(loss, argnums=(0, 1, 2, 3))(
        xe, *(w[layer] for w in ws))
    np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(g_s[0]))
    for gw, gw_s in zip(g[1:], g_s[1:]):
        np.testing.assert_array_equal(np.asarray(gw[layer]),
                                      np.asarray(gw_s))
        others = np.delete(np.asarray(gw), layer, axis=0)
        assert not others.any()


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal,window,cap", [
    (1, 128, 128, 4, 2, 64, True, 0, 0.0),
    (2, 128, 256, 8, 8, 32, True, 0, 50.0),
    (1, 64, 192, 4, 1, 64, True, 64, 0.0),
    (2, 128, 128, 2, 2, 128, False, 0, 0.0),
    (1, 256, 256, 16, 2, 64, True, 0, 30.0),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Sk, Hq, Hkv, D, causal, window, cap, dt):
    q = jnp.asarray(RNG.standard_normal((B, Sq, Hq, D)), dt)
    k = jnp.asarray(RNG.standard_normal((B, Sk, Hkv, D)), dt)
    v = jnp.asarray(RNG.standard_normal((B, Sk, Hkv, D)), dt)
    o = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                        block_q=64, block_k=64, interpret=True)
    r = flash_attention_ref(q, k, v, causal=causal, window=window,
                            softcap=cap)
    err = float(jnp.abs(o.astype(jnp.float32)
                        - r.astype(jnp.float32)).max())
    assert err < _tol(dt), err


@pytest.mark.parametrize("T,E,k,rt,renorm", [
    (128, 8, 2, "topk_softmax", True),       # Mixtral router
    (256, 64, 6, "softmax_topk", True),      # DeepSeek router
    (64, 128, 1, "sigmoid", False),          # Llama4 router
    (100, 16, 4, "softmax_topk", False),     # padded T
    (512, 128, 8, "softmax_topk", True),     # Qwen3-30B router
])
def test_gating(T, E, k, rt, renorm):
    lg = jnp.asarray(RNG.standard_normal((T, E)) * 2, jnp.float32)
    g1, i1 = gating(lg, k, router_type=rt, renormalize=renorm,
                    block_t=64, interpret=True)
    g2, i2 = gating_ref(lg, k, router_type=rt, renormalize=renorm)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)
