"""Compile rehearsals of the expert-FFN Pallas kernels for a TPU v5e that
is described, not attached: the chip's compiler refuses what interpret
mode accepts (scoped-VMEM overruns, untiled slices), so each variant on
the serving path is compiled at real widths here, at no chip time.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under several test workers a
module-level call would give the workers different test sets."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.expert_ffn.kernel import expert_ffn


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described device's executables cannot be read back from the
    # persistent cache without the chip: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _widths(arch):
    cfg = get_config(arch)
    return cfg.d_model, cfg.moe.d_expert, cfg.moe.n_routed, cfg.moe.top_k


def _capacity(arch, tokens):
    """Capacity bucket of a ``tokens``-token prefill (moe.expert_capacity
    at the config's capacity factor)."""
    from repro.models.moe import expert_capacity
    return expert_capacity(get_config(arch).moe, tokens)


# (arch, prefill tokens, expert-parallel devices for the grouped variant);
# DeepSeek-V2-Lite's f = 1408 = 11 x 128 leaves block_f = 128 alone
WIDTHS = [("mixtral-8x7b", 128, 4), ("qwen3-30b-a3b", 512, 4),
          ("deepseek-v2-lite-16b", 512, 4)]


@pytest.mark.parametrize("variant", ["dense", "ragged", "grouped"])
@pytest.mark.parametrize("arch,tokens,tp", WIDTHS)
def test_expert_ffn_compiles_for_v5e(one_chip, arch, tokens, tp, variant):
    d, f, E, _ = _widths(arch)
    C = _capacity(arch, tokens)

    def spec(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if variant == "grouped":
        # the expert-parallel receive side: this chip's E/tp weight sets,
        # one row group per (source device, local expert)
        E_loc = E // tp
        G = E_loc * tp
        args = (spec((G, C, d)), spec((E_loc, d, f)), spec((E_loc, d, f)),
                spec((E_loc, f, d)), spec((G,), jnp.int32),
                spec((G,), jnp.int32))

        def fn(xe, wg, wu, wd, counts, eids):
            return expert_ffn(xe, wg, wu, wd, counts=counts,
                              expert_ids=eids)
    else:
        args = (spec((E, C, d)), spec((E, d, f)), spec((E, d, f)),
                spec((E, f, d)))
        if variant == "ragged":
            args += (spec((E,), jnp.int32),)

        def fn(xe, wg, wu, wd, counts=None):
            return expert_ffn(xe, wg, wu, wd, counts=counts)

    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == args[0].shape and out.dtype == jnp.bfloat16


@pytest.mark.parametrize("batch,seq", [(24, 1), (1, 512)])
def test_resident_moe_reads_expert_stacks_in_place(one_chip, monkeypatch,
                                                   batch, seq):
    """A scanned resident MoE at Mixtral widths (4 layers; a 24-row
    decode, a 512-token prefill) hands the expert-FFN kernel the whole
    (L, E, ...) stacks: no (E, d, f) slice of them is written, and the
    kernel keeps the name the trace reader matches (bench/readers.py)."""
    import dataclasses
    import re

    from repro.models.model import apply_model, init_model
    cfg = get_config("mixtral-8x7b")
    cfg = cfg.replace(n_layers=4, moe=dataclasses.replace(
        cfg.moe, capacity_factor=0.0))
    shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    # the model's kernel branch asks the default backend, which here is
    # the CPU: the compile itself targets the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = jax.jit(lambda p, t: apply_model(
        p, t, cfg, last_logit_only=True)[0]).lower(params, toks) \
        .compile().as_text()
    d, f, E, _ = _widths("mixtral-8x7b")
    for sliced in (f"bf16[{E},{d},{f}]", f"bf16[{E},{f},{d}]"):
        assert sliced not in hlo
    kernels = re.findall(r"%(\S+) = [^\n]*custom_call_target="
                         r"\"tpu_custom_call\"", hlo)
    assert kernels
    assert all(re.fullmatch(r"expert_ffn(\.\d+)?", k) for k in kernels)
