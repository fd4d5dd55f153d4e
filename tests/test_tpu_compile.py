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


# (arch, prefill tokens, expert-parallel devices for the grouped variant)
WIDTHS = [("mixtral-8x7b", 128, 4), ("qwen3-30b-a3b", 512, 4)]


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
