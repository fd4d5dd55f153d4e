"""OffloadPolicy API: bit-identity with the pre-refactor engine,
NumPy-vs-JAX parity per registered policy, retrace stability, and
construction-time validation (DESIGN.md §7)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import DaliConfig, dali_schedule, init_dali_state
from repro.core.policy import (POLICY_COMPOSITIONS, Observation, make_policy,
                               policy_names)

L, E, T, D = 3, 8, 6, 16
TEL_KEYS = ("on_gpu", "on_cpu", "T_cpu", "T_gpu", "hits", "misses",
            "swaps", "prefetched", "pf_pred", "link_seconds",
            "step_moe_time")
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "dali_schedule_fixture.npz")


def _dcfg(**kw):
    base = dict(n_moe_layers=L, n_experts=E, cache_size=3,
                prefetch_size=2, w_size=2, u_size=1)
    base.update(kw)
    return DaliConfig(**base)


def _fixture_prng():
    """The fixture's initial cache was drawn with JAX's original threefry
    bit layout; JAX 0.5 made ``jax_threefry_partitionable`` the default,
    which draws different permutations from the same key.  Seed the
    initial state under the recorded layout so the decisions are checked
    against the recording itself."""
    return jax.threefry_partitionable(False)


def _fixture_trace():
    """The exact deterministic trace the pre-refactor fixture was recorded
    on (seed 42; steps >= 4 carry a live-token mask)."""
    rng = np.random.default_rng(42)
    routers = jnp.asarray(rng.standard_normal((L, D, E)), jnp.float32) * 0.3
    res_vecs = jnp.asarray(rng.standard_normal((L, D)), jnp.float32) * 0.1
    steps = []
    for step in range(8):
        wl = jnp.asarray(rng.integers(0, 5, (L, E)), jnp.int32)
        gi = jnp.asarray(rng.standard_normal((L, T, D)), jnp.float32)
        mask = jnp.asarray(np.arange(T) < 4) if step >= 4 else None
        steps.append((wl, gi, mask))
    return routers, res_vecs, steps


# --------------------------------------------------------------------------
# (a) bit-identity with the pre-refactor dali_schedule
# --------------------------------------------------------------------------

def test_dali_policy_bit_identical_to_prerefactor_fixture():
    """tests/data/dali_schedule_fixture.npz was recorded by running the
    PRE-refactor monolithic ``dali_schedule`` on this trace; the jitted
    "dali" policy must reproduce every telemetry array and the final
    state bit-for-bit."""
    fx = np.load(FIXTURE)
    dcfg = _dcfg()
    routers, res_vecs, steps = _fixture_trace()
    pol = make_policy("dali", dcfg, top_k=2)
    with _fixture_prng():
        state = pol.init()
    step_fn = jax.jit(pol.step)
    for i, (wl, gi, mask) in enumerate(steps):
        state, dec = step_fn(state, wl,
                             Observation(gi, routers, res_vecs, mask))
        for k in TEL_KEYS:
            np.testing.assert_array_equal(
                np.asarray(dec.tel[k]), fx[f"step{i}_{k}"],
                err_msg=f"step {i} tel[{k}]")
    np.testing.assert_array_equal(np.asarray(state["resident"]),
                                  fx["final_resident"])
    np.testing.assert_array_equal(np.asarray(state["cache"]["scores"]),
                                  fx["final_scores"])
    assert int(state["tick"]) == int(fx["final_tick"])
    for k in ("steps", "moe_time", "link_time", "hits", "misses", "swaps"):
        np.testing.assert_array_equal(np.asarray(state["acc"][k]),
                                      fx[f"final_acc_{k}"])


def test_compat_wrapper_matches_fixture():
    """``engine.dali_schedule`` (now a wrapper over the policy API) keeps
    the legacy flat state layout AND the recorded numerics."""
    fx = np.load(FIXTURE)
    dcfg = _dcfg()
    routers, res_vecs, steps = _fixture_trace()
    with _fixture_prng():
        state = init_dali_state(dcfg)
    for i, (wl, gi, mask) in enumerate(steps):
        state, tel = dali_schedule(state, wl, gi, routers, res_vecs, dcfg,
                                   top_k=2, token_mask=mask)
        np.testing.assert_array_equal(np.asarray(tel["on_gpu"]),
                                      fx[f"step{i}_on_gpu"])
    np.testing.assert_array_equal(np.asarray(state["resident"]),
                                  fx["final_resident"])
    np.testing.assert_array_equal(np.asarray(state["scores"]),
                                  fx["final_scores"])


# --------------------------------------------------------------------------
# (b) NumPy-vs-JAX parity per registered policy
# --------------------------------------------------------------------------

def _parity_trace(kind: str, n_steps: int = 9, seed: int = 1):
    """Zipf-skewed or uniform per-expert workloads + gaussian features."""
    rng = np.random.default_rng(seed)
    routers = rng.standard_normal((L, D, E)).astype(np.float32) * 0.3
    res_vecs = rng.standard_normal((L, D)).astype(np.float32) * 0.1
    steps = []
    for _ in range(n_steps):
        if kind == "zipf":
            # T*K token slots drawn Zipf(1.5) over experts -> skewed counts
            draws = np.minimum(rng.zipf(1.5, (L, T * 2)) - 1, E - 1)
            wl = np.stack([np.bincount(d, minlength=E) for d in draws])
        else:
            wl = rng.integers(0, 5, (L, E))
        gi = rng.standard_normal((L, T, D)).astype(np.float32)
        steps.append((wl.astype(np.int32), gi))
    return routers, res_vecs, steps


EXACT_KEYS = ("on_gpu", "on_cpu", "hits", "misses", "swaps", "prefetched")


@pytest.mark.parametrize("kind", ["zipf", "uniform"])
@pytest.mark.parametrize("name", sorted(POLICY_COMPOSITIONS))
def test_numpy_jax_parity(name, kind):
    dcfg = _dcfg()
    pol = make_policy(name, dcfg, top_k=2)
    routers, res_vecs, steps = _parity_trace(kind)
    sj = pol.init()
    sn = pol.init_np()
    step_j = jax.jit(pol.step)
    for wl, gi in steps:
        obs_j = Observation(jnp.asarray(gi), jnp.asarray(routers),
                            jnp.asarray(res_vecs))
        obs_n = Observation(gi, routers, res_vecs)
        sj, dj = step_j(sj, jnp.asarray(wl), obs_j)
        sn, dn = pol.step_np(sn, wl, obs_n)
        if name == "random":
            # the NumPy mirror draws from its own generator: check the
            # structural invariants rather than the exact sets
            for dec in (dj, dn):
                pf = np.asarray(dec.prefetch_set)
                assert not pf[0].any()
                assert (pf.sum(-1) <= dcfg.prefetch_size).all()
            continue
        for k in EXACT_KEYS:
            np.testing.assert_array_equal(
                np.asarray(dj.tel[k]), np.asarray(dn.tel[k]),
                err_msg=f"{name}/{kind} tel[{k}]")
        np.testing.assert_array_equal(np.asarray(sj["resident"]),
                                      sn["resident"])
        np.testing.assert_allclose(np.asarray(dj.tel["T_cpu"]),
                                   dn.tel["T_cpu"], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(dj.tel["T_gpu"]),
                                   dn.tel["T_gpu"], rtol=1e-5)


# --------------------------------------------------------------------------
# (c) stable state pytree: one compile across steps, per policy
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", policy_names())
def test_state_pytree_stable_no_retrace(name):
    dcfg = _dcfg()
    pol = make_policy(name, dcfg, top_k=2)
    routers, res_vecs, steps = _parity_trace("uniform", n_steps=6, seed=3)
    compiles = []

    @jax.jit
    def step_fn(state, wl, obs):
        compiles.append(1)           # appended once per (re)trace
        return pol.step(state, wl, obs)

    state = pol.init()
    struct = jax.tree_util.tree_structure(state)
    for wl, gi in steps:
        obs = Observation(jnp.asarray(gi), jnp.asarray(routers),
                          jnp.asarray(res_vecs))
        state, _ = step_fn(state, jnp.asarray(wl), obs)
        assert jax.tree_util.tree_structure(state) == struct
    assert len(compiles) == 1, f"{name} retraced {len(compiles)}x"


# --------------------------------------------------------------------------
# construction-time validation (same style as force_path/force_exchange)
# --------------------------------------------------------------------------

def test_unknown_policy_name_lists_registry():
    with pytest.raises(ValueError, match="dali") as ei:
        make_policy("bogus")
    assert "none" in str(ei.value) and "'bogus'" in str(ei.value)


def test_unknown_sub_policy_lists_registry():
    with pytest.raises(ValueError, match="workload") as ei:
        make_policy("dali", _dcfg(), top_k=2, cache="bogus")
    assert "lru" in str(ei.value)
    with pytest.raises(ValueError, match="residual"):
        make_policy("dali", _dcfg(), top_k=2, prefetch="bogus")
    with pytest.raises(ValueError, match="greedy"):
        make_policy("dali", _dcfg(), top_k=2, assignment="bogus")


def test_server_validates_policy_at_construction():
    from repro.configs import get_config, make_smoke
    from repro.serving.scheduler import ContinuousBatchServer
    cfg = make_smoke(get_config("mixtral_8x7b")).replace(n_layers=2)
    with pytest.raises(ValueError, match="policy must be one of"):
        ContinuousBatchServer(None, cfg, batch_size=2, max_len=32,
                              policy="bogus")


# --------------------------------------------------------------------------
# simulator replay consumes the same registry
# --------------------------------------------------------------------------

def test_simulate_policy_dali_beats_none():
    from repro.configs import get_config, make_smoke
    from repro.core.cost_model import CostModel, LOCAL_PC
    from repro.core.simulator import simulate_policy
    from test_simulator import _toy_trace  # tests dir on sys.path (conftest)
    cfg = make_smoke(get_config("mixtral_8x7b")).replace(n_layers=4)
    cm = CostModel.for_config(get_config("mixtral_8x7b"), LOCAL_PC)
    tr = _toy_trace(cfg)
    rs = {name: simulate_policy(tr, cfg, cm, name, batch=8)
          for name in ("dali", "none", "all_gpu")}
    assert rs["dali"].tokens_per_s > rs["none"].tokens_per_s
    assert rs["dali"].tokens_per_s >= rs["all_gpu"].tokens_per_s
    assert 0.0 <= rs["dali"].cache_hit_rate <= 1.0
    # an already-built NullPolicy OBJECT replays like the "none" string
    r_obj = simulate_policy(tr, cfg, cm, make_policy("none"), batch=8)
    assert r_obj.tokens_per_s == pytest.approx(rs["none"].tokens_per_s)
