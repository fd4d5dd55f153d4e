"""Topology-aware EP resilience (DESIGN.md §13): greedy expert
placement against the per-link topology, analytic per-pair demand
accounting, the EPResilience degrade -> re-route -> heal -> restore
cycle, and (in a forced-8-device subprocess) the bit-exact placed
exchange contract of models/moe_ep.py."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.cost_model import LinkTopology
from repro.models.moe_ep import (placement_pair_bytes, solve_placement)


def _zipf_demand(n_dev=4, E=16, a=1.2):
    per_e = (1000 / np.arange(1, E + 1) ** a).astype(np.int64)
    return np.tile(per_e, (n_dev, 1))


def test_solve_placement_identity_under_homogeneous():
    topo = LinkTopology.homogeneous(4, 10.0, 1e-4)
    p = solve_placement(_zipf_demand(), topo)
    assert np.array_equal(p, np.arange(16))
    # (E,) demand accepted too, tp override works
    p = solve_placement(_zipf_demand()[0], topo, tp=4)
    assert np.array_equal(p, np.arange(16))


def test_solve_placement_moves_hot_experts_off_degraded_link():
    """The acceptance regression: per-link calibration demonstrably
    changes placement vs the homogeneous model."""
    topo = LinkTopology.homogeneous(4, 10.0, 1e-4)
    bad = topo.degrade(0, 3, 8.0).degrade(3, 0, 8.0)
    demand = _zipf_demand()
    p = solve_placement(demand, bad)
    assert not np.array_equal(p, np.arange(16))
    assert np.array_equal(np.sort(p), np.arange(16))    # a permutation
    # devices 0 and 3 share the bad link: the hottest expert groups land
    # on the well-connected devices 1 and 2
    per_e = demand.sum(0)
    e_loc = 4
    load = [per_e[p[k * e_loc:(k + 1) * e_loc]].sum() for k in range(4)]
    assert max(load[0], load[3]) <= min(load[1], load[2])
    # same demand, healthy fabric -> identity: the placement difference
    # is driven purely by the per-link constants
    assert np.array_equal(solve_placement(demand, topo), np.arange(16))


def test_solve_placement_validates():
    topo = LinkTopology.homogeneous(3, 10.0, 1e-4)
    with pytest.raises(ValueError):
        solve_placement(_zipf_demand(3, 16), topo)      # 16 % 3 != 0


def test_placement_pair_bytes_accounting():
    topo = LinkTopology.homogeneous(4, 10.0, 1e-4)
    E, d_model, itemsize = 16, 8, 4
    demand = np.zeros((4, E), np.int64)
    demand[:, 0] = 10                  # every device routes to expert 0
    ident = np.arange(E)
    pb = placement_pair_bytes(demand, ident, d_model, itemsize)
    assert pb.shape == (4, 4)
    assert np.array_equal(pb, pb.T)    # dispatch + symmetric return
    # expert 0 lives on device 0: each other device ships 10 rows there
    # (1>0 carries the dispatch, 0>1 the symmetric return)
    row = 10 * d_model * itemsize
    assert pb[1, 0] == row and pb[0, 1] == row and pb[2, 3] == 0
    assert np.all(np.diag(pb) == 0)    # local rows never cross a link
    # re-route expert 0 to device 3's slots: traffic follows it
    perm = ident.copy()
    perm[[0, 12]] = perm[[12, 0]]
    pb2 = placement_pair_bytes(demand, perm, d_model, itemsize)
    assert pb2[1, 3] == row and pb2[1, 0] == 0
    # a degraded 0<->3 fabric plus zipf demand: the solver's placement
    # carries less traffic over the bad pair than identity
    bad = topo.degrade(0, 3, 8.0).degrade(3, 0, 8.0)
    zd = _zipf_demand()
    p = solve_placement(zd, bad)
    before = placement_pair_bytes(zd, np.arange(E), d_model, itemsize)
    after = placement_pair_bytes(zd, p, d_model, itemsize)
    assert after[0, 3] < before[0, 3]


def test_ep_resilience_cycle():
    """degrade -> re-route -> heal -> restore, with the wall clock
    charged only while the fault is live."""
    from repro.serving.ep_resilience import EPResilience
    topo = LinkTopology.homogeneous(4, 10.0, 1e-5)
    ctrl = EPResilience(topo, n_experts=16, d_model=8, itemsize=4,
                        faults="link_degrade[0>3]:x8@5-14", seed=0)
    demand = _zipf_demand()
    placements = []
    for _ in range(24):
        rep = ctrl.step(demand)
        placements.append(rep["placement"])
    ident = np.arange(16)
    assert np.array_equal(placements[3], ident)         # healthy prefix
    kinds = [(frm, to) for _, _, frm, to in ctrl.events]
    assert ("healthy", "degraded") in kinds
    assert ("degraded", "healthy") in kinds
    assert ctrl.reroutes == 2                           # out and back
    moved = [t for t, p in enumerate(placements)
             if not np.array_equal(p, ident)]
    assert moved and 5 <= moved[0] < 14                 # inside the fault
    assert np.array_equal(placements[-1], ident)        # restored
    assert ctrl.slept_s > 0.0
    rep = ctrl.link_report()
    assert rep["0>3"]["degrade_events"] == 1
    assert rep["0>3"]["state"] == "healthy"
    assert all(r["degrade_events"] == 0
               for n, r in rep.items() if n != "0>3")
    full = ctrl.report()
    assert full["reroutes"] == 2 and full["degraded_pairs"] == []


def test_ep_resilience_no_reroute_baseline_detects_only():
    from repro.serving.ep_resilience import EPResilience
    topo = LinkTopology.homogeneous(4, 10.0, 1e-5)
    ctrl = EPResilience(topo, n_experts=16, d_model=8, itemsize=4,
                        faults="link_degrade[0>3]:x8@5-14", seed=0,
                        reroute=False)
    for _ in range(16):
        rep = ctrl.step(_zipf_demand())
        assert np.array_equal(rep["placement"], np.arange(16))
    assert ctrl.reroutes == 0
    assert any(to == "degraded" for _, _, _, to in ctrl.events)


def test_ep_resilience_validates_demand_shape():
    from repro.serving.ep_resilience import EPResilience
    topo = LinkTopology.homogeneous(4, 10.0, 1e-5)
    ctrl = EPResilience(topo, n_experts=16, d_model=8, itemsize=4)
    with pytest.raises(ValueError, match="demand"):
        ctrl.step(np.zeros((3, 16)))
    with pytest.raises(ValueError, match="divide"):
        EPResilience(topo, n_experts=15, d_model=8, itemsize=4)


SCRIPT = textwrap.dedent("""
    import os
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import sys; sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.ep_serve import build_model, zipf_request, E
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models.moe_ep import apply_moe_ep, permute_expert_params
    cfg, params = build_model()
    mesh = make_mesh((1, 8), ('data', 'model'))
    dt = jnp.dtype(cfg.dtype)
    x = zipf_request(4, 160, dt, 11)
    lmap = shd.logical_map_for(cfg, 'prefill_32k', mesh)
    perm = np.random.default_rng(3).permutation(E).astype(np.int32)
    with mesh, shd.rules(mesh, lmap, 'tp'):
        plain = jax.jit(lambda p, x: apply_moe_ep(p, x, cfg))
        f = jax.jit(lambda p, x, pm: apply_moe_ep(
            p, x, cfg, placement=pm, demand_view=True))
        y0 = np.asarray(plain(params, x)[0])
        # identity placement: bit-equal to the plain path, repeatable
        ident = jnp.arange(E, dtype=jnp.int32)
        a, ia = f(params, x, ident)
        b, _ = f(params, x, ident)
        assert np.array_equal(np.asarray(a), np.asarray(b)), 'not repeatable'
        assert np.array_equal(np.asarray(a), y0), 'identity != plain'
        # a real permutation with pre-permuted weights: same bits (the
        # re-route contract -- placement only moves WHERE experts run)
        pp = permute_expert_params(params, perm)
        c, ic = f(pp, x, jnp.asarray(perm))
        assert np.array_equal(np.asarray(c), y0), 'placed != plain'
        # the demand view is the (tp, E) capped-count gather and is
        # placement-invariant (it reports LOGICAL expert demand)
        dv = np.asarray(ia['ep_counts'])
        assert dv.shape == (8, E)
        assert np.array_equal(np.asarray(ic['ep_counts']), dv)
        # jaxpr census: the placed exchange adds gathers, NOT callbacks
        jxp = jax.make_jaxpr(
            lambda p, x, pm: apply_moe_ep(p, x, cfg, placement=pm,
                                          demand_view=True))(
            params, x, jnp.asarray(perm))
        assert 'callback' not in str(jxp), 'callback in placed EP graph'
    print('EP_RESILIENCE_OK')
""")


def test_placed_exchange_bit_exact_subprocess():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT, src],
                       capture_output=True, text=True, timeout=900)
    assert "EP_RESILIENCE_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
