"""Workload-aware sparse expert execution (DESIGN.md §4): decode fast
path vs dense capacity-bucket dispatch, skip-empty ragged kernel vs its
oracle, static path selection, and the chunked ragged-tail fix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.moe as moe
from repro.configs import get_config, make_smoke
from repro.kernels.expert_ffn.kernel import expert_ffn
from repro.kernels.expert_ffn.ref import expert_ffn_ragged_ref, expert_ffn_ref
from repro.models.config import ModelConfig, MoEConfig
from repro.models.model import init_model
from repro.models.moe import apply_moe, init_moe, use_sparse_path

RNG = np.random.default_rng(0)


def _moe_cfg(E, K, shared=0, router="softmax_topk"):
    return ModelConfig(
        d_model=32, d_ff=64, vocab=64, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(n_routed=E, top_k=K, d_expert=48,
                      n_shared=shared, d_shared=48, router_type=router))


# --------------------------------------------------------------------------
# decode fast path == dense dispatch
# --------------------------------------------------------------------------

@pytest.mark.parametrize("E,K,shared", [(8, 2, 0), (64, 6, 0), (16, 2, 1),
                                        (128, 1, 0)])
@pytest.mark.parametrize("router", ["softmax_topk", "topk_softmax",
                                    "sigmoid"])
def test_sparse_path_matches_dense(E, K, shared, router):
    """Same routing, same logits, same observables — the fast path only
    changes how the activated experts are computed.  Dense runs at full
    capacity (T) so neither path drops."""
    cfg = _moe_cfg(E, K, shared, router)
    params = init_moe(jax.random.PRNGKey(E + K), cfg)
    B, T = 4, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, 32))
    ys, i_s = apply_moe(params, x, cfg, force_path="sparse")
    yd, i_d = apply_moe(params, x, cfg, force_path="dense", capacity=T)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yd),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(i_s["workload"]),
                                  np.asarray(i_d["workload"]))
    np.testing.assert_array_equal(np.asarray(i_s["topk_idx"]),
                                  np.asarray(i_d["topk_idx"]))
    np.testing.assert_allclose(float(i_s["aux_loss"]),
                               float(i_d["aux_loss"]), rtol=1e-5)
    assert int(i_s["dropped"]) == int(i_d["dropped"]) == 0


def test_sparse_path_never_drops_under_skew():
    """All T*K slots on ONE expert: the dense bucket (capacity floor 4)
    drops the overflow; the fast path computes every slot."""
    cfg = _moe_cfg(64, 2)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    # identical tokens route identically -> one expert gets all 2*8 slots
    x = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 32)),
                         (8, 1, 32))
    _, i_d = apply_moe(params, x, cfg, force_path="dense")
    _, i_s = apply_moe(params, x, cfg, force_path="sparse")
    assert int(i_d["dropped"]) > 0          # bucket overflow on dense
    assert int(i_s["dropped"]) == 0         # no buckets, no drops
    assert int(i_s["workload"].max()) == 8  # true workload still reported


def test_path_selection_is_static_and_shape_driven():
    m = MoEConfig(n_routed=64, top_k=2)
    assert use_sparse_path(m, n_tokens=4, capacity=None)        # decode
    assert not use_sparse_path(m, n_tokens=4096, capacity=None)  # prefill
    assert not use_sparse_path(m, n_tokens=4, capacity=8)  # pinned capacity
    # gather overhead: small expert pools need a real row advantage
    # (measured break-even, benchmarks/moe_dispatch.py: E=8 B=4 favors
    # dense, B=1 favors sparse)
    m8 = MoEConfig(n_routed=8, top_k=2)
    assert use_sparse_path(m8, n_tokens=1, capacity=None)
    assert not use_sparse_path(m8, n_tokens=4, capacity=None)
    cfg = _moe_cfg(64, 2)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 32))
    y_auto, _ = apply_moe(params, x, cfg)                 # auto -> sparse
    y_sparse, _ = apply_moe(params, x, cfg, force_path="sparse")
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_sparse))


def test_decode_step_fast_path_matches_dense_per_slot_masked():
    """Full serving decode step, per-slot layout with retired slots: the
    auto-selected fast path must match a dense decode step (capacity
    pinned at B, so dense cannot drop) on logits, sampled tokens and
    masked workloads."""
    from repro.models.model import init_caches
    from repro.serving.steps import (default_dali_config, init_serve_state,
                                     make_admit_prefill, make_admit_step,
                                     make_decode_step)
    import dataclasses
    cfg = make_smoke(get_config("mixtral_8x7b")).replace(n_layers=4)
    # smoke configs cap at 4 experts, below the sparse break-even at B=3;
    # widen the expert pool so the auto rule picks the fast path
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=16))
    params = init_model(jax.random.PRNGKey(0), cfg)
    dcfg = default_dali_config(cfg, cache_ratio=0.5)
    B, S, max_len = 3, 8, 32
    assert use_sparse_path(cfg.moe, B, None)
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    admit_prefill = jax.jit(make_admit_prefill(cfg))
    admit = jax.jit(make_admit_step(cfg))

    def build():
        st = init_serve_state(cfg, B, max_len, dali_cfg=dcfg, per_slot=True)
        for b in range(B):
            fresh = init_caches(cfg, 1, max_len)
            t1, fresh, _ = admit_prefill(params, toks[b:b + 1], fresh,
                                      jnp.asarray(S, jnp.int32))
            st = admit(st, fresh, t1, jnp.asarray(b, jnp.int32),
                       jnp.asarray(S, jnp.int32))
        return dict(st, active=st["active"].at[1].set(False))  # retired slot

    fast = jax.jit(make_decode_step(cfg, dcfg))                # auto: sparse
    dense = jax.jit(make_decode_step(cfg, dcfg, moe_capacity=B))
    sf, lf, tf = fast(params, build(), None)
    sd, ld, td = dense(params, build(), None)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(sf["tokens"]),
                                  np.asarray(sd["tokens"]))
    # live-token-masked workloads feed DALI identically on both paths
    np.testing.assert_array_equal(
        np.asarray(sf["dali"]["acc"]["hits"]),
        np.asarray(sd["dali"]["acc"]["hits"]))


# --------------------------------------------------------------------------
# skip-empty ragged kernel vs oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("counts", [
    [0, 128, 37, 5],            # skewed: empty, full, partial, tiny
    [0, 0, 0, 0],               # fully idle layer
    [128, 128, 128, 128],       # saturated == dense
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_expert_ffn_ragged_kernel_matches_oracle(counts, dt):
    E, C, d, f = 4, 128, 64, 256
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), dt)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, dt)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, dt)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.05, dt)
    cnt = jnp.asarray(counts, jnp.int32)
    y = expert_ffn(xe, wg, wu, wd, counts=cnt, block_c=64, block_f=128,
                   interpret=True)
    r = expert_ffn_ragged_ref(xe, wg, wu, wd, cnt)
    tol = 3e-2 if dt == jnp.bfloat16 else 3e-5
    scale = float(jnp.abs(r.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32)
                        - r.astype(jnp.float32)).max()) / scale
    assert err < tol, err
    # rows at/beyond the count are exactly zero (skipped or masked)
    rows = np.asarray(jnp.arange(C)[None, :] >= cnt[:, None])
    assert not np.asarray(y.astype(jnp.float32))[rows].any()


def test_expert_ffn_ragged_saturated_matches_dense_kernel():
    E, C, d, f = 2, 128, 64, 128
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, jnp.float32)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.05, jnp.float32)
    y_r = expert_ffn(xe, wg, wu, wd, counts=jnp.full((E,), C, jnp.int32),
                     block_c=64, block_f=128, interpret=True)
    y_d = expert_ffn(xe, wg, wu, wd, block_c=64, block_f=128,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_d),
                               rtol=1e-6, atol=1e-6)


def test_expert_ffn_nondivisible_shapes():
    """Capacities pad to multiples of 4 (not of the 128 block) and
    d_expert need not divide block_f: the kernel must pick divisor block
    sizes instead of asserting (the production dense path routes through
    it on TPU with arbitrary serving shapes)."""
    E, C, d, f = 3, 36, 32, 96
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05, jnp.float32)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.05, jnp.float32)
    cnt = jnp.asarray([36, 0, 7], jnp.int32)
    y_d = expert_ffn(xe, wg, wu, wd, block_c=16, block_f=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_d),
                               np.asarray(expert_ffn_ref(xe, wg, wu, wd)),
                               rtol=1e-4, atol=1e-5)
    y_r = expert_ffn(xe, wg, wu, wd, counts=cnt, block_c=16, block_f=64,
                     interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_r),
        np.asarray(expert_ffn_ragged_ref(xe, wg, wu, wd, cnt)),
        rtol=1e-4, atol=1e-5)
    # bf16 sublane tile is 16: C=20 forces the pad-to-tile path
    xb, wgb, wub, wdb = (a.astype(jnp.bfloat16)[:, :20] if a is xe
                         else a.astype(jnp.bfloat16)
                         for a in (xe, wg, wu, wd))
    y_b = expert_ffn(xb, wgb, wub, wdb, counts=jnp.asarray([20, 0, 3]),
                     block_c=16, block_f=64, interpret=True)
    r_b = expert_ffn_ragged_ref(xb, wgb, wub, wdb, jnp.asarray([20, 0, 3]))
    assert y_b.shape == xb.shape
    np.testing.assert_allclose(np.asarray(y_b, np.float32),
                               np.asarray(r_b, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_expert_ffn_grouped_matches_gathered_oracle():
    """The grouped variant (G row groups sharing E weight sets via a
    scalar-prefetched group→expert map — the EP receive-bucket entry,
    moe_ep._ep_expert_ffn) must match the gathered-weight oracle,
    including fully-empty groups and partial tails."""
    E, G, C, d, f = 3, 6, 32, 16, 48
    xe = jnp.asarray(RNG.standard_normal((G, C, d)), jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.1, jnp.float32)
    cnt = jnp.asarray([0, 32, 7, 0, 12, 1], jnp.int32)
    eids = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    y = expert_ffn(xe, wg, wu, wd, counts=cnt, expert_ids=eids,
                   block_c=16, block_f=32, interpret=True)
    r = expert_ffn_ragged_ref(xe, wg, wu, wd, cnt, expert_ids=eids)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-5, atol=1e-6)
    # rows at/beyond each group's count are exactly zero
    rows = np.asarray(jnp.arange(C)[None, :] >= cnt[:, None])
    assert not np.asarray(y)[rows].any()
    # groups mapping to the same expert with equal inputs agree
    xe2 = xe.at[3].set(xe[2])
    y2 = expert_ffn(xe2, wg, wu, wd,
                    counts=jnp.asarray([0, 32, 7, 7, 12, 1], jnp.int32),
                    expert_ids=eids, block_c=16, block_f=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(y2[2]), np.asarray(y2[3]))
    with pytest.raises(ValueError):
        expert_ffn(xe, wg, wu, wd, expert_ids=eids, interpret=True)


@pytest.mark.parametrize("variant", ["dense", "ragged", "grouped"])
def test_expert_ffn_kernel_path_is_differentiable(variant):
    """pallas_call has no autodiff rule, so the op wraps the kernel in a
    custom VJP (kernel forward, oracle backward) — grads through the TPU
    paths (single-device dense, EP receive buckets) must match the
    oracle's grads exactly (train_step runs through both)."""
    from repro.kernels.expert_ffn.ops import expert_ffn_op
    E, C, d, f = 3, 16, 8, 24
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.1, jnp.float32)
    cnt = None if variant == "dense" else jnp.asarray([16, 0, 5], jnp.int32)
    eids = (jnp.asarray([0, 1, 1], jnp.int32) if variant == "grouped"
            else None)

    def loss(kernel):
        def f_(xe, wg, wu, wd):
            y = expert_ffn_op(xe, wg, wu, wd, counts=cnt, expert_ids=eids,
                              force_kernel=kernel, interpret=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return f_

    gk = jax.grad(loss(True), argnums=(0, 1, 2, 3))(xe, wg, wu, wd)
    go = jax.grad(loss(False), argnums=(0, 1, 2, 3))(xe, wg, wu, wd)
    for k, o in zip(gk, go):
        np.testing.assert_allclose(np.asarray(k), np.asarray(o),
                                   rtol=1e-5, atol=1e-6)


def test_ragged_ref_masks_garbage_rows():
    """The dispatch zero-fills unused bucket rows; the ragged oracle (and
    kernel) must not depend on that — garbage tails stay contained."""
    E, C, d, f = 2, 8, 16, 32
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), jnp.float32)
    wg = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(RNG.standard_normal((E, f, d)) * 0.1, jnp.float32)
    cnt = jnp.asarray([3, 0], jnp.int32)
    full = expert_ffn_ref(xe, wg, wu, wd)
    ragged = expert_ffn_ragged_ref(xe, wg, wu, wd, cnt)
    np.testing.assert_allclose(np.asarray(ragged[0, :3]),
                               np.asarray(full[0, :3]), rtol=1e-6)
    assert not np.asarray(ragged)[0, 3:].any()
    assert not np.asarray(ragged)[1].any()


# --------------------------------------------------------------------------
# chunked ragged-tail fix
# --------------------------------------------------------------------------

def test_chunked_ragged_tail_matches_unchunked(monkeypatch):
    """A token count that does NOT divide the chunk size must produce the
    same outputs and observables as the unchunked dispatch (full capacity
    so per-chunk capacities cannot introduce drops)."""
    cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, dtype="float32",
                      param_dtype="float32",
                      moe=MoEConfig(n_routed=8, top_k=2, d_expert=48,
                                    capacity_factor=0.0))
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 25, 32))   # T=50
    y_ref, i_ref = apply_moe(params, x, cfg)                    # unchunked
    monkeypatch.setattr(moe, "MOE_CHUNK_TOKENS", 16)            # 50 = 3*16+2
    y_c, i_c = apply_moe(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(i_c["workload"]),
                                  np.asarray(i_ref["workload"]))
    np.testing.assert_array_equal(np.asarray(i_c["topk_idx"]),
                                  np.asarray(i_ref["topk_idx"]))
    assert int(i_c["dropped"]) == 0
    # z is a per-token mean (linear): valid-count weighting makes the
    # chunked value exact.  aux is nonlinear in the token set, so chunking
    # approximates it (as the pre-fix divisible path already did) — but
    # the padded tail must not push it far off.
    np.testing.assert_allclose(float(i_c["z_loss"]),
                               float(i_ref["z_loss"]), rtol=1e-4)
    np.testing.assert_allclose(float(i_c["aux_loss"]),
                               float(i_ref["aux_loss"]), rtol=0.2)


def test_valid_mask_excludes_padded_tokens():
    """Direct check of the mask semantics the ragged tail relies on: a
    right-padded batch with ``valid`` must reproduce the unpadded run on
    every observable, with zero output rows for the padding."""
    cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, dtype="float32",
                      param_dtype="float32",
                      moe=MoEConfig(n_routed=8, top_k=2, d_expert=48,
                                    capacity_factor=0.0))
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x_real = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 32))
    x_pad = jnp.concatenate(
        [x_real, 7.7 * jnp.ones((1, 13, 32), x_real.dtype)], axis=1)
    valid = jnp.arange(16) < 3
    y_ref, i_ref = apply_moe(params, x_real, cfg, force_path="dense",
                             capacity=4)
    y_p, i_p = apply_moe(params, x_pad, cfg, force_path="dense",
                         capacity=4, valid=valid)
    np.testing.assert_allclose(np.asarray(y_p[:, :3]), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-6)
    assert not np.asarray(y_p)[:, 3:].any()
    np.testing.assert_array_equal(np.asarray(i_p["workload"]),
                                  np.asarray(i_ref["workload"]))
    np.testing.assert_allclose(float(i_p["aux_loss"]),
                               float(i_ref["aux_loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(i_p["z_loss"]),
                               float(i_ref["z_loss"]), rtol=1e-5)
    assert int(i_p["dropped"]) == 0


def test_chunked_divisible_unchanged(monkeypatch):
    cfg = ModelConfig(d_model=32, d_ff=64, vocab=64, dtype="float32",
                      param_dtype="float32",
                      moe=MoEConfig(n_routed=8, top_k=2, d_expert=48,
                                    capacity_factor=0.0))
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))   # T=48=3*16
    y_ref, i_ref = apply_moe(params, x, cfg)
    monkeypatch.setattr(moe, "MOE_CHUNK_TOKENS", 16)
    y_c, i_c = apply_moe(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(i_c["workload"]),
                                  np.asarray(i_ref["workload"]))


# --------------------------------------------------------------------------
# sync-free telemetry accumulator
# --------------------------------------------------------------------------

def test_telemetry_accumulator_matches_per_step_sums():
    """The device-side accumulator drained once must equal per-step host
    conversion of the same telemetry stream."""
    from repro.core.engine import (DaliConfig, TelemetryAggregator,
                                   dali_schedule, init_dali_state)
    rng = np.random.default_rng(0)
    L, E, T, d = 3, 8, 6, 16
    dcfg = DaliConfig(n_moe_layers=L, n_experts=E, cache_size=3,
                      prefetch_size=2, w_size=2, u_size=1)
    routers = jnp.asarray(rng.standard_normal((L, d, E)), jnp.float32) * .3
    res = jnp.asarray(rng.standard_normal((L, d)), jnp.float32) * .1
    step = jax.jit(lambda s, w, g: dali_schedule(s, w, g, routers, res,
                                                 dcfg, 2))
    state = init_dali_state(dcfg)
    legacy = TelemetryAggregator()
    agg = TelemetryAggregator(flush_interval=4)
    n_steps = 10                   # not a multiple of the flush interval
    for i in range(n_steps):
        wl = jnp.asarray(rng.integers(0, 5, (L, E)), jnp.int32)
        gi = jnp.asarray(rng.standard_normal((L, T, d)), jnp.float32)
        state, tel = step(state, wl, gi)
        legacy.update(tel, n_active=T)
        agg.observe(state, n_active=T)
    agg.end_epoch()                # drain the non-flushed remainder
    assert agg.steps == legacy.steps == n_steps
    assert agg.active_tokens == legacy.active_tokens
    assert agg.hits == legacy.hits
    assert agg.misses == legacy.misses
    assert agg.swaps == legacy.swaps
    np.testing.assert_allclose(agg.moe_time_est, legacy.moe_time_est,
                               rtol=1e-5)
    np.testing.assert_allclose(agg.link_time_est, legacy.link_time_est,
                               rtol=1e-5)
    assert int(state["acc"]["steps"]) == n_steps


def test_telemetry_epochs_rebase_across_state_reinit():
    """Wave serving re-inits the DALI state per wave: totals must keep
    accumulating across epochs instead of resetting or double counting."""
    from repro.core.engine import (DaliConfig, TelemetryAggregator,
                                   dali_schedule, init_dali_state)
    rng = np.random.default_rng(1)
    L, E, T, d = 2, 8, 4, 16
    dcfg = DaliConfig(n_moe_layers=L, n_experts=E, cache_size=3)
    routers = jnp.asarray(rng.standard_normal((L, d, E)), jnp.float32) * .3
    res = jnp.asarray(rng.standard_normal((L, d)), jnp.float32) * .1
    agg = TelemetryAggregator(flush_interval=100)   # drain only at epochs
    ref_hits = 0
    for _ in range(2):                              # two "waves"
        state = init_dali_state(dcfg)
        for _ in range(3):
            wl = jnp.asarray(rng.integers(1, 5, (L, E)), jnp.int32)
            gi = jnp.asarray(rng.standard_normal((L, T, d)), jnp.float32)
            state, tel = dali_schedule(state, wl, gi, routers, res, dcfg, 2)
            agg.observe(state, n_active=T)
        ref_hits += int(state["acc"]["hits"])
        agg.end_epoch()
    assert agg.steps == 6
    assert agg.hits == ref_hits
