"""Workload-sized ragged EP exchange (moe_ep.py, DESIGN.md §6) vs the
dense exchange and the single-device dense dispatch — run in a subprocess
with 8 forced host devices so the single-device test session is
unaffected.

Covers uniform, Zipf-skewed, all-on-one-expert and zero-token-shard
routings: outputs, workload/dropped observables, grads through the
all_to_all pair, the regression pinning the exchanged capacity
C_x < C whenever the workload leaves headroom, and the
attention-overlapped count exchange (count_overlap, DESIGN.md §9) being
a pure scheduling change — outputs/ep_cx/workload/dropped bit-identical
with the hoist on vs off, grads matching tightly."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import sys; sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.config import MoEConfig, ModelConfig
    from repro.models.moe import apply_moe, init_moe
    from repro.models.moe_ep import ep_applicable, exchange_ladder
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh

    assert exchange_ladder(64) == [4, 8, 16, 32, 64]
    assert exchange_ladder(96) == [4, 8, 16, 32, 64, 96]
    assert exchange_ladder(4) == [4]

    mesh = make_mesh((2, 4), ('data', 'model'))
    B, S, d, E, K = 4, 128, 64, 64, 2
    C = (B // 2) * (S // 4)                       # cf=0: per-device T_my

    def routed_x(kind, seed=0):
        rng = np.random.default_rng(seed)
        T = B * S
        x = 0.05 * rng.standard_normal((T, d))
        if kind == 'uniform':
            tgt = rng.integers(0, E, T)
        elif kind == 'zipf':
            p = 1.0 / np.arange(1, E + 1) ** 1.2
            tgt = rng.choice(E, size=T, p=p / p.sum())
        elif kind == 'one_expert':
            tgt = np.zeros(T, np.int64)
        elif kind == 'zero_shard':              # experts 0/1 live on model
            tgt = rng.integers(0, 2, T)         # device 0; 1..3 get nothing
            x[:, :2] += 1.5                     # top-2 stays inside {0, 1}
        x[np.arange(T), tgt] += 3.0
        return jnp.asarray(x.reshape(B, S, d), jnp.float32)

    def run(cfg, params, x, force_exchange, overlap=None):
        lmap = shd.logical_map_for(cfg, 'prefill_32k', mesh)
        with mesh, shd.rules(mesh, lmap, 'tp'):
            assert ep_applicable(cfg, B, S)
            y, i = jax.jit(lambda p, x: apply_moe(
                p, x, cfg, force_exchange=force_exchange,
                count_overlap=overlap))(params, x)
            g = jax.jit(jax.grad(lambda p: jnp.sum(apply_moe(
                p, x, cfg, force_exchange=force_exchange,
                count_overlap=overlap)[0] ** 2)))(params)
        return y, i, g

    cfg = ModelConfig(d_model=d, d_ff=128, dtype='float32',
                      param_dtype='float32',
                      moe=MoEConfig(n_routed=E, top_k=K, d_expert=48,
                                    capacity_factor=0.0))
    params = init_moe(jax.random.PRNGKey(0), cfg)
    # deterministic routing: logit_e = 6 * x[:, e]
    params = dict(params, router=6.0 * jnp.eye(d, E, dtype=jnp.float32))

    expect_cx = {'uniform': C // 2, 'zipf': C // 2,
                 'one_expert': C, 'zero_shard': C}
    for kind in ('uniform', 'zipf', 'one_expert', 'zero_shard'):
        x = routed_x(kind)
        y_ref, i_ref = apply_moe(params, x, cfg)          # single device
        y_rag, i_rag, g_rag = run(cfg, params, x, None)
        y_dns, i_dns, g_dns = run(cfg, params, x, 'dense')
        # ragged == dense exchange on every output/observable
        assert float(jnp.abs(y_rag - y_dns).max()) < 1e-6, kind
        assert np.array_equal(np.asarray(i_rag['workload']),
                              np.asarray(i_dns['workload'])), kind
        assert int(i_rag['dropped']) == int(i_dns['dropped']) == 0, kind
        # EP == the dense single-device dispatch
        assert float(jnp.abs(y_rag - y_ref).max()) < 1e-4, kind
        assert np.array_equal(np.asarray(i_rag['workload']),
                              np.asarray(i_ref['workload'])), kind
        # grads flow through the ladder's all_to_all pair and match the
        # dense exchange
        for lr, ld in zip(jax.tree.leaves(g_rag), jax.tree.leaves(g_dns)):
            assert np.isfinite(np.asarray(lr)).all(), kind
            np.testing.assert_allclose(np.asarray(lr), np.asarray(ld),
                                       rtol=1e-4, atol=1e-5)
        # regression: the exchange ships <= the workload-sized rung
        cx = int(i_rag['ep_cx'])
        assert cx <= expect_cx[kind], (kind, cx, C)
        assert int(i_dns['ep_cx']) == C, kind
        # the attention-overlapped count exchange is a pure scheduling
        # change: hoisting the count all_to_all ahead of the dispatch
        # math changes NOTHING observable (the default runs overlapped,
        # so y_rag above is the overlap=True side)
        y_seq, i_seq, g_seq = run(cfg, params, x, None, overlap=False)
        assert np.array_equal(np.asarray(y_rag), np.asarray(y_seq)), kind
        assert int(i_rag['ep_cx']) == int(i_seq['ep_cx']), kind
        assert np.array_equal(np.asarray(i_rag['workload']),
                              np.asarray(i_seq['workload'])), kind
        assert int(i_rag['dropped']) == int(i_seq['dropped']), kind
        for lr, ls in zip(jax.tree.leaves(g_rag), jax.tree.leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(lr), np.asarray(ls),
                                       rtol=1e-5, atol=1e-6)
        print(kind, 'cx', cx, 'of C', C, 'overlap parity ok')
    assert 'ep_cx' not in i_ref                    # dense path unchanged

    # under a tight capacity the ragged exchange must drop EXACTLY the
    # slots the dense exchange drops (keep/dropped share one rule)
    import dataclasses
    cfg_t = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                capacity_factor=2.0))
    params_t = dict(init_moe(jax.random.PRNGKey(1), cfg_t),
                    router=6.0 * jnp.eye(d, E, dtype=jnp.float32))
    x = routed_x('zipf', seed=3)
    y_rag, i_rag, _ = run(cfg_t, params_t, x, None)
    y_dns, i_dns, _ = run(cfg_t, params_t, x, 'dense')
    assert int(i_rag['dropped']) == int(i_dns['dropped']) > 0
    assert float(jnp.abs(y_rag - y_dns).max()) < 1e-6
    assert np.array_equal(np.asarray(i_rag['workload']),
                          np.asarray(i_dns['workload']))
    # drops are decided by the same keep-rule either side of the count
    # hoist: bit-identical under capacity pressure too
    y_seq, i_seq, _ = run(cfg_t, params_t, x, None, overlap=False)
    assert np.array_equal(np.asarray(y_rag), np.asarray(y_seq))
    assert int(i_rag['dropped']) == int(i_seq['dropped'])
    print('EP_RAGGED_OK')
""")


def test_moe_ep_ragged_parity_subprocess():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT, src],
                       capture_output=True, text=True, timeout=900)
    assert "EP_RAGGED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
