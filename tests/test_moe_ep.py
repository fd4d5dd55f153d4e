"""Expert-parallel shard_map MoE (moe_ep.py) vs the dense dispatch path —
run in a subprocess with 8 forced host devices so the single-device test
session is unaffected."""
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
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ('data', 'model'))
    for shared, mode, rt in [(1, 'tp', 'softmax_topk'), (0, 'tp', 'topk_softmax'),
                             (2, 'fsdp', 'softmax_topk'), (0, 'fsdp', 'sigmoid')]:
        cfg = ModelConfig(d_model=64, d_ff=128, dtype='float32',
                          param_dtype='float32',
                          moe=MoEConfig(n_routed=8, top_k=2, d_expert=96,
                                        n_shared=shared, d_shared=64,
                                        router_type=rt, capacity_factor=8.0))
        params = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 64))
        y_ref, i_ref = apply_moe(params, x, cfg)
        lmap = shd.logical_map_for(cfg, 'prefill_32k', mesh)
        with mesh, shd.rules(mesh, lmap, mode):
            from repro.models.moe_ep import ep_applicable
            assert ep_applicable(cfg, 4, 128)
            y_ep, i_ep = jax.jit(lambda p, x: apply_moe(p, x, cfg))(params, x)
            # grads flow through the all_to_all pair (EP path)
            g = jax.jit(jax.grad(
                lambda p: jnp.sum(apply_moe(p, x, cfg)[0] ** 2)))(params)
            assert all(np.isfinite(np.asarray(l)).all()
                       for l in jax.tree.leaves(g))
        assert float(jnp.abs(y_ref - y_ep).max()) < 1e-4, (shared, mode, rt)
        assert np.array_equal(np.asarray(i_ref['workload']),
                              np.asarray(i_ep['workload']))
    print('EP_OK')
""")


def test_moe_ep_parity_subprocess():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT, src],
                       capture_output=True, text=True, timeout=900)
    assert "EP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
