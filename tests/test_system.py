"""End-to-end system behaviour: train -> calibrate -> serve with the DALI
engine, and the residual/prefetch/cache pipeline on real routing traces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_smoke
from repro.core.residual import calibrate_residuals, cosine_similarity
from repro.core.tracing import (capture_decode_trace, capture_prefill_trace,
                                moe_layer_indices)
from repro.models.model import init_model
from repro.serving.scheduler import BatchServer, Request
from repro.serving.steps import (default_dali_config, init_serve_state,
                                 make_decode_step, make_prefill_step)


@pytest.fixture(scope="module")
def small_moe():
    cfg = make_smoke(get_config("mixtral_8x7b")).replace(n_layers=4)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_trace_capture_shapes(small_moe):
    cfg, params = small_moe
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                 cfg.vocab)
    tr = capture_decode_trace(params, cfg, prompts, n_decode=5)
    assert tr.n_steps == 5
    assert tr.n_moe_layers == len(moe_layer_indices(cfg)) == 4
    for l in range(tr.n_moe_layers):
        assert tr.workload[0][l].shape == (cfg.moe.n_routed,)
        assert tr.workload[0][l].sum() == 4 * cfg.moe.top_k
        assert tr.gate_in[0][l].shape == (4, cfg.d_model)


def test_residual_calibration_and_cosine(small_moe):
    cfg, params = small_moe
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                 cfg.vocab)
    calib = capture_decode_trace(params, cfg, prompts, n_decode=8)
    res = calibrate_residuals([calib])
    assert len(res) == calib.n_moe_layers
    assert res[-1].shape == (cfg.d_model,)
    # corrected features at least as close on the calibration set itself
    test = calib
    raw, corr = [], []
    for t in range(test.n_steps):
        for l in range(test.n_moe_layers - 1):
            raw.append(cosine_similarity(test.gate_in[t][l],
                                         test.gate_in[t][l + 1]))
            corr.append(cosine_similarity(
                test.gate_in[t][l] + res[l][None],
                test.gate_in[t][l + 1]))
    assert np.mean(corr) >= np.mean(raw) - 0.02


def test_prefill_trace(small_moe):
    cfg, params = small_moe
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab)
    tr = capture_prefill_trace(params, cfg, toks)
    assert tr.n_steps == 1
    assert tr.workload[0][0].sum() == 2 * 16 * cfg.moe.top_k


def test_decode_step_with_dali_engine(small_moe):
    cfg, params = small_moe
    dcfg = default_dali_config(cfg, cache_ratio=0.5)
    B, S = 2, 8
    state = init_serve_state(cfg, B, 32, dali_cfg=dcfg)
    prefill = jax.jit(make_prefill_step(cfg, 32))
    decode = jax.jit(make_decode_step(cfg, dcfg))
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    nxt, caches, _ = prefill(params, toks, state["caches"])
    state = dict(state, tokens=nxt, caches=caches,
                 pos=jnp.asarray(S, jnp.int32))
    hits = 0
    for _ in range(6):
        state, logits, tel = decode(params, state)
        assert np.isfinite(np.asarray(logits)).all()
        hits += int(np.asarray(tel["hits"]).sum())
        assert float(tel["step_moe_time"]) > 0
    assert int(state["pos"]) == S + 6
    # cache respects size
    assert int(np.asarray(state["dali"]["resident"]).sum(-1).max()) \
        <= dcfg.cache_size


def test_batch_server_end_to_end(small_moe):
    cfg, params = small_moe
    dcfg = default_dali_config(cfg, cache_ratio=0.5)
    server = BatchServer(params, cfg, batch_size=4, max_len=48,
                         dali_cfg=dcfg)
    rng = np.random.default_rng(0)
    for i in range(6):
        server.submit(Request(rid=i,
                              prompt=rng.integers(0, cfg.vocab, 12,
                                                  ).astype(np.int32),
                              max_new_tokens=8))
    done = server.run()
    assert len(done) == 6
    for r in done:
        assert 1 <= len(r.output) <= 8
        assert r.done_at >= r.submitted_at
    assert server.metrics.decode_tokens > 0
    assert server.metrics.dali_lookups >= 0


def test_dali_inapplicable_archs_serve_without_engine():
    cfg = make_smoke(get_config("olmo_1b"))
    assert default_dali_config(cfg) is None
    params = init_model(jax.random.PRNGKey(0), cfg)
    server = BatchServer(params, cfg, batch_size=2, max_len=32)
    server.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                          max_new_tokens=4))
    done = server.run()
    assert len(done) == 1 and len(done[0].output) >= 1
