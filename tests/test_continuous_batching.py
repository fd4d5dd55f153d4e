"""Slot-level continuous batching (serving/scheduler.py, serving/steps.py):
admission/retirement ordering, per-slot position correctness (late-admitted
request == solo run), DALI telemetry aggregation under partial batches, and
the decode-token accounting regression (DESIGN.md §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_smoke
from repro.core.engine import TelemetryAggregator, masked_workloads
from repro.models.model import init_model
from repro.serving.scheduler import (BatchServer, ContinuousBatchServer,
                                     Request, make_server)
from repro.serving.steps import (default_dali_config, init_serve_state,
                                 make_admit_prefill, make_admit_step,
                                 make_decode_step)

# an id outside the sampled-token range: requests only retire on budget
NO_EOS = 10_000_000


@pytest.fixture(scope="module")
def small_moe():
    cfg = make_smoke(get_config("mixtral_8x7b")).replace(n_layers=4)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lengths]


# --------------------------------------------------------------------------
# admission / retirement ordering
# --------------------------------------------------------------------------

def test_fifo_admission_and_budget_retirement(small_moe):
    cfg, params = small_moe
    server = ContinuousBatchServer(params, cfg, batch_size=1, max_len=64,
                                   eos_id=NO_EOS)
    for i, (p, budget) in enumerate(zip(_prompts(cfg, [8, 12, 6]),
                                        [3, 2, 4])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=budget))
    done = server.run()
    # single slot: strict FIFO service order, each exactly at budget
    assert [r.rid for r in done] == [0, 1, 2]
    assert [len(r.output) for r in done] == [3, 2, 4]
    for r in done:
        assert r.first_token_at <= r.done_at


def test_freed_slot_readmits_while_others_run(small_moe):
    cfg, params = small_moe
    server = ContinuousBatchServer(params, cfg, batch_size=2, max_len=64,
                                   eos_id=NO_EOS)
    for i, (p, budget) in enumerate(zip(_prompts(cfg, [10, 10, 10]),
                                        [12, 2, 12])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=budget))
    done = server.run()
    by_rid = {r.rid: r for r in done}
    assert sorted(by_rid) == [0, 1, 2]
    assert len(by_rid[1].output) == 2
    # rid 2 was admitted into rid 1's freed slot BEFORE rid 0 finished —
    # the continuous-batching property the wave scheduler lacks
    assert by_rid[2].first_token_at < by_rid[0].done_at
    # occupancy stayed above 1: slots were refilled mid-flight
    assert server.metrics.mean_occupancy() > 1.0


def test_eos_retires_slot(small_moe):
    cfg, params = small_moe
    # greedy decode of a random-init model: find the argmax token the
    # model emits after one step and use it as EOS for the next request
    probe = ContinuousBatchServer(params, cfg, batch_size=1, max_len=64,
                                  eos_id=NO_EOS)
    probe.submit(Request(rid=0, prompt=_prompts(cfg, [8])[0],
                         max_new_tokens=4))
    first = probe.run()[0].output
    eos = first[1]           # token emitted by the first decode step
    server = ContinuousBatchServer(params, cfg, batch_size=1, max_len=64,
                                   eos_id=eos)
    server.submit(Request(rid=0, prompt=_prompts(cfg, [8])[0],
                          max_new_tokens=32))
    done = server.run()
    assert done[0].output[-1] == eos
    assert len(done[0].output) < 32       # retired by EOS, not budget


# --------------------------------------------------------------------------
# per-slot position correctness
# --------------------------------------------------------------------------

def test_late_admitted_request_matches_solo_run(small_moe):
    """The acceptance criterion: a request admitted mid-flight into a
    freed slot — different slot position, different admission step —
    produces exactly the tokens of a solo run of the same prompt."""
    cfg, params = small_moe
    prompts = _prompts(cfg, [14, 9, 21], seed=3)

    server = ContinuousBatchServer(params, cfg, batch_size=2, max_len=96,
                                   eos_id=NO_EOS)
    # rid 0 runs long; rid 1 short, freeing its slot; rid 2 late-admitted
    budgets = [16, 3, 10]
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = {r.rid: r for r in server.run()}
    assert len(done) == 3

    for rid in (0, 1, 2):
        solo = ContinuousBatchServer(params, cfg, batch_size=1, max_len=96,
                                     eos_id=NO_EOS)
        solo.submit(Request(rid=0, prompt=prompts[rid],
                            max_new_tokens=budgets[rid]))
        solo_out = solo.run()[0].output
        assert done[rid].output == solo_out, \
            f"rid {rid}: batched {done[rid].output} != solo {solo_out}"


def test_sliding_window_prompt_longer_than_window_matches_solo():
    """Rolling (attn_local) caches keep the LAST S_c chunk positions, so a
    bucketed right-padded admit prefill would evict real prompt tokens;
    the continuous server must prefill such configs at exact length.  A
    prompt longer than the window, late-admitted, must still match solo."""
    cfg = make_smoke(get_config("gemma2_9b"))      # window 16, local+global
    params = init_model(jax.random.PRNGKey(1), cfg)
    assert cfg.attn.sliding_window == 16
    prompts = _prompts(cfg, [40, 9, 37], seed=5)   # > window, bucket would
    budgets = [12, 2, 8]                           # pad 40 -> 64

    server = ContinuousBatchServer(params, cfg, batch_size=2, max_len=96,
                                   eos_id=NO_EOS)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = {r.rid: r for r in server.run()}
    for rid in (0, 2):                             # the long-prompt ones
        solo = ContinuousBatchServer(params, cfg, batch_size=1, max_len=96,
                                     eos_id=NO_EOS)
        solo.submit(Request(rid=0, prompt=prompts[rid],
                            max_new_tokens=budgets[rid]))
        assert done[rid].output == solo.run()[0].output


def test_wave_bucketing_never_truncates_budget(small_moe):
    """The wave bucket is capped so S + budget fits the KV horizon
    whenever the raw prompt length would: max_len=96, prompt 48, budget
    32 must yield 32 tokens (a naive 64-bucket would cap decode at 31)."""
    cfg, params = small_moe
    server = BatchServer(params, cfg, batch_size=1, max_len=96,
                         eos_id=NO_EOS)
    server.submit(Request(rid=0, prompt=_prompts(cfg, [48])[0],
                          max_new_tokens=32))
    done = server.run()
    assert len(done[0].output) == 32


def test_per_slot_decode_matches_shared_decode(small_moe):
    """Two slots admitted at the SAME length decoded with per-slot
    positions must match the wave-style shared-position decode."""
    cfg, params = small_moe
    B, S, n_steps, max_len = 2, 8, 5, 32
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab)

    # shared-position (wave) reference
    from repro.serving.steps import make_prefill_step
    state = init_serve_state(cfg, B, max_len)
    prefill = jax.jit(make_prefill_step(cfg, max_len))
    decode = jax.jit(make_decode_step(cfg))
    nxt, caches, _ = prefill(params, toks, state["caches"])
    state = dict(state, tokens=nxt, caches=caches,
                 pos=jnp.asarray(S, jnp.int32))
    ref = [np.asarray(nxt)[:, 0].copy()]
    for _ in range(n_steps):
        state, _, _ = decode(params, state)
        ref.append(np.asarray(state["tokens"])[:, 0].copy())

    # per-slot path: admit each row separately, then batch-decode
    admit_prefill = jax.jit(make_admit_prefill(cfg))
    admit = jax.jit(make_admit_step(cfg))
    from repro.models.model import init_caches
    ps = init_serve_state(cfg, B, max_len, per_slot=True)
    for b in range(B):
        fresh = init_caches(cfg, 1, max_len)
        tok1, fresh, _ = admit_prefill(params, toks[b:b + 1], fresh,
                                    jnp.asarray(S, jnp.int32))
        ps = admit(ps, fresh, tok1, jnp.asarray(b, jnp.int32),
                   jnp.asarray(S, jnp.int32))
    got = [np.asarray(ps["tokens"])[:, 0].copy()]
    for _ in range(n_steps):
        ps, _, _ = decode(params, ps)
        got.append(np.asarray(ps["tokens"])[:, 0].copy())
    np.testing.assert_array_equal(np.stack(ref), np.stack(got))


# --------------------------------------------------------------------------
# DALI telemetry under partial batches
# --------------------------------------------------------------------------

def test_masked_workloads_counts_only_live_tokens():
    topk = jnp.asarray([[[0, 1], [2, 3], [0, 2]]])        # (L=1, T=3, K=2)
    mask = jnp.asarray([True, False, True])
    w = np.asarray(masked_workloads(topk, 4, mask))
    assert w.tolist() == [[2, 1, 1, 0]]                   # token 1 dropped
    assert w.sum() == 2 * 2                               # live tokens * K


def test_decode_telemetry_masks_retired_slots(small_moe):
    cfg, params = small_moe
    dcfg = default_dali_config(cfg, cache_ratio=0.5)
    L, K = dcfg.n_moe_layers, cfg.moe.top_k
    B, S, max_len = 3, 8, 32
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S), 0, cfg.vocab)

    admit_prefill = jax.jit(make_admit_prefill(cfg))
    admit = jax.jit(make_admit_step(cfg))
    decode = jax.jit(make_decode_step(cfg, dcfg))
    from repro.models.model import init_caches
    state = init_serve_state(cfg, B, max_len, dali_cfg=dcfg, per_slot=True)
    for b in range(B):
        fresh = init_caches(cfg, 1, max_len)
        tok1, fresh, _ = admit_prefill(params, toks[b:b + 1], fresh,
                                    jnp.asarray(S, jnp.int32))
        state = admit(state, fresh, tok1, jnp.asarray(b, jnp.int32),
                      jnp.asarray(S, jnp.int32))
    # retire slots 1 and 2: only ONE live token remains
    state["active"] = state["active"].at[1].set(False).at[2].set(False)

    agg = TelemetryAggregator()
    for _ in range(4):
        state, _, tel = decode(params, state, None)
        # with one live token, at most top_k experts are active per layer
        assert int(tel["hits"].sum() + tel["misses"].sum()) <= L * K
        agg.update(tel, n_active=1)
    assert agg.steps == 4
    assert agg.active_tokens == 4
    assert agg.lookups <= 4 * L * K
    assert agg.moe_time_est > 0


def test_server_aggregates_telemetry_per_step(small_moe):
    cfg, params = small_moe
    dcfg = default_dali_config(cfg, cache_ratio=0.5)
    server = ContinuousBatchServer(params, cfg, batch_size=2, max_len=64,
                                   dali_cfg=dcfg, eos_id=NO_EOS)
    for i, (p, b) in enumerate(zip(_prompts(cfg, [8, 8, 8]), [6, 2, 6])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    server.run()
    m = server.metrics
    assert m.dali.steps == m.steps > 0
    # occupancy-weighted: aggregator saw exactly the emitted decode tokens
    assert m.dali.active_tokens == m.decode_tokens
    # partial batches happened (a slot retired before the run drained)
    assert m.steps * 2 > m.decode_tokens
    assert m.dali.lookups > 0
    assert m.dali.lookups <= m.decode_tokens * dcfg.n_moe_layers \
        * cfg.moe.top_k


# --------------------------------------------------------------------------
# decode-token accounting (regression)
# --------------------------------------------------------------------------

def test_wave_decode_token_accounting_no_double_count(small_moe):
    """Old wave loop counted live.sum() after retirement plus a re-derived
    term for just-finished requests, double-counting a request's final
    token whenever its last emission also appeared in the re-derived scan.
    Now: decode_tokens == total appended decode outputs, exactly (the
    first token comes from prefill in both servers, so decode emissions
    are len(output) - 1 per request)."""
    cfg, params = small_moe
    server = BatchServer(params, cfg, batch_size=4, max_len=64,
                         eos_id=NO_EOS)
    for i, (p, b) in enumerate(zip(_prompts(cfg, [8, 8, 12, 12]),
                                   [1, 3, 5, 2])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = server.run()
    assert all(len(r.output) == r.max_new_tokens for r in done)
    assert server.metrics.decode_tokens == \
        sum(len(r.output) - 1 for r in done)


def test_continuous_decode_token_accounting(small_moe):
    cfg, params = small_moe
    server = ContinuousBatchServer(params, cfg, batch_size=2, max_len=64,
                                   eos_id=NO_EOS)
    for i, (p, b) in enumerate(zip(_prompts(cfg, [8, 10, 6]), [4, 1, 3])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = server.run()
    # first token comes from prefill-on-admit; decode emits the rest
    assert server.metrics.decode_tokens == \
        sum(len(r.output) - 1 for r in done)
    assert all(len(r.output) <= r.max_new_tokens for r in done)


# --------------------------------------------------------------------------
# presets
# --------------------------------------------------------------------------

def test_make_server_presets(small_moe):
    cfg, params = small_moe
    assert isinstance(make_server("continuous", params, cfg, batch_size=1,
                                  max_len=32), ContinuousBatchServer)
    assert isinstance(make_server("wave", params, cfg, batch_size=1,
                                  max_len=32), BatchServer)
    with pytest.raises(ValueError):
        make_server("nope", params, cfg)


@pytest.mark.parametrize("server_cls", [ContinuousBatchServer, BatchServer])
def test_requested_logits_are_the_sampled_rows(small_moe, server_cls):
    """``Request(logits=[])`` records one (V,) row per emitted token — the
    row the greedy token was taken from; other requests record nothing."""
    cfg, params = small_moe
    server = server_cls(params, cfg, batch_size=2, max_len=64,
                        eos_id=NO_EOS)
    for i, p in enumerate(_prompts(cfg, [7, 11, 9])):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=4,
                              logits=[] if i != 1 else None))
    done = sorted(server.run(), key=lambda r: r.rid)
    assert done[1].logits is None
    for r in (done[0], done[2]):
        rows = np.stack(r.logits)
        assert rows.shape == (len(r.output), cfg.vocab)
        assert np.argmax(rows, axis=-1).tolist() == r.output
