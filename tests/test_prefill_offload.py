"""Prefill through the physical offload path (DESIGN.md §11):

(a) wave prefill through the slot pool is BIT-identical to full-resident
    prefill (tokens AND every cache leaf) in every physical mode, with
    the served params STRIPPED of on-device expert stacks;
(b) right-padded admission prefill (prefill-on-admit) holds the same
    bit-parity — pad tokens route and stream like real ones;
(c) a forced-miss prefill (pool emptied) streams EVERY activated expert
    through ``prefill_rows``-sized waves and stays bit-exact;
(d) the chunked ``apply_moe`` path (prompt tokens > MOE_CHUNK_TOKENS,
    ragged tail) threads the slot state through the chunk scan with the
    same parity;
(e) the "host" miss tier runs the missing experts' capacity buckets on
    the host to float32 tolerance and is actually exercised;
(f) sliding-window configs (exact-length admissions) and whole servers
    constructed through ``ServeSpec.resolve`` serve bit-identically to
    the full-resident "modeled" server, stripped params and all.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, make_smoke
from repro.models.model import init_caches, init_model
from repro.serving.spec import OffloadSpec, ServeSpec
from repro.serving.steps import make_admit_prefill, make_prefill_step

PHYSICAL = ("blocking", "overlap", "pipelined")
MAX_LEN = 48


def _cfg(n_routed=16):
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=4)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=n_routed))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _resolve(cfg, params, mode, **off_kw):
    return ServeSpec(cfg=cfg, policy="dali", batch_size=2, max_len=MAX_LEN,
                     offload=OffloadSpec(mode=mode, **off_kw)
                     ).resolve(params)


def _assert_tree_equal(ref, got):
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _wreck_pool(rs, off):
    """Empty the pool (and a pipelined store's inject seam): EVERY
    activated expert of the next sweep must miss and stream."""
    off = dict(off, cur=jnp.full_like(off["cur"], -1))
    rs.store._cur[:] = -1
    if "inject" in off:
        inj = dict(off["inject"],
                   cur=jnp.full_like(off["inject"]["cur"], -1),
                   inj_of=jnp.full_like(off["inject"]["inj_of"], -1))
        off["inject"] = inj
    return off


def _has_expert_stacks(params):
    # scanned layers stack expert weights as (L, E, d_model, d_ff);
    # strip_expert_params drops the gate/up/down keys entirely
    mlp = params["scan"][0]["mlp"]
    return any(k in mlp for k in ("gate", "up", "down"))


# --------------------------------------------------------------------------
# (a) wave-prefill bit-parity, stripped params, every physical mode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", PHYSICAL)
def test_prefill_slot_bit_identical(model, mode):
    cfg, params = model
    B, S = 2, 24
    toks = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab, (B, S)), jnp.int32)
    caches0 = init_caches(cfg, B, MAX_LEN)
    ref_tok, ref_caches, _ = jax.jit(make_prefill_step(cfg, MAX_LEN))(
        params, toks, caches0)

    rs = _resolve(cfg, params, mode)
    assert not _has_expert_stacks(rs.params)     # resolve() stripped them
    state = rs.init_state(batch=B)
    tok, caches, _ = jax.jit(rs.prefill_step())(
        rs.params, toks, caches0, None, state["offload"])
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok))
    _assert_tree_equal(ref_caches, caches)
    # the pool is smaller than the activated set, so waves must have
    # streamed misses for the parity above to mean anything
    st = rs.store.stats()
    assert st["prefill_fetch_rows"] > 0 and st["prefill_waves"] > 0


# --------------------------------------------------------------------------
# (b) right-padded admission prefill parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["blocking", "pipelined"])
def test_admit_prefill_slot_bit_identical(model, mode):
    cfg, params = model
    Sb, L = 16, 11                               # bucketed, right-padded
    toks = np.zeros((1, Sb), np.int32)
    toks[0, :L] = np.random.default_rng(5).integers(1, cfg.vocab, L)
    toks = jnp.asarray(toks)
    length = jnp.asarray(L, jnp.int32)
    caches0 = init_caches(cfg, 1, MAX_LEN)
    ref_tok, ref_caches, _ = jax.jit(make_admit_prefill(cfg))(
        params, toks, caches0, length)

    rs = _resolve(cfg, params, mode)
    state = rs.init_state(batch=1)
    tok, caches, _ = jax.jit(rs.admit_prefill())(
        rs.params, toks, caches0, length, state["offload"])
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok))
    _assert_tree_equal(ref_caches, caches)
    assert rs.store.stats()["prefill_fetch_rows"] > 0


# --------------------------------------------------------------------------
# (c) forced-miss sweep: everything streams, still bit-exact
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["blocking", "pipelined"])
def test_prefill_forced_miss_streams_all_activated(model, mode):
    cfg, params = model
    B, S = 2, 20
    toks = jnp.asarray(np.random.default_rng(9).integers(
        1, cfg.vocab, (B, S)), jnp.int32)
    caches0 = init_caches(cfg, B, MAX_LEN)
    ref_tok, ref_caches, _ = jax.jit(make_prefill_step(cfg, MAX_LEN))(
        params, toks, caches0)

    # prefill_rows=4 << E=16: an all-miss layer needs several waves
    rs = _resolve(cfg, params, mode, prefill_rows=4)
    state = rs.init_state(batch=B)
    off = _wreck_pool(rs, state["offload"])
    tok, caches, _ = jax.jit(rs.prefill_step())(
        rs.params, toks, caches0, None, off)
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok))
    _assert_tree_equal(ref_caches, caches)
    st = rs.store.stats()
    n_moe = rs.store.n_layers
    # every layer's activated set missed entirely -> multiple waves per
    # layer at 4 rows/wave, and streamed rows cover > one wave's worth
    assert st["prefill_waves"] > n_moe
    assert st["prefill_fetch_rows"] > 4
    assert st["prefill_host_rows"] == 0          # fetch tier stays exact


# --------------------------------------------------------------------------
# (d) chunked apply_moe path (ragged tail) through the slot state
# --------------------------------------------------------------------------

def test_prefill_chunked_slot_parity(model, monkeypatch):
    import repro.models.moe as moe_mod
    cfg, params = model
    # B*S = 20 tokens over chunks of 8 -> 3 chunks with a ragged tail
    monkeypatch.setattr(moe_mod, "MOE_CHUNK_TOKENS", 8)
    B, S = 2, 10
    toks = jnp.asarray(np.random.default_rng(13).integers(
        1, cfg.vocab, (B, S)), jnp.int32)
    caches0 = init_caches(cfg, B, MAX_LEN)
    # the reference traces under the same chunking, so the parity below
    # isolates the slot path (not chunked-vs-unchunked float order)
    ref_tok, ref_caches, _ = jax.jit(make_prefill_step(cfg, MAX_LEN))(
        params, toks, caches0)

    rs = _resolve(cfg, params, "pipelined")
    state = rs.init_state(batch=B)
    tok, caches, _ = jax.jit(rs.prefill_step())(
        rs.params, toks, caches0, None, state["offload"])
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok))
    _assert_tree_equal(ref_caches, caches)
    assert rs.store.stats()["prefill_waves"] > 0


# --------------------------------------------------------------------------
# (e) host miss tier: allclose, actually exercised
# --------------------------------------------------------------------------

def test_prefill_host_tier_allclose(model):
    cfg, params = model
    B, S = 2, 20
    toks = jnp.asarray(np.random.default_rng(17).integers(
        1, cfg.vocab, (B, S)), jnp.int32)
    caches0 = init_caches(cfg, B, MAX_LEN)
    ref_tok, ref_caches, _ = jax.jit(make_prefill_step(cfg, MAX_LEN))(
        params, toks, caches0)

    rs = _resolve(cfg, params, "blocking", fallback="host")
    state = rs.init_state(batch=B)
    off = _wreck_pool(rs, state["offload"])      # all activated miss
    tok, caches, _ = jax.jit(rs.prefill_step())(
        rs.params, toks, caches0, None, off)
    # materialize BEFORE reading counters — dispatch is async, so the
    # callbacks only have provably fired once the outputs are ready
    for a, b in zip(jax.tree_util.tree_leaves(ref_caches),
                    jax.tree_util.tree_leaves(caches)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(ref_tok), np.asarray(tok))
    st = rs.store.stats()
    assert st["prefill_host_rows"] > 0
    assert st["prefill_fetch_rows"] == 0         # host tier, not fetch


# --------------------------------------------------------------------------
# (f) end-to-end: spec-built servers, sliding-window admissions
# --------------------------------------------------------------------------

def _serve(cfg, params, mode, *, prompts, max_new=3, max_len=40):
    from repro.serving.scheduler import Request
    spec = ServeSpec(cfg=cfg, server="continuous", policy="dali",
                     batch_size=2, max_len=max_len,
                     offload=OffloadSpec(mode=mode))
    server = spec.resolve(params).server()
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    done = server.run()
    return server, {r.rid: r.output for r in done}


def test_server_e2e_stripped_params_matches_modeled(model):
    cfg, params = model
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32)
               for n in (9, 13, 7)]
    _, ref = _serve(cfg, params, "modeled", prompts=prompts)
    for mode in PHYSICAL:
        server, out = _serve(cfg, params, mode, prompts=prompts)
        assert not _has_expert_stacks(server.params), mode
        assert server.store.stats()["prefill_waves"] > 0, mode
        assert out == ref, mode


@pytest.mark.parametrize("mode", PHYSICAL)
def test_admission_keeps_pool_buffers(model, mode):
    """An admission hands the slot pool through untouched: the pool never
    enters the admit jit, whose pass-through outputs would be copies (at
    full width one more pool's worth of device memory per admission)."""
    from repro.serving.scheduler import Request
    cfg, params = model
    server = _resolve(cfg, params, mode).server()
    state = server._resolved.init_state(per_slot=True)
    prompt = np.random.default_rng(29).integers(1, cfg.vocab, 9)
    new = server._admit_request(
        state, Request(rid=0, prompt=prompt.astype(np.int32),
                       max_new_tokens=2), slot=1)
    old_leaves = jax.tree_util.tree_leaves(state["offload"])
    new_leaves = jax.tree_util.tree_leaves(new["offload"])
    assert len(old_leaves) == len(new_leaves)
    assert all(a is b for a, b in zip(old_leaves, new_leaves))
    assert bool(new["active"][1]) and not bool(new["active"][0])
    assert int(new["pos"][1]) == len(prompt)


def test_server_sliding_window_exact_admissions(model):
    """sliding_window < max_len forces exact-length admission prefills
    (no bucket padding — right-pad would evict real prompt tokens from
    the rolling cache); the slot-streamed sweep must stay bit-exact
    there too."""
    cfg, params = model
    cfg_sw = cfg.replace(attn=dataclasses.replace(cfg.attn,
                                                  sliding_window=16))
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg_sw.vocab, n).astype(np.int32)
               for n in (11, 19)]
    _, ref = _serve(cfg_sw, params, "modeled", prompts=prompts)
    server, out = _serve(cfg_sw, params, "pipelined", prompts=prompts)
    assert server._exact_prefill                  # the path under test
    assert out == ref
