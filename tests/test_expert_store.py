"""Physical expert residency (serving/expert_store.py, DESIGN.md §8–§9):

(a) slot-pool decode is BIT-identical to full-resident decode over
    Zipf/uniform token traces while the pool streams policy decisions —
    including a forced-miss step that exercises the host fallback (the
    demand-fetch tier keeps the FFN on device, so misses round
    identically);
(b) the host-executed FFN tier ("host" fallback) matches to float32
    tolerance and is actually exercised;
(c) slot-plan lowering: NumPy and JAX mirrors produce identical plans,
    and plan application preserves the pool invariants under
    retire/readmit-style target churn;
(d) servers produce identical outputs whichever --offload mode runs;
(e) pipelined per-layer streaming (DESIGN.md §9): bit-parity against
    full-resident decode AND against the step-boundary-commit modes over
    Zipf/uniform traces incl. forced misses mid-trace, no more forced
    misses than overlap under identical traces, and the t+1-freshness
    regression — a decision staged at step t is readable by step t+1's
    decode (overlap only reaches it at t+2).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, make_smoke
from repro.models.model import apply_model, collect_field, init_model
from repro.serving.expert_store import (ExpertStore, lower_slot_plan,
                                        lower_slot_plan_np,
                                        strip_expert_params)
from repro.serving.steps import (init_serve_state, make_decode_step,
                                 resolve_policy)


def _cfg(n_routed=16):
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=4)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=n_routed))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _tokens(kind, rng, cfg, B):
    """Per-step token draw: uniform over the vocab or Zipf-skewed (token
    ids cluster -> routing concentrates on few experts)."""
    if kind == "zipf":
        t = np.minimum(rng.zipf(1.3, (B, 1)) - 1, cfg.vocab - 1)
    else:
        t = rng.integers(0, cfg.vocab, (B, 1))
    return jnp.asarray(t, jnp.int32)


def _run_pair(cfg, params, kind, n_steps=8, B=2, fallback="fetch",
              force_miss_at=None):
    """Drive full-resident and slot-pool decode on identical token
    traces, streaming the pool from the policy's decisions the way the
    serving loop does.  Returns per-step logits pairs + the store."""
    pol = resolve_policy("dali", cfg)
    dcfg = pol.dcfg
    store = ExpertStore(params, cfg,
                        n_slots=dcfg.cache_size + dcfg.prefetch_size,
                        fallback=fallback)
    dec_ref = jax.jit(make_decode_step(cfg, policy=pol))
    dec_slot = jax.jit(make_decode_step(cfg, policy=pol, offload=store))
    s_ref = init_serve_state(cfg, B, 48, policy=pol)
    s_slot = init_serve_state(cfg, B, 48, policy=pol, offload=store)
    slim = strip_expert_params(params, cfg)
    rng = np.random.default_rng(7)
    out = []
    for t in range(n_steps):
        tok = _tokens(kind, rng, cfg, B)
        s_ref["tokens"] = tok
        s_slot["tokens"] = tok
        if t == force_miss_at:
            # blow every pooled expert away: the step must serve every
            # activated expert from the host fallback tier
            s_slot["offload"] = dict(
                s_slot["offload"],
                cur=jnp.full_like(s_slot["offload"]["cur"], -1))
            store._cur[:] = -1
        s_ref, lg_ref, _ = dec_ref(params, s_ref)
        s_slot, lg_slot, tel = dec_slot(slim, s_slot)
        target = (np.asarray(s_slot["dali"]["resident"])
                  | np.asarray(tel["prefetched"]))
        s_slot["offload"] = store.step_update(s_slot["offload"], target)
        out.append((np.asarray(lg_ref), np.asarray(lg_slot)))
    np.testing.assert_array_equal(
        np.asarray(s_ref["dali"]["resident"]),
        np.asarray(s_slot["dali"]["resident"]))
    return out, store


# --------------------------------------------------------------------------
# (a) bit-parity, demand-fetch tier
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["zipf", "uniform"])
def test_slot_decode_bit_identical(model, kind):
    cfg, params = model
    pairs, store = _run_pair(cfg, params, kind)
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")
    # the pool is smaller than the working set, so the fallback tier must
    # actually have served misses for the parity above to mean anything
    assert store.stats()["fallback_rows"] > 0
    assert store.stats()["h2d_rows"] > 0


def test_forced_miss_step_hits_host_fallback_bitwise(model):
    cfg, params = model
    pairs, store = _run_pair(cfg, params, "uniform", n_steps=5,
                             force_miss_at=2)
    before = store.stats()["fallback_rows"]
    assert before > 0          # the emptied pool forced demand fetches
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


# --------------------------------------------------------------------------
# (b) host-executed FFN tier
# --------------------------------------------------------------------------

def test_host_ffn_fallback_close_and_exercised(model):
    cfg, params = model
    pairs, store = _run_pair(cfg, params, "uniform", n_steps=5,
                             fallback="host", force_miss_at=1)
    assert store.stats()["fallback_rows"] > 0
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_allclose(ref, slot, rtol=2e-4, atol=2e-4,
                                   err_msg=f"step {i}")


def test_dead_slots_do_not_trigger_fallback(model):
    """A retired/empty batch slot decodes garbage tokens; its routed
    experts must NOT count as misses (the policy only sees masked
    workloads, so it would never cache them — every step would pay a
    host round trip for a dead slot)."""
    cfg, params = model
    pol = resolve_policy("dali", cfg)
    store = ExpertStore(params, cfg,
                        n_slots=pol.dcfg.cache_size + pol.dcfg.prefetch_size)
    dec = jax.jit(make_decode_step(cfg, policy=pol, offload=store))
    state = init_serve_state(cfg, 2, 32, policy=pol, per_slot=True,
                             offload=store)
    state["active"] = jnp.asarray([True, False])
    # empty the pool: EVERY activated expert would miss — so the
    # fallback row count tells exactly whose rows reached the host tier
    state["offload"] = dict(state["offload"],
                            cur=jnp.full_like(state["offload"]["cur"], -1))
    store._cur[:] = -1
    state, _, _ = dec(strip_expert_params(params, cfg), state)
    jax.block_until_ready(state["tokens"])
    live_rows = 1 * cfg.moe.top_k * store.n_layers      # one live slot
    assert 0 < store.stats()["fallback_rows"] <= live_rows


def _fetch_step(cfg, params, tokens, resident=None):
    """One continuous-batching decode step, full-resident and through a
    store whose pool holds ``resident`` ((L, E) bools; None = empty, so
    every activated expert misses), on the same live tokens.  Returns
    (reference logits, store logits, the store, each layer's routed
    experts (L, T·K) from the full-resident forward)."""
    pol = resolve_policy("dali", cfg)
    store = ExpertStore(params, cfg,
                        n_slots=pol.dcfg.cache_size + pol.dcfg.prefetch_size)
    B = tokens.shape[0]
    states = []
    for off in (None, store):
        st = init_serve_state(cfg, B, 32, policy=pol, per_slot=True,
                              offload=off)
        st["active"] = jnp.ones((B,), bool)
        st["tokens"] = tokens
        states.append(st)
    s_ref, s_slot = states
    if resident is None:
        resident = np.zeros((store.n_layers, cfg.moe.n_routed), bool)
    s_slot["offload"] = store.init_device_state(resident)
    _, _, infos = apply_model(params, tokens, cfg,
                              positions=s_ref["pos"][:, None],
                              caches=s_ref["caches"], trace=True)
    routed = np.asarray(collect_field(infos, "topk_idx")).reshape(
        store.n_layers, -1)
    _, lg_ref, _ = jax.jit(make_decode_step(cfg, policy=pol))(params, s_ref)
    _, lg_slot, _ = jax.jit(make_decode_step(cfg, policy=pol,
                                             offload=store))(
        strip_expert_params(params, cfg), s_slot)
    return np.asarray(lg_ref), np.asarray(lg_slot), store, routed


def test_fetch_counters_book_host_seconds_and_returned_bytes(model):
    """An emptied pool makes every activated expert miss: each MoE layer
    calls the fetch seam once per DISTINCT routed expert, so
    ``fetch_bytes`` counts the distinct missing experts × expert_bytes
    (no zero or duplicate rows), ``fallback_rows`` every (token, k) row
    and ``fetch_s`` the host time spent inside the callbacks."""
    cfg, params = model
    B = 2
    tokens = jnp.asarray([[7], [11]], jnp.int32)
    _, _, store, routed = _fetch_step(cfg, params, tokens)
    st = store.stats()
    distinct = sum(len(set(r.tolist())) for r in routed)
    # these two tokens share an expert in some layer, so the distinct
    # count sits below the (token, k) rows
    assert distinct < store.n_layers * B * cfg.moe.top_k
    assert st["fallback_fetches"] == distinct
    assert st["fetch_bytes"] == distinct * store.expert_bytes
    assert st["fetch_s"] > 0.0
    assert st["fallback_rows"] == store.n_layers * B * cfg.moe.top_k


def test_shared_missing_expert_fetched_once_bitwise(model):
    """Both live tokens are the same token at the same position, so every
    layer routes them to the same experts: each missing expert is
    shipped once for its two rows, and decode stays bit-equal to
    full-resident decode."""
    cfg, params = model
    B, K = 2, cfg.moe.top_k
    lg_ref, lg_slot, store, routed = _fetch_step(
        cfg, params, jnp.full((B, 1), 17, jnp.int32))
    st = store.stats()
    distinct = sum(len(set(r.tolist())) for r in routed)
    assert distinct == store.n_layers * K        # every row shared
    assert st["fallback_fetches"] == distinct
    assert st["fetch_bytes"] == distinct * store.expert_bytes
    assert st["fallback_rows"] == store.n_layers * B * K
    np.testing.assert_array_equal(lg_ref, lg_slot)


def test_all_hit_step_makes_no_fetch_callback(model):
    """A pool that holds every routed expert serves the step on device:
    the fetch seam never runs, and the logits are bit-equal."""
    cfg, params = model
    tokens = jnp.asarray([[7], [11]], jnp.int32)
    _, _, _, routed = _fetch_step(cfg, params, tokens)
    resident = np.zeros((routed.shape[0], cfg.moe.n_routed), bool)
    for l, r in enumerate(routed):
        resident[l, r] = True
    lg_ref, lg_slot, store, _ = _fetch_step(cfg, params, tokens, resident)
    st = store.stats()
    assert st["fallback_fetches"] == st["fallback_rows"] == 0
    assert st["fetch_bytes"] == 0 and st["fetch_s"] == 0.0
    np.testing.assert_array_equal(lg_ref, lg_slot)


def test_fetch_callback_returns_views_of_the_host_store(model):
    """The decode miss callback hands the runtime the host store's own
    (contiguous) expert blocks: no host copy."""
    cfg, params = model
    store = ExpertStore(params, cfg, n_slots=4)
    out = store.fetch_weights_cb(np.int32(1), np.int32(5), np.int32(2))
    for w, k in zip(out, ("gate", "up", "down")):
        assert np.shares_memory(w, store.host[k])
        assert w.flags["C_CONTIGUOUS"]
        np.testing.assert_array_equal(w, store.host[k][1, 5])
    st = store.stats()
    assert st["fallback_fetches"] == 1 and st["fallback_rows"] == 2
    assert st["fetch_bytes"] == store.expert_bytes


def test_bad_fallback_rejected(model):
    cfg, params = model
    with pytest.raises(ValueError, match="fetch"):
        ExpertStore(params, cfg, n_slots=4, fallback="bogus")


# --------------------------------------------------------------------------
# (c) slot-plan lowering: np/jax parity + invariants under churn
# --------------------------------------------------------------------------

def _random_targets(rng, L, E, S, n_steps):
    """Target sequences shaped like retire/readmit churn: the wanted set
    drifts a few experts per step (cache swaps + prefetch churn) with
    occasional bursts (a retirement flips the whole batch mix)."""
    want = np.zeros((L, E), bool)
    for l in range(L):
        want[l, rng.choice(E, S - 1, replace=False)] = True
    steps = []
    for t in range(n_steps):
        for l in range(L):
            flips = rng.integers(1, 4) if t % 5 else rng.integers(4, S)
            on = np.where(want[l])[0]
            off = np.where(~want[l])[0]
            drop = rng.choice(on, min(flips, len(on)), replace=False)
            add = rng.choice(off, min(flips, len(off)), replace=False)
            want[l, drop] = False
            want[l, add] = True
            # keep |target| <= S (pool capacity contract)
            over = np.where(want[l])[0]
            if len(over) > S:
                want[l, rng.choice(over, len(over) - S, replace=False)] = False
        steps.append(want.copy())
    return steps


def test_slot_plan_np_jax_parity_and_invariants():
    L, E, S, M = 3, 16, 6, 3
    rng = np.random.default_rng(11)
    cur = np.full((L, S), -1, np.int32)
    for l in range(L):
        cur[l, :4] = rng.choice(E, 4, replace=False)
    lower_j = jax.jit(lower_slot_plan, static_argnums=2)
    for target in _random_targets(rng, L, E, S, n_steps=24):
        new_np, e_np, s_np, v_np = lower_slot_plan_np(cur, target, M)
        new_j, e_j, s_j, v_j = jax.tree.map(
            np.asarray, lower_j(jnp.asarray(cur), jnp.asarray(target), M))
        np.testing.assert_array_equal(v_np, v_j)
        np.testing.assert_array_equal(e_np[v_np], e_j[v_j])
        np.testing.assert_array_equal(s_np[v_np], s_j[v_j])
        np.testing.assert_array_equal(new_np, new_j)
        for l in range(L):
            ins_e = e_np[l][v_np[l]]
            ins_s = s_np[l][v_np[l]]
            assert len(ins_e) <= M
            # inserted experts were wanted and not already pooled
            assert target[l][ins_e].all()
            assert not np.isin(ins_e, cur[l]).any()
            # victims were free or evicted out of the target
            occupied = cur[l][ins_s]
            evicted = occupied[occupied >= 0]
            assert not target[l][evicted].any()
            # no slot/expert used twice in one plan
            assert len(set(ins_s.tolist())) == len(ins_s)
            assert len(set(ins_e.tolist())) == len(ins_e)
            # pool never holds an expert twice
            pooled = new_np[l][new_np[l] >= 0]
            assert len(set(pooled.tolist())) == len(pooled)
        cur = new_np


def test_step_update_converges_to_target(model):
    """Bounded per-step moves: repeated step_update calls against a fixed
    target make the pool converge to exactly that target."""
    cfg, params = model
    E = cfg.moe.n_routed
    store = ExpertStore(params, cfg, n_slots=6, max_moves=2)
    rng = np.random.default_rng(5)
    resident = np.zeros((store.n_layers, E), bool)
    for l in range(store.n_layers):
        resident[l, rng.choice(E, 4, replace=False)] = True
    off = store.init_device_state(resident)
    target = np.zeros_like(resident)
    for l in range(store.n_layers):
        target[l, rng.choice(E, 6, replace=False)] = True
    for _ in range(6):                      # 6 slots / 2 moves -> <= 3 + slack
        off = store.step_update(off, target)
    cur = np.asarray(off["cur"])
    for l in range(store.n_layers):
        pooled = set(cur[l][cur[l] >= 0].tolist())
        assert pooled == set(np.where(target[l])[0].tolist())
    np.testing.assert_array_equal(cur, store._cur)   # mirror in lockstep
    # pool rows really hold the experts the table claims
    g = np.asarray(off["gate"])
    for l in range(store.n_layers):
        for s in range(store.n_slots):
            e = cur[l, s]
            if e >= 0:
                np.testing.assert_array_equal(g[l, s],
                                              store.host["gate"][l, e])


# --------------------------------------------------------------------------
# (d) servers: identical outputs whichever offload mode runs
# --------------------------------------------------------------------------

def test_server_outputs_identical_across_offload_modes(model):
    from repro.serving.scheduler import ContinuousBatchServer, Request
    cfg, params = model
    outs = {}
    for mode in ("modeled", "blocking", "overlap", "pipelined"):
        rng = np.random.default_rng(3)
        srv = ContinuousBatchServer(params, cfg, batch_size=2, max_len=32,
                                    policy="dali", offload=mode)
        for i in range(4):
            srv.submit(Request(
                rid=i, prompt=rng.integers(1, cfg.vocab, 10).astype(np.int32),
                max_new_tokens=5))
        done = srv.run()
        outs[mode] = [r.output for r in sorted(done, key=lambda r: r.rid)]
        if mode != "modeled":
            assert srv.store.stats()["h2d_rows"] > 0
    assert (outs["modeled"] == outs["blocking"] == outs["overlap"]
            == outs["pipelined"])


def test_offload_requires_scheduling_policy(model):
    from repro.serving.scheduler import ContinuousBatchServer
    cfg, params = model
    with pytest.raises(ValueError, match="scheduling policy"):
        ContinuousBatchServer(params, cfg, batch_size=2, max_len=32,
                              policy="none", offload="overlap")
    with pytest.raises(ValueError, match="modeled"):
        ContinuousBatchServer(params, cfg, batch_size=2, max_len=32,
                              policy="dali", offload="bogus")


# --------------------------------------------------------------------------
# (e) pipelined per-layer streaming (DESIGN.md §9)
# --------------------------------------------------------------------------

def _run_hooked(cfg, params, mode, kind, n_steps=8, B=2,
                force_miss_at=None):
    """Drive one --offload mode through the serving-loop hook protocol
    (pre_step / decode / post_dispatch / next_target) against a
    full-resident reference on the same token trace — the exact loop
    scheduler.py and launch/serve.py run.  Returns per-step logits pairs
    + the store."""
    pol = resolve_policy("dali", cfg)
    dcfg = pol.dcfg
    store = ExpertStore(params, cfg,
                        n_slots=dcfg.cache_size + dcfg.prefetch_size,
                        mode=mode)
    dec_ref = jax.jit(make_decode_step(cfg, policy=pol))
    dec_slot = jax.jit(make_decode_step(cfg, policy=pol, offload=store))
    s_ref = init_serve_state(cfg, B, 48, policy=pol)
    s_slot = init_serve_state(cfg, B, 48, policy=pol, offload=store)
    slim = strip_expert_params(params, cfg)
    rng = np.random.default_rng(7)
    target = None
    out = []
    for t in range(n_steps):
        tok = _tokens(kind, rng, cfg, B)
        s_ref["tokens"] = tok
        s_slot["tokens"] = tok
        if t == force_miss_at:
            # blow every pooled expert away mid-trace; for pipelined the
            # generation selector is the inject table, so empty that too
            # (weights buffers can stay — inj_of = -1 means no override
            # row is ever gathered)
            off = dict(s_slot["offload"],
                       cur=jnp.full_like(s_slot["offload"]["cur"], -1))
            if "inject" in off:
                off["inject"] = dict(
                    off["inject"],
                    cur=jnp.full_like(off["inject"]["cur"], -1),
                    inj_of=jnp.full_like(off["inject"]["inj_of"], -1))
            s_slot["offload"] = off
            store._cur[:] = -1
        s_slot["offload"] = store.pre_step(s_slot["offload"], mode, target)
        s_ref, lg_ref, _ = dec_ref(params, s_ref)
        s_slot, lg_slot, tel = dec_slot(slim, s_slot)
        store.post_dispatch(mode, target)
        jax.block_until_ready(lg_slot)
        target = store.next_target(s_slot, tel)
        out.append((np.asarray(lg_ref), np.asarray(lg_slot)))
    return out, store


@pytest.mark.parametrize("kind", ["zipf", "uniform"])
def test_pipelined_decode_bit_identical(model, kind):
    cfg, params = model
    pairs, store = _run_hooked(cfg, params, "pipelined", kind)
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")
    # misses + streaming both happened, so the parity is load-bearing
    assert store.stats()["fallback_rows"] > 0
    assert store.stats()["h2d_rows"] > 0
    # the fold + stage run as one fused dispatch timed under stage_s
    assert store.stats()["stage_s"] > 0.0


def test_pipelined_forced_miss_mid_trace_bitwise(model):
    cfg, params = model
    pairs, store = _run_hooked(cfg, params, "pipelined", "uniform",
                               n_steps=6, force_miss_at=3)
    assert store.stats()["fallback_rows"] > 0
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


def test_pipelined_matches_boundary_commit_modes(model):
    """Per-layer commit vs step-boundary commit: identical logits every
    step, and the shrunken decision→visibility lag means pipelined pays
    the same forced misses as blocking (t+1 fresh) and no more than
    overlap (t+2 fresh)."""
    cfg, params = model
    runs = {m: _run_hooked(cfg, params, m, "zipf", n_steps=10)
            for m in ("blocking", "overlap", "pipelined")}
    for i in range(10):
        np.testing.assert_array_equal(
            runs["pipelined"][0][i][1], runs["blocking"][0][i][1],
            err_msg=f"pipelined vs blocking, step {i}")
        np.testing.assert_array_equal(
            runs["pipelined"][0][i][1], runs["overlap"][0][i][1],
            err_msg=f"pipelined vs overlap, step {i}")
    miss = {m: st.stats()["fallback_rows"] for m, (_, st) in runs.items()}
    assert miss["pipelined"] == miss["blocking"]
    assert miss["pipelined"] <= miss["overlap"]


def test_pipelined_decision_readable_at_t_plus_1(model):
    """Freshness regression: a decision staged by pre_step at step t is
    already selectable by step t's decode (i.e. by the pool read one
    step after the telemetry that produced it), whereas overlap's staged
    copy only reaches the live generation at the SECOND pre_step."""
    cfg, params = model
    E = cfg.moe.n_routed
    e_star = E - 1
    resident = np.zeros((4, E), bool)       # n_layers = 4 in _cfg()
    resident[:, :2] = True

    store = ExpertStore(params, cfg, n_slots=4, max_moves=2,
                        mode="pipelined")
    off = store.init_device_state(resident)
    target = resident.copy()
    target[:, e_star] = True
    off = store.pre_step(off, "pipelined", target)
    inj = jax.tree.map(np.asarray, off["inject"])
    for l in range(store.n_layers):
        assert (inj["cur"][l] == e_star).any(), f"layer {l}"
        m = int(inj["inj_of"][l, e_star])
        s = int(np.nonzero(inj["cur"][l] == e_star)[0][0])
        if m >= 0:
            # the override row the decode gathers is the real host weight
            np.testing.assert_array_equal(inj["gate"][m],
                                          store.host["gate"][l, e_star])
        else:
            # this layer's chunk already folded (the global buffer is
            # smaller than the plan): its POOL row is already fresh
            np.testing.assert_array_equal(np.asarray(off["gate"])[l, s],
                                          store.host["gate"][l, e_star])

    store_o = ExpertStore(params, cfg, n_slots=4, max_moves=2,
                          mode="overlap")
    off_o = store_o.init_device_state(resident)
    off_o = store_o.pre_step(off_o, "overlap", target)   # nothing staged yet
    store_o.post_dispatch("overlap", target)             # stage behind step t
    assert not (np.asarray(off_o["cur"]) == e_star).any()
    off_o = store_o.pre_step(off_o, "overlap", target)   # boundary commit
    assert (np.asarray(off_o["cur"]) == e_star).any()


# --------------------------------------------------------------------------
# (f) full-width hand-off: expert stacks on the host, device copies freed
# --------------------------------------------------------------------------

def test_host_expert_params_frees_device_copies(model):
    from repro.serving.expert_store import host_expert_params
    cfg, params = model
    own = jax.tree.map(jnp.copy, params)          # the helper deletes these
    dev_stacks = [own["scan"][0]["mlp"][k] for k in ("gate", "up", "down")]
    host = host_expert_params(own, cfg)
    assert all(a.is_deleted() for a in dev_stacks)
    mlp = host["scan"][0]["mlp"]
    assert all(isinstance(mlp[k], np.ndarray) for k in ("gate", "up", "down"))
    assert isinstance(mlp["router"], jax.Array)   # non-expert weights stay
    a = ExpertStore(params, cfg, n_slots=4)
    b = ExpertStore(host, cfg, n_slots=4)
    for k in ("gate", "up", "down"):
        np.testing.assert_array_equal(a.host[k], b.host[k])
    # one scan position holds every layer: the store takes it without a copy
    assert b.host["gate"] is mlp["gate"]


def test_server_from_host_params_matches_resident(model):
    """A pipelined server built from host-resident expert stacks serves
    exactly what the full-resident server serves, logits included."""
    from repro.serving.expert_store import host_expert_params
    from repro.serving.scheduler import Request
    from repro.serving.spec import OffloadSpec, ServeSpec
    cfg, params = model
    runs = {}
    for mode, p in (("modeled", params),
                    ("pipelined", host_expert_params(
                        jax.tree.map(jnp.copy, params), cfg))):
        rng = np.random.default_rng(5)
        srv = ServeSpec(cfg=cfg, policy="dali", batch_size=2, max_len=32,
                        offload=OffloadSpec(mode=mode)).resolve(p).server()
        for i in range(3):
            srv.submit(Request(
                rid=i, prompt=rng.integers(2, cfg.vocab, 9).astype(np.int32),
                max_new_tokens=4, logits=[]))
        runs[mode] = sorted(srv.run(), key=lambda r: r.rid)
    for a, b in zip(runs["modeled"], runs["pipelined"]):
        assert a.output == b.output
        np.testing.assert_array_equal(np.stack(a.logits), np.stack(b.logits))


@pytest.mark.parametrize("mode", ["blocking", "pipelined"])
def test_state_shapes_match_seeded_state(model, mode):
    """``state_shapes`` (lowering without seeding a pool) describes
    exactly the tree ``init_device_state`` builds."""
    cfg, params = model
    store = ExpertStore(params, cfg, n_slots=4, mode=mode)
    pol = resolve_policy("dali", cfg)
    off = store.init_device_state(np.asarray(pol.init()["resident"]))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), off)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), store.state_shapes())
    assert got == want
