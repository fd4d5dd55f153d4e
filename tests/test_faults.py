"""Fault injection + self-healing offload streaming (serving/faults.py,
DESIGN.md §10):

(a) fault-schedule grammar and the guarded link fit (degenerate lstsq is
    rejected, not baked into nonsense constants);
(b) transient faults (stage stall, host read error) are absorbed by
    bounded retry with BIT-identical outputs across every physical mode;
(c) corrupted staged rows are caught by the per-row checksum verify,
    re-staged, and decode stays bit-identical;
(d) a persistent link slowdown walks the ladder to DEGRADED (halved
    moves, re-solved assignment with degraded t_trans, zeroed prefetch)
    and back to HEALTHY once the link heals — outputs exact throughout;
(e) the resident int8 little tier: forced misses under
    ``fallback="little"`` are served from the twins (no host round
    trips) within quantization tolerance, and the full ladder rides
    healthy -> degraded -> little -> healthy with exact outputs outside
    the little rung;
(f) drain-safe telemetry: ``drain()`` windows partition the counter
    stream, ``stats()`` stays monotonic.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, make_smoke
from repro.core.cost_model import CostModel, fit_link_constants
from repro.models.model import init_model
from repro.serving.expert_store import ExpertStore, strip_expert_params
from repro.serving.faults import (DEGRADED, HEALTHY, LITTLE,
                                  DegradationLadder, FaultInjector,
                                  FaultSpec, LinkWatchdog, parse_faults)
from repro.serving.steps import (ResilientDecode, init_serve_state,
                                 make_decode_step, resolve_policy)

MODES = ("blocking", "overlap", "pipelined")


def _cfg(n_routed=16):
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=4)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=n_routed))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _tight_watchdog(store, *, margin=3.0, patience=2, recover_patience=2,
                    calib_n=2, little_after=3, enable_little=True):
    """Swap the store's auto-built watchdog/ladder for test-speed ones
    (tiny calibration window, short patience) so ladder trips happen
    within a handful of steps instead of the serving-scale defaults."""
    wd = LinkWatchdog(store.expert_bytes, store.watchdog.gbps,
                      store.watchdog.latency_s, margin=margin,
                      patience=patience, recover_patience=recover_patience,
                      calib_n=calib_n)
    store.watchdog = wd
    store.ladder = DegradationLadder(wd, little_after=little_after,
                                     enable_little=enable_little)
    return store


def _run_faulted(cfg, params, mode, faults, n_steps=10, B=2,
                 fallback="fetch", tighten=None, force_miss_at=None,
                 seed=7):
    """Drive one physical mode with injected faults through the serving
    hook protocol (pre_step / react / decode / post_dispatch /
    next_target) against a full-resident reference on the same token
    trace.  Returns (per-step logits pairs, store, decode, per-step
    active rung)."""
    pol = resolve_policy("dali", cfg)
    dcfg = pol.dcfg
    store = ExpertStore(params, cfg,
                        n_slots=dcfg.cache_size + dcfg.prefetch_size,
                        mode=mode, faults=faults, retry_backoff_s=1e-4)
    if tighten:
        _tight_watchdog(store, **tighten)
    dec_ref = jax.jit(make_decode_step(cfg, policy=pol))
    decode = ResilientDecode(cfg, policy=pol, offload=store)
    s_ref = init_serve_state(cfg, B, n_steps + 40, policy=pol)
    s_slot = init_serve_state(cfg, B, n_steps + 40, policy=pol,
                              offload=store)
    slim = strip_expert_params(params, cfg)
    rng = np.random.default_rng(seed)
    target = None
    out, rungs = [], []
    for t in range(n_steps):
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
        s_ref["tokens"] = tok
        s_slot["tokens"] = tok
        if t == force_miss_at:
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
        decode.react()
        rungs.append(decode.active)
        s_ref, lg_ref, _ = dec_ref(params, s_ref)
        s_slot, lg_slot, tel = decode(slim, s_slot)
        store.post_dispatch(mode, target)
        jax.block_until_ready(lg_slot)
        target = store.next_target(s_slot, tel)
        out.append((np.asarray(lg_ref), np.asarray(lg_slot)))
    return out, store, decode, rungs


def _rel_err(ref, got):
    return float(np.linalg.norm(got - ref)
                 / max(np.linalg.norm(ref), 1e-9))


# --------------------------------------------------------------------------
# (a) schedule grammar + guarded link fit
# --------------------------------------------------------------------------

def test_parse_faults_grammar():
    specs = parse_faults("link_degrade:x12@8-26,transient_stall@5-7")
    assert specs == [
        FaultSpec("link_degrade", 8, 26, 12.0),
        FaultSpec("transient_stall", 5, 7, 8.0)]
    # bare @START means one step; bare kind uses the preset schedule
    (s,) = parse_faults("read_error@5")
    assert (s.start, s.stop) == (5, 6)
    (p,) = parse_faults("corrupt_rows")
    assert (p.start, p.stop) == (4, 7)
    # pass-throughs
    assert parse_faults(None) == []
    assert parse_faults(specs) == specs
    assert parse_faults(s) == [s]
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_faults("meteor_strike@3")
    with pytest.raises(ValueError, match="bad fault spec"):
        parse_faults("link_degrade:x12@abc-")


def test_fault_spec_active_window():
    s = FaultSpec("link_degrade", 3, 6)
    assert [s.active(t) for t in range(8)] == [
        False, False, False, True, True, True, False, False]


def test_injector_fires_once_per_spec_step():
    inj = FaultInjector("transient_stall@0-3")
    for _ in range(3):
        inj.tick()
        with pytest.raises(Exception):
            inj.maybe_stall()
        inj.maybe_stall()            # same step: already fired -> clean
    inj.tick()                       # step 3: out of the window
    inj.maybe_stall()


def test_fit_link_constants_degenerate_rejected():
    cm = CostModel.for_config(_cfg())
    prof = cm.profile
    # constant sizes: no slope information -> rejected, profile defaults
    gbps, lat, rejected = fit_link_constants(
        [1e6, 1e6, 1e6], [1e-3, 2e-3, 1.5e-3], prof)
    assert rejected
    assert gbps == prof.link_gbps and lat == prof.link_latency_s
    # negative slope (bigger buffer "faster"): rejected too
    gbps, lat, rejected = fit_link_constants(
        [1e6, 2e6, 4e6], [4e-3, 2e-3, 1e-3], prof)
    assert rejected
    # a sane line fits and is NOT rejected
    sizes = np.asarray([1e6, 2e6, 4e6, 8e6])
    gbps, lat, rejected = fit_link_constants(
        sizes, 1e-4 + sizes / 8e9, prof)
    assert not rejected
    assert gbps == pytest.approx(8.0, rel=1e-6)
    assert lat == pytest.approx(1e-4, rel=1e-6)


def test_calibrate_link_records_rejection():
    cm = CostModel.for_config(_cfg())
    # constant transfer sizes carry no slope information: the fit is
    # degenerate by construction and must clamp to profile defaults
    fitted = cm.calibrate_link(n_experts=(4, 4, 4), repeats=1)
    assert fitted.link_fit_rejected
    assert fitted.link_gbps == cm.profile.link_gbps
    assert fitted.link_latency_s == cm.profile.link_latency_s


def test_make_store_rejects_faults_on_modeled(model):
    from repro.serving.scheduler import make_store
    cfg, params = model
    pol = resolve_policy("dali", cfg)
    with pytest.raises(ValueError, match="physical offload mode"):
        make_store("modeled", params, cfg, pol, faults="transient_stall")


# --------------------------------------------------------------------------
# (b) transient faults: bounded retry, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_transient_stall_retries_bit_identical(model, mode):
    cfg, params = model
    pairs, store, _, _ = _run_faulted(cfg, params, mode,
                                      "transient_stall@2-5", n_steps=8)
    st = store.stats()
    assert st["stalls"] >= 3 and st["retries"] >= 3
    assert st["stage_aborts"] == 0      # fire-once -> first retry clears
    assert store.ladder.state == HEALTHY
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


@pytest.mark.parametrize("mode", MODES)
def test_read_error_retries_bit_identical(model, mode):
    cfg, params = model
    pairs, store, _, _ = _run_faulted(cfg, params, mode,
                                      "read_error@1-4", n_steps=7)
    st = store.stats()
    assert st["read_errors"] >= 3 and st["retries"] >= 3
    assert st["stage_aborts"] == 0
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


# --------------------------------------------------------------------------
# (c) corrupted staged rows: caught, re-staged, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_corrupt_rows_caught_and_restaged(model, mode):
    cfg, params = model
    # forced miss mid-window keeps the plans full so every corrupt step
    # actually stages rows for the injector to flip bits in
    pairs, store, _, _ = _run_faulted(cfg, params, mode,
                                      "corrupt_rows@1-8", n_steps=10,
                                      force_miss_at=3)
    st = store.stats()
    assert st["corrupt_caught"] > 0
    assert st["restaged_rows"] >= st["corrupt_caught"]
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


# --------------------------------------------------------------------------
# (d) persistent slowdown: degrade, re-solve, heal — exact throughout
# --------------------------------------------------------------------------

def test_degraded_dcfg_resolves_with_worse_link(model):
    cfg, params = model
    pol = resolve_policy("dali", cfg)
    store = ExpertStore(params, cfg, n_slots=8, faults="link_degrade")
    # feed the watchdog a slow-link window so refit() sees it
    for i in range(8):
        store.watchdog.observe(store.expert_bytes * (1 + i % 3),
                               1e-3 * (1 + i % 3))
    deg = store.degraded_dcfg(pol.dcfg)
    assert deg.prefetch_size == 0
    assert deg.t_trans > pol.dcfg.t_trans
    dpol = store.degraded_policy(pol)
    assert dpol.dcfg is deg or dpol.dcfg == deg
    # a policy without cost constants passes through untouched
    none_pol = resolve_policy("none", cfg)
    assert store.degraded_policy(none_pol) is none_pol


@pytest.mark.parametrize("mode", ["overlap", "pipelined"])
def test_persistent_slowdown_degrades_and_heals_exact(model, mode):
    cfg, params = model
    pairs, store, decode, rungs = _run_faulted(
        cfg, params, mode, "link_degrade:x25@4-14", n_steps=22,
        tighten=dict(enable_little=False))
    # the ladder tripped DEGRADED during the fault and healed after it
    assert DEGRADED in rungs
    assert LITTLE not in rungs
    assert store.ladder.state == HEALTHY
    assert store.watchdog.deadline_misses > 0
    frm_to = [(a, b) for _, a, b in store.ladder.transitions]
    assert (HEALTHY, DEGRADED) in frm_to
    assert (DEGRADED, HEALTHY) in frm_to
    assert store.ladder.time_to_recover() > 0
    # the degraded variant really compiled and ran
    assert "degraded" in decode._variants
    # fetch fallback keeps every step bit-exact, degraded or not
    for i, (ref, slot) in enumerate(pairs):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")


# --------------------------------------------------------------------------
# (e) the little tier
# --------------------------------------------------------------------------

def test_little_fallback_forced_miss_close(model):
    cfg, params = model
    pol = resolve_policy("dali", cfg)
    dcfg = pol.dcfg
    store = ExpertStore(params, cfg,
                        n_slots=dcfg.cache_size + dcfg.prefetch_size,
                        fallback="little")
    dec_ref = jax.jit(make_decode_step(cfg, policy=pol))
    dec = jax.jit(make_decode_step(cfg, policy=pol, offload=store))
    s_ref = init_serve_state(cfg, 2, 48, policy=pol)
    s = init_serve_state(cfg, 2, 48, policy=pol, offload=store)
    slim = strip_expert_params(params, cfg)
    rng = np.random.default_rng(3)
    errs = []
    for t in range(5):
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
        s_ref["tokens"] = tok
        s["tokens"] = tok
        if t == 2:
            s["offload"] = dict(s["offload"],
                                cur=jnp.full_like(s["offload"]["cur"], -1))
            store._cur[:] = -1
        s_ref, lg_ref, _ = dec_ref(params, s_ref)
        s, lg, tel = dec(slim, s)
        errs.append(_rel_err(np.asarray(lg_ref), np.asarray(lg)))
        target = (np.asarray(s["dali"]["resident"])
                  | np.asarray(tel["prefetched"]))
        s["offload"] = store.step_update(s["offload"], target)
    st = store.stats()
    # misses were served from the resident twins: no host round trips
    assert st["fallback_rows"] > 0
    assert st["fallback_fetches"] == 0
    # int8 quality: clearly quantized (nonzero) but nowhere near garbage
    assert 0.0 < max(errs) < 0.2


def test_little_pool_dequantizes_close(model):
    cfg, params = model
    store = ExpertStore(params, cfg, n_slots=4)
    lv = jax.tree.map(np.asarray, store.little_view())
    w = store.host["gate"].astype(np.float32)
    back = lv["gate_q"].astype(np.float32) * lv["gate_s"]
    err = np.abs(back - w).max() / max(np.abs(w).max(), 1e-9)
    assert err < 1.5 / 127          # half-ULP of the int8 grid, scaled


def test_full_ladder_to_little_and_recover(model):
    cfg, params = model
    mode = "pipelined"
    pairs, store, decode, rungs = _run_faulted(
        cfg, params, mode, "link_degrade:x25@4-18", n_steps=28,
        tighten=dict(little_after=2))
    assert DEGRADED in rungs and LITTLE in rungs
    assert store.ladder.state == HEALTHY        # healed by the end
    assert rungs[-1] == HEALTHY
    assert store.stats()["little_steps"] > 0
    frm_to = [(a, b) for _, a, b in store.ladder.transitions]
    assert (DEGRADED, LITTLE) in frm_to
    assert (LITTLE, HEALTHY) in frm_to
    # exact until the little tier engages; after it the KV caches carry
    # quantized-step history, so the stream stays close (not bit-equal)
    first_little = rungs.index(LITTLE)
    assert first_little > 0
    for i, (ref, slot) in enumerate(pairs[:first_little]):
        np.testing.assert_array_equal(ref, slot, err_msg=f"step {i}")
    for i, (ref, slot) in enumerate(pairs[first_little:]):
        assert _rel_err(ref, slot) < 0.2, f"step {first_little + i}"
    # healed: FRESH state decodes bit-identically again — full-quality
    # streaming is restored, which old-cache comparisons cannot show
    pol = resolve_policy("dali", cfg)
    dec_ref = jax.jit(make_decode_step(cfg, policy=pol))
    s_ref = init_serve_state(cfg, 2, 48, policy=pol)
    s_slot = init_serve_state(cfg, 2, 48, policy=pol, offload=store)
    slim = strip_expert_params(params, cfg)
    rng = np.random.default_rng(11)
    target = None
    for t in range(4):
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
        s_ref["tokens"] = tok
        s_slot["tokens"] = tok
        s_slot["offload"] = store.pre_step(s_slot["offload"], mode, target)
        decode.react()
        assert decode.active == HEALTHY
        s_ref, lg_ref, _ = dec_ref(params, s_ref)
        s_slot, lg_slot, tel = decode(slim, s_slot)
        store.post_dispatch(mode, target)
        jax.block_until_ready(lg_slot)
        target = store.next_target(s_slot, tel)
        np.testing.assert_array_equal(np.asarray(lg_ref),
                                      np.asarray(lg_slot),
                                      err_msg=f"post-recovery step {t}")


# --------------------------------------------------------------------------
# (f) drain-safe telemetry
# --------------------------------------------------------------------------

def test_drain_windows_partition_counters(model):
    cfg, params = model
    store = ExpertStore(params, cfg, n_slots=4)
    store._bump("fallback_rows", 3)
    store._bump("retries", 2)
    d1 = store.drain()
    assert d1["fallback_rows"] == 3 and d1["retries"] == 2
    # an empty window drains zeros; totals stay monotonic
    d2 = store.drain()
    assert all(v == 0 for v in d2.values())
    store._bump("fallback_rows", 4)
    assert store.drain()["fallback_rows"] == 4
    assert store.stats()["fallback_rows"] == 7


def test_server_reports_fallback_rate(model):
    from repro.serving.scheduler import ContinuousBatchServer, Request
    cfg, params = model
    rng = np.random.default_rng(3)
    srv = ContinuousBatchServer(params, cfg, batch_size=2, max_len=32,
                                policy="dali", offload="pipelined")
    for i in range(3):
        srv.submit(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab, 10).astype(np.int32),
            max_new_tokens=4))
    done = srv.run()
    assert len(done) == 3
    assert srv.metrics.requests == 3
    assert srv.metrics.offload_tel.get("h2d_rows", 0) > 0
    assert srv.metrics.fallback_rate() >= 0.0
    assert "fb_rows/req" in srv.metrics.summary()
    # folding drained every window: totals match the store's own stats
    assert (srv.metrics.offload_tel["fallback_rows"]
            == srv.store.stats()["fallback_rows"])


def test_server_transient_faults_identical_outputs(model):
    """Server-level recovery contract (the CI tier-2 check in miniature):
    the same workload with and without injected transient stalls produces
    identical per-request outputs."""
    from repro.serving.scheduler import ContinuousBatchServer, Request
    cfg, params = model
    outs = {}
    for faults in (None, "transient_stall@2-4"):
        rng = np.random.default_rng(5)
        srv = ContinuousBatchServer(params, cfg, batch_size=2, max_len=32,
                                    policy="dali", offload="pipelined",
                                    faults=faults)
        for i in range(3):
            srv.submit(Request(
                rid=i,
                prompt=rng.integers(1, cfg.vocab, 10).astype(np.int32),
                max_new_tokens=4))
        done = srv.run()
        outs[faults] = [r.output for r in sorted(done, key=lambda r: r.rid)]
        if faults:
            assert srv.metrics.offload_tel.get("stalls", 0) > 0
    assert outs[None] == outs["transient_stall@2-4"]


# -- per-link faults + watchdog bank (DESIGN.md §13) -----------------------

def test_parse_faults_link_selector():
    from repro.serving.faults import HOST_LINK, FaultParseError
    (s,) = parse_faults("link_degrade[0>3]:x8@20-60")
    assert s == FaultSpec("link_degrade", 20, 60, 8.0, link=(0, 3))
    (s,) = parse_faults("link_degrade[host>*]:x4")
    assert s.link == ("host", "*")
    (s,) = parse_faults("transient_stall[*>2]@5")
    assert s.link == ("*", 2) and (s.start, s.stop) == (5, 6)
    # selector matching: directed, wildcarded, host-defaulted
    s = FaultSpec("link_degrade", link=(0, 3))
    assert s.matches_link((0, 3)) and not s.matches_link((3, 0))
    assert not s.matches_link(None)
    h = FaultSpec("link_degrade", link=("host", "*"))
    assert h.matches_link(None) and h.matches_link(HOST_LINK)
    assert not h.matches_link((0, 3))
    assert FaultSpec("link_degrade").matches_link((5, 6))   # no selector
    for bad in ("link_degrade[0-3]:x8", "link_degrade[0>]:x8",
                "link_degrade[a>b]:x8", "read_error[0>3]@5",
                "corrupt_rows[host>0]"):
        with pytest.raises(FaultParseError):
            parse_faults(bad)
    # FaultParseError is a ValueError: legacy handlers still catch it
    assert issubclass(FaultParseError, ValueError)


def test_injector_link_factor_per_pair():
    inj = FaultInjector("link_degrade[0>3]:x8@0-5")
    inj.tick()
    assert inj.link_factor((0, 3)) == 8.0
    assert inj.link_factor((3, 0)) == 1.0      # directed
    assert inj.link_factor() == 1.0            # host link unselected
    # an unselected spec still hits every link (pre-topology behaviour)
    inj = FaultInjector("link_degrade:x4@0-5")
    inj.tick()
    assert inj.link_factor((0, 3)) == 4.0
    assert inj.link_factor() == 4.0


def test_overlapping_link_windows_take_max():
    inj = FaultInjector("link_degrade[0>3]:x4@0-10,link_degrade[0>3]:x8@3-6")
    factors = []
    for _ in range(10):
        inj.tick()
        factors.append(inj.link_factor((0, 3)))
    # steps 0-2: only x4; 3-5: overlap -> max wins; 6-9: x4 again
    # (the first tick lands on step 0)
    assert factors == [4.0, 4.0, 4.0, 8.0, 8.0, 8.0, 4.0, 4.0, 4.0, 4.0]


def test_fire_once_under_multiple_specs():
    inj = FaultInjector("transient_stall@1-3,transient_stall@2-4")
    fired = []
    for step in range(1, 5):
        inj.tick()
        n = 0
        for _ in range(4):      # each call fires at most one NEW spec
            try:
                inj.maybe_stall()
            except Exception:
                n += 1
        fired.append(n)
    # ticks land on steps 0..3: step 0 has no active spec, step 1 one,
    # step 2 both (each fires once), step 3 one
    assert fired == [0, 1, 2, 1]


def test_watchdog_counters_and_report():
    wd = LinkWatchdog(1 << 20, 10.0, 1e-4, name="0>3", margin=2.0,
                      patience=2, calib_n=2, floor_s=0.0)
    good = wd.expected_s(1 << 20)
    for _ in range(4):
        wd.observe(1 << 20, good)
    assert wd.degrade_events == 0
    for _ in range(3):
        wd.observe(1 << 20, 50 * good)
    rep = wd.report()
    assert rep["name"] == "0>3"
    assert rep["degrade_events"] == 1          # counted at the streak edge
    assert rep["deadline_misses"] == 3
    n_refits = wd.refits
    wd.refit()
    assert wd.refits == n_refits + 1
    assert wd.report()["refits"] == wd.refits


def test_watchdog_bank_degrade_heal_refit():
    from repro.core.cost_model import LinkTopology
    from repro.serving.faults import WatchdogBank
    topo = LinkTopology.homogeneous(4, 10.0, 1e-4)
    bank = WatchdogBank(1 << 20, topo, margin=2.0, patience=2,
                        recover_patience=2, calib_n=2)
    assert len(bank.watchdogs) == 4 * 3
    nb = 1 << 20
    states = []
    for step in range(14):
        for (i, j) in topo.pairs():
            t = topo.pair_time(i, j, nb)
            if (i, j) == (0, 3) and 4 <= step < 9:
                t *= 16                        # injected slow link
            bank.observe((i, j), nb, t)
        bank.on_step(step)
        states.append(bank.state((0, 3)))
    assert DEGRADED in states                  # tripped during the fault
    assert states[-1] == HEALTHY               # healed after it cleared
    assert bank.degraded_pairs() == []
    # every other pair stayed healthy the whole time
    assert all(bank.state(p) == HEALTHY
               for p in topo.pairs() if p != (0, 3))
    # while degraded, refit_topology charges the measured constants
    di = states.index(DEGRADED)
    bank2 = WatchdogBank(1 << 20, topo, margin=2.0, patience=2,
                         recover_patience=2, calib_n=2)
    for step in range(di + 1):
        for (i, j) in topo.pairs():
            t = topo.pair_time(i, j, nb)
            if (i, j) == (0, 3) and step >= 4:
                t *= 16
            bank2.observe((i, j), nb, t)
        bank2.on_step(step)
    assert bank2.state((0, 3)) == DEGRADED
    now = bank2.refit_topology(topo)
    assert now.pair_time(0, 3, nb) > 2 * topo.pair_time(0, 3, nb)
    assert now.pair(1, 2) == topo.pair(1, 2)   # healthy pairs keep base
    rep = bank2.report()
    assert rep["0>3"]["state"] == DEGRADED and rep["0>3"]["degrade_events"]
    assert rep["1>2"]["state"] == HEALTHY
