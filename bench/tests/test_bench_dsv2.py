"""DeepSeek-V2 at tiny widths on the CPU (``data/tiny-dsv2-host.json``:
MLA with YaRN, a dense layer 0, 8 routed experts top-6 unrenormalised, 2
shared): the family's program weights against the reference's leaves,
the served path (``ServeSpec``, continuous, host store and resident)
against the plain float32 reference, and the comparison that decides
``correct`` failing for the float8 control and for a broken timed path."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from bench import check, run, system
from bench import weights as W
from bench.families import deepseek_v2 as fam
from bench.reference import deepseek_v2 as ref
from bench.tests import tiny
from bench.tests.test_bench_control import (_prefill_token_altered,
                                            _state_unchanged, _token_altered)

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 77
# Per position, the largest |program - reference logit| over the
# vocabulary; the median over positions is held to MEDIAN_TOL.  bfloat16
# over 3 tiny layers reads 0.029-0.035 (logit std ~1.15; 20 seeds, CPU),
# the float8 control 0.33-0.42, the program without YaRN 0.66-0.79.  The
# largest over positions reads 0.04-0.18 in sound runs: a bf16 near tie of
# the router sends a token to another of 8 experts, and the logits there
# move by much more than rounding; MAX_TOL only catches a broken path
# (without YaRN it reads 1.4-2.0).
MEDIAN_TOL = 0.1
MAX_TOL = 0.5
# the cell's limits at this size, from tiny readings (CPU, windows closed
# by count, 5 seeds): sound runs read logit_err 0.028-0.032 and
# widest_gap 0-0.083, the float8 control logit_err 0.31-0.34, an altered
# token a widest gap of 5.5-6.9
LIMITS = {"compare": {"widest_gap": 2.0, "logit_err": 0.12},
          "record_logits": True, "sample_tokens": 64, "max_requests": 16}
CONTROL_SEEDS = (2**31 + 11, 3)


def tiny_dsv2(offload="pipelined"):
    m = json.load(open(os.path.join(DATA, "tiny-dsv2-host.json")))
    m["name"] = "tiny-dsv2-host"
    m["deployment"] = dict(m["deployment"], offload=offload)
    return m


def serve(m, prompts, new_tokens):
    from repro.serving.scheduler import Request
    params = system.program_params(m, SEED)
    srv = system.build_server(m, {"slots": 2, "max_len": 64,
                                  "min_bucket": 16}, params)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens, logits=[])
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    done = {r.rid: r for r in srv.run()}
    return [done[i] for i in range(len(prompts))], srv


def prompts(m, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, m["vocab_size"], n).astype(np.int32)
            for n in (20, 33, 9)]


def test_program_weights_are_the_reference_leaves():
    m = tiny_dsv2()
    p = system.program_params(m, SEED)
    base = W.base_key(*W.seed_halves(SEED))

    def leaf(name, layer=-1, expert=0):
        return np.asarray(W.make_leaf(base, m, name, layer, expert))

    H, nope, rp, vd, R = (m["num_attention_heads"], m["qk_nope_head_dim"],
                          m["qk_rope_head_dim"], m["v_head_dim"],
                          m["kv_lora_rank"])
    order = fam.rope_order(rp)
    assert list(order) == list(range(0, rp, 2)) + list(range(1, rp, 2))
    for l, blk in ((0, p["prefix"][0]), (2, jax.tree.map(
            lambda a: a[1], p["scan"][0]))):
        mix = {k: np.asarray(v) for k, v in blk["mixer"].items()}
        q = leaf("q_proj", l).reshape(-1, H, nope + rp)
        wq = mix["wq"].reshape(-1, H, nope + rp)
        np.testing.assert_array_equal(wq[..., :nope], q[..., :nope])
        np.testing.assert_array_equal(wq[..., nope:], q[..., nope:][..., order])
        dkv = leaf("kv_a_proj_with_mqa", l)
        np.testing.assert_array_equal(mix["wdkv"][:, :R], dkv[:, :R])
        np.testing.assert_array_equal(mix["wdkv"][:, R:], dkv[:, R:][:, order])
        kvb = leaf("kv_b_proj", l).reshape(R, H, nope + vd)
        np.testing.assert_array_equal(mix["wuk"].reshape(R, H, nope),
                                      kvb[..., :nope])
        np.testing.assert_array_equal(mix["wuv"].reshape(R, H, vd),
                                      kvb[..., nope:])
        np.testing.assert_array_equal(mix["ckv_norm"],
                                      leaf("kv_a_layernorm", l))
        np.testing.assert_array_equal(mix["wo"], leaf("o_proj", l))
        np.testing.assert_array_equal(np.asarray(blk["norm2"]["w"]),
                                      leaf("ffn_norm", l))
    np.testing.assert_array_equal(np.asarray(p["prefix"][0]["mlp"]["gate"]),
                                  leaf("dense_gate", 0))
    mlp = p["scan"][0]["mlp"]
    for l, e in ((1, 0), (2, 7), (1, 5)):
        for prog, name in (("gate", "w_gate"), ("up", "w_up"),
                           ("down", "w_down")):
            np.testing.assert_array_equal(np.asarray(mlp[prog][l - 1, e]),
                                          leaf(name, l, e))
    np.testing.assert_array_equal(np.asarray(mlp["shared"]["down"][1]),
                                  leaf("shared_down", 2))
    np.testing.assert_array_equal(np.asarray(mlp["router"][0]),
                                  leaf("router", 1).astype(np.float32))


@pytest.mark.parametrize("offload", ["pipelined", "modeled"])
def test_served_logits_follow_the_reference(offload):
    """Prefill, then decode through the latent cache, against the
    reference's full forward pass of each prompt and its served tokens."""
    m = tiny_dsv2(offload)
    ps = prompts(m)
    done, srv = serve(m, ps, 6)
    served = [(p, r.output) for p, r in zip(ps, done)]
    seqs, rows, targets = check.teacher_forced(served, range(len(served)))
    want = ref.logits_at(m, SEED, seqs, rows)
    err = []
    for r, got in zip(want, done):
        prog = np.stack(got.logits)[:, :m["vocab_size"]]
        assert prog.shape == r.shape
        err.append(np.abs(prog - r).max(axis=1))
    err = np.concatenate(err)
    assert np.median(err) < MEDIAN_TOL and err.max() < MAX_TOL
    assert check.gaps(want, targets).max() < MAX_TOL
    if offload == "pipelined":
        s = srv.store.stats()
        # every decode step's live rows at top-6 in 2 MoE layers; the
        # 5-slot pool of 8 experts misses some of them
        assert s["routed_rows"] == 6 * 2 * sum(len(r.output) - 1
                                               for r in done)
        assert 0 < s["fallback_rows"] < s["routed_rows"]


def test_tolerance_fails_the_float8_control_and_the_yarn_less_rope():
    """The median tolerance is tight enough that the reference in float8,
    and the program's own forward pass without YaRN (its rope and softmax
    scale before this configuration), both fail it."""
    from repro.models.model import apply_model
    m = tiny_dsv2("modeled")
    ps = prompts(m, 9)
    rows = [np.arange(len(p)) for p in ps]
    want = ref.logits_at(m, SEED, ps, rows)

    def median_err(got):
        return np.median(np.concatenate([np.abs(a - b).max(axis=1)
                                         for a, b in zip(got, want)]))

    assert median_err(ref.logits_at(m, SEED, ps, rows, "fp8")) > MEDIAN_TOL
    cfg = system.model_config(m)
    params = system.program_params(m, SEED)
    for yarn, fails in ((cfg.attn.yarn, False), (None, True)):
        c = cfg.replace(attn=dataclasses.replace(cfg.attn, yarn=yarn))
        err = median_err([np.asarray(apply_model(params, p[None], c)[0][
            0, :, :m["vocab_size"]]) for p in ps])
        assert (err > MEDIAN_TOL) == fails, (yarn, err)


def test_control_fails_where_sound_runs_pass():
    m, t = tiny_dsv2(), tiny.traffic("decode-closed")
    for seed in CONTROL_SEEDS:
        r = run.run_cell(m, t, LIMITS, seed, 0, control=True,
                         close_after=tiny.CLOSE_AFTER)
        c = r["check"]
        assert r["correct"], (r["compared"], c)
        assert c["control_logit_err"] > LIMITS["compare"]["logit_err"], c
        assert c["altered_widest_gap"] > LIMITS["compare"]["widest_gap"], c


FAULTS = (_token_altered, _state_unchanged, _prefill_token_altered)


@pytest.mark.parametrize("fault", FAULTS,
                         ids=[f.__name__.strip("_") for f in FAULTS])
def test_broken_timed_path_is_not_correct(fault):
    m, t = tiny_dsv2(), tiny.traffic("decode-closed")
    r = run.run_cell(m, t, LIMITS, CONTROL_SEEDS[0], 0, fault=fault,
                     close_after=tiny.CLOSE_AFTER)
    assert not r["correct"], r["check"]


class _Ctx:
    def __init__(self, open_, close):
        self.counters = {"open": open_, "close": close}

    delta = run.Ctx.delta


def test_store_readers_read_the_new_counters_and_skip_without_them():
    hit = run.load_reader("pool_hit_share.host")
    fetches = run.load_reader("miss_fetches_per_step.host")
    a = {"steps": 10, "store.routed_rows": 1000, "store.fallback_rows": 100,
         "store.fallback_fetches": 40}
    b = {"steps": 30, "store.routed_rows": 1960, "store.fallback_rows": 340,
         "store.fallback_fetches": 200}
    assert hit(_Ctx(a, b)) == pytest.approx(75.0)
    assert fetches(_Ctx(a, b)) == pytest.approx(8.0)
    # a program whose store has no routed_rows (before this counter) and
    # a server with no store: nothing to read, no error
    old = {k: v for k, v in a.items() if k != "store.routed_rows"}
    assert hit(_Ctx(old, b)) is None
    bare = {"steps": 10}
    assert hit(_Ctx(bare, {"steps": 20})) is None
    assert fetches(_Ctx(bare, {"steps": 20})) is None


def test_flops_count_the_published_mla_and_every_ffn():
    with open(os.path.join(os.path.dirname(DATA), "..", "configs",
                           "deepseek-v2-lite-l9-host.json")) as fh:
        m = json.load(fh)
    d, H, V = 2048, 16, 102400
    attn = (2 * d * H * 192 + 2 * d * 576 + 2 * 512 * H * 256
            + 2 * H * 128 * d)
    moe = 2 * d * 64 + (6 + 2) * 6 * d * 1408
    want = 9 * attn + 6 * d * 10944 + 8 * moe + 2 * d * V
    assert fam.token_flops(m, 0) == want
    assert fam.token_flops(m, 100) - fam.token_flops(m, 0) == \
        9 * 2 * H * (192 + 128) * 100
    assert fam.moe_dims(m) == (2048, 1408, 64, 6, 8)
    assert fam.prefill_flops(m, 4) == (4 * fam.token_flops(m, 0, False)
                                       + 9 * 2 * H * 320 * 10 + 2 * d * V)
