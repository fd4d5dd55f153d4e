"""The plain float32 reference against the served path at tiny widths,
on the CPU: logits of every served token, both deployments (all experts
resident, and experts in the host store).  A broken reference, or
weights that reach the program differently from the reference, fail
here without the chip."""
import json
import os

import numpy as np
import pytest

from bench import check, system
from bench import weights as W
from bench.reference import mixtral

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 77
# bfloat16 activations over 2 tiny layers put the program's logits within
# a few hundredths of the float32 reference (logit std ~1.15 here)
LOGIT_TOL = 0.08


def tiny(mode):
    m = json.load(open(os.path.join(DATA, f"tiny-{mode}.json")))
    m["name"] = f"tiny-{mode}"
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
    return [done[i] for i in range(len(prompts))]


def test_one_call_weights_equal_leaf_by_leaf_weights():
    m = tiny("hbm")
    p = system.program_params(m, SEED)
    base = W.base_key(*W.seed_halves(SEED))
    blk = p["scan"][0]
    for l, e in ((0, 0), (1, 7), (1, 3)):
        for prog, name in (("gate", "w_gate"), ("up", "w_up"),
                           ("down", "w_down")):
            np.testing.assert_array_equal(
                np.asarray(blk["mlp"][prog][l, e]),
                np.asarray(W.make_leaf(base, m, name, l, e)))
    np.testing.assert_array_equal(np.asarray(blk["mixer"]["wq"][1]),
                                  np.asarray(W.make_leaf(base, m, "wq", 1)))
    np.testing.assert_array_equal(np.asarray(p["embed"]["tok"]),
                                  np.asarray(W.make_leaf(base, m, "embed")))


@pytest.mark.parametrize("mode", ["hbm", "host"])
def test_served_logits_follow_the_reference(mode):
    m = tiny(mode)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, m["vocab_size"], n).astype(np.int32)
               for n in (20, 33, 9)]
    done = serve(m, prompts, 6)
    served = [(p, r.output) for p, r in zip(prompts, done)]
    seqs, rows, targets = check.teacher_forced(served, range(len(served)))
    ref = mixtral.logits_at(m, SEED, seqs, rows)
    for r, got in zip(ref, done):
        prog = np.stack(got.logits)[:, :m["vocab_size"]]
        assert prog.shape == r.shape
        assert np.abs(prog - r).max() < LOGIT_TOL
    g = check.gaps(ref, targets)
    assert g.max() < LOGIT_TOL and np.count_nonzero(g) <= 2


def test_reference_is_causal_and_batch_independent():
    m = tiny("hbm")
    rng = np.random.default_rng(6)
    a = rng.integers(3, m["vocab_size"], 30).astype(np.int32)
    b = rng.integers(3, m["vocab_size"], 17).astype(np.int32)
    alone = mixtral.logits_at(m, SEED, [a[:12]], [np.arange(12)])[0]
    both = mixtral.logits_at(m, SEED, [b, a], [np.arange(5),
                                               np.arange(12)])[1]
    np.testing.assert_allclose(alone, both, atol=1e-5)
    other = mixtral.logits_at(m, SEED + 1, [a[:12]], [np.arange(12)])[0]
    assert np.abs(other - alone).max() > 0.1
