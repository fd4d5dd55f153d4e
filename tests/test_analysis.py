"""The graph-contract auditor (repro/analysis): a green audit over real
resolved servers, a red self-test over the seeded-violation fixtures,
unit coverage of each AST-lint rule, and the ResolvedServe.audit() /
cost-audit surfaces."""
import dataclasses

import jax
import pytest

from repro.analysis.contracts import (E_CALLBACK_UNGUARDED,
                                      E_CALLBACK_UNREGISTERED,
                                      E_CONST_CAPTURE, E_DONATION_DROPPED,
                                      E_SYNC_CENSUS, GraphContract,
                                      GraphContractError, Violation,
                                      maybe_raise)
from repro.analysis.lint import lint_source, lint_tree
from repro.configs import get_config, make_smoke
from repro.models.model import init_model
from repro.serving.spec import OffloadSpec, ServeSpec

jax.config.update("jax_platforms", "cpu")


def _cfg(n_layers=2, n_routed=4):
    cfg = make_smoke(get_config("mixtral-8x7b")).replace(n_layers=n_layers)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_routed=n_routed))


@pytest.fixture(scope="module")
def params_and_cfg():
    cfg = _cfg()
    return init_model(jax.random.PRNGKey(0), cfg), cfg


def _resolve(params, cfg, mode, **kw):
    return ServeSpec(cfg=cfg, policy="dali", batch_size=2, max_len=32,
                     offload=OffloadSpec(mode=mode), **kw).resolve(params)


# ---------------------------------------------------------------------------
# the audit itself: green on real serving graphs
# ---------------------------------------------------------------------------

def test_audit_modeled_passes(params_and_cfg):
    params, cfg = params_and_cfg
    rs = _resolve(params, cfg, "modeled")
    report = rs.audit()
    assert report["ok"]
    assert report["violations"] == []
    names = [e["name"] for e in report["entries"]]
    assert any(n.startswith("decode[") for n in names)
    assert any(n.startswith("prefill[") for n in names)


def test_audit_pipelined_all_rungs_pass(params_and_cfg):
    params, cfg = params_and_cfg
    rs = _resolve(params, cfg, "pipelined")
    report = rs.audit(with_costs=True)
    assert report["ok"], report["violations"]
    names = [e["name"] for e in report["entries"]]
    # all three ladder rungs, the store's donated jits, and the policy
    for expect in ("decode[pipelined/healthy]", "decode[pipelined/little]",
                   "store._apply", "store._stage_inj", "store._fold_inj"):
        assert expect in names, names
    # donation verified as real aliases, not just requested
    by_name = {e["name"]: e for e in report["entries"]}
    assert by_name["store._apply"]["aliased"] == [0, 1, 2, 3]
    assert by_name["store._stage_inj"]["aliased"] == [0, 1, 2]
    # every callback in every graph is a registered, guarded seam
    for e in report["entries"]:
        for cb in e["callbacks"]:
            assert cb["seam"] is not None
            assert cb["guarded"]
    # the full-quality decode demand-fetches its misses through the
    # registered per-expert seam (inside its guarded loop)
    seams = {cb["seam"]
             for cb in by_name["decode[pipelined/healthy]"]["callbacks"]}
    assert "fetch_weights" in seams


def test_audit_cost_checks_pipelined(params_and_cfg):
    params, cfg = params_and_cfg
    from repro.analysis.cost_audit import audit_costs
    rs = _resolve(params, cfg, "pipelined")
    rec = audit_costs(rs)
    assert rec["ok"], rec["violations"]
    # the H2D convention holds tightly (meta/pos overhead only)
    assert rec["stage_h2d"]["drift"] < 0.01
    assert rec["store_expert_bytes"] == rec["cm_expert_bytes"]
    # compiled decode matmul flops within a generous ratio of analytic
    assert 1 / 8 < rec["flops_ratio"] < 8


def test_audit_raises_typed_error_on_violation():
    report = {"mode": "x", "violations": [
        Violation(E_CONST_CAPTURE, "e", "boom").asdict()], "ok": False}
    with pytest.raises(GraphContractError) as ei:
        maybe_raise(report, True)
    assert ei.value.violations[0].code == E_CONST_CAPTURE
    assert "boom" in str(ei.value)


# ---------------------------------------------------------------------------
# seeded violations: each defect class fails with its own code
# ---------------------------------------------------------------------------

def test_selftest_fixtures_each_fire_their_code():
    from repro.analysis.selftest import run_selftest
    report = run_selftest()
    assert report["ok"], report["fixtures"]
    got = {r["fixture"]: r["expected"] for r in report["fixtures"]}
    assert set(got.values()) == {
        E_CONST_CAPTURE, E_DONATION_DROPPED, E_CALLBACK_UNREGISTERED,
        E_CALLBACK_UNGUARDED, E_SYNC_CENSUS}
    # distinct: five fixtures, five different codes
    assert len(set(got.values())) == len(got)


# ---------------------------------------------------------------------------
# graph contracts
# ---------------------------------------------------------------------------

def test_const_allowed_by_budget_identity_and_shape():
    import numpy as np
    small = np.zeros((4,), np.float32)
    big = np.zeros((64, 1024), np.float32)       # 256 KiB
    twin = np.zeros((64, 1024), np.float32)
    c = GraphContract(allow_consts=(big,))
    assert c.const_allowed(small)                # under budget
    assert c.const_allowed(big)                  # identity
    assert c.const_allowed(twin)                 # shape+dtype allowlisted
    assert not c.const_allowed(np.zeros((64, 1024), np.int32))


# ---------------------------------------------------------------------------
# AST lint rules (unit level) + clean tree
# ---------------------------------------------------------------------------

def test_lint_a001_bare_assert_in_serving():
    src = "def f(x):\n    assert x > 0\n    return x\n"
    assert [f.code for f in lint_source(src, "repro/serving/foo.py")] \
        == ["A001"]
    # same code outside serving/core is fine
    assert lint_source(src, "repro/models/foo.py") == []


def test_lint_a002_sync_in_hot_hook():
    src = ("class H:\n"
           "    def pre_step(self, state):\n"
           "        x = state.loss.item()\n"
           "        y = float(state.t)\n"
           "        return x + y\n"
           "    def other(self, state):\n"
           "        return state.loss.item()\n")
    codes = [f.code for f in lint_source(src, "repro/serving/hooks.py")]
    assert codes == ["A002", "A002"]     # only inside the hot hook


def test_lint_a003_callback_outside_seam_helpers():
    src = ("import jax\n"
           "def f(x):\n"
           "    return jax.pure_callback(abs, x, x)\n")
    assert [f.code for f in lint_source(src, "repro/serving/foo.py")] \
        == ["A003"]
    # the seam-helper module itself is the allowed call site
    assert lint_source(src, "repro/models/moe.py") == []


def test_lint_a004_tel_mutation_outside_lock():
    src = ("class ExpertStore:\n"
           "    def _bump(self, k, v):\n"
           "        self._tel[k] += v\n"
           "    def rogue(self):\n"
           "        self._tel['h2d_bytes'] += 1\n")
    findings = lint_source(src, "repro/serving/expert_store.py")
    assert [f.code for f in findings] == ["A004"]
    assert findings[0].line == 5


def test_lint_tree_is_clean():
    findings = lint_tree()
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_audit_cli_lint_only(capsys):
    from repro.analysis.audit import main
    assert main(["--lint-only"]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_audit_cli_rejects_unknown_mode():
    from repro.analysis.audit import main
    with pytest.raises(SystemExit):
        main(["--modes", "warp-drive"])
