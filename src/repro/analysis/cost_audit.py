"""Static calibration of the cost model the Greedy Assignment solver
trusts: extract per-mode H2D bytes and FLOPs from compiled HLO text
(``launch/hloparse``) and cross-check them against
:class:`~repro.core.cost_model.CostModel` predictions (DESIGN.md §12).

Three checks:

* **expert-row bytes** — ``CostModel.expert_bytes`` must equal the
  store's measured host-row bytes EXACTLY (the unit every ``t_trans``
  prediction and the watchdog's budget are denominated in);
* **pipelined stage H2D** — the bytes a ``_stage_inj`` dispatch actually
  ships (non-donated entry parameters of the compiled program) must
  agree with the store's accounting convention ``Q x expert_bytes``
  (what ``h2d_bytes`` telemetry and the offload benchmark report)
  within tolerance — packing drift here would make the benchmark lie;
* **decode FLOPs** — scan-expanded ``dot`` FLOPs of the compiled decode
  step, compared (a) against the analytic active-param model
  (``2 x N_active x tokens``) within a generous ratio, and (b) across
  offload modes against the modeled baseline within a tight tolerance:
  the slot path must not re-introduce dense dispatch compute.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.analysis.contracts import E_COST_DRIFT, Violation
from repro.core.cost_model import CostModel
from repro.launch.hloparse import (donated_params, entry_param_bytes,
                                   hlo_flops)


def stage_h2d_bytes(store, q: int = 2) -> Dict[str, float]:
    """HLO-extracted bytes one pipelined ``_stage_inj`` dispatch of a
    ``q``-row bucket ships host->device: the compiled program's entry
    parameters minus the donated (device-resident) inject buffers."""
    import functools
    L, S, E = store.n_layers, store.n_slots, store.E
    d, f = store.d, store.f
    dt = store.dtype
    sds = jax.ShapeDtypeStruct
    args = (sds((store._buf_cap, d, f), dt), sds((store._buf_cap, d, f), dt),
            sds((store._buf_cap, f, d), dt), sds((q,), jnp.int32),
            (sds((q, d, f), dt), sds((q, d, f), dt), sds((q, f, d), dt)), sds((L, S + E), jnp.int32))
    jitted = jax.jit(functools.partial(store._stage_inj, S=S),
                     donate_argnums=(0, 1, 2))
    hlo = jitted.lower(*args).compile().as_text()
    pb = entry_param_bytes(hlo)
    donated = donated_params(hlo)
    shipped = sum(b for i, b in pb.items() if i not in donated)
    return {"hlo_bytes": float(shipped),
            "model_bytes": float(q * store.expert_bytes),
            "donated": sorted(donated), "q": q}


def decode_dot_flops(rs, rung: str = "healthy") -> float:
    """Scan-expanded matmul FLOPs of one compiled decode step."""
    fn = rs.resilient_decode().variant(rung, jit=True)
    state = rs.init_state(per_slot=True)
    hlo = fn.lower(rs.params, state, None).compile().as_text()
    return float(hlo_flops(hlo)["dot_flops"])


def analytic_decode_flops(cfg, batch: int) -> float:
    """The active-param analytic model (``launch/dryrun.model_flops``):
    2 x N_active x tokens for one decode step."""
    from repro.launch.dryrun import model_flops
    return float(model_flops(
        cfg, SimpleNamespace(batch=batch, seq=1, kind="decode")))


def audit_costs(rs, tol_h2d: float = 0.10, tol_mode_flops: float = 0.25,
                flops_ratio_max: float = 8.0,
                reference_flops: Optional[float] = None,
                rung: str = "healthy") -> Dict[str, Any]:
    """Cross-check HLO-extracted costs of one resolved server against
    the CostModel.  ``reference_flops`` (the modeled mode's decode
    FLOPs, when auditing a physical mode) arms the cross-mode check.
    Returns a record with ``violations`` as dicts (never raises)."""
    spec = rs.spec
    cfg = spec.cfg
    mode = spec.offload.mode
    violations = []
    out: Dict[str, Any] = {"mode": mode, "violations": violations}

    cm = CostModel.for_config(cfg)
    out["cm_expert_bytes"] = cm.expert_bytes
    if rs.store is not None:
        out["store_expert_bytes"] = rs.store.expert_bytes
        if rs.store.expert_bytes != cm.expert_bytes:
            violations.append(Violation(
                E_COST_DRIFT, f"expert_bytes[{mode}]",
                f"CostModel.expert_bytes={cm.expert_bytes} but the host "
                f"store rows measure {rs.store.expert_bytes}B — every "
                f"t_trans prediction is denominated in the wrong unit"
            ).asdict())

    if mode == "pipelined":
        h2d = stage_h2d_bytes(rs.store)
        out["stage_h2d"] = h2d
        drift = abs(h2d["hlo_bytes"] - h2d["model_bytes"]) \
            / max(h2d["model_bytes"], 1.0)
        out["stage_h2d"]["drift"] = drift
        if drift > tol_h2d:
            violations.append(Violation(
                E_COST_DRIFT, f"stage_h2d[{mode}]",
                f"HLO ships {h2d['hlo_bytes']:.0f}B per "
                f"{h2d['q']}-row stage but the telemetry/benchmark "
                f"convention records Q x expert_bytes = "
                f"{h2d['model_bytes']:.0f}B ({drift:.1%} > "
                f"{tol_h2d:.0%}) — the packed stage payload drifted "
                f"from the cost model").asdict())

    flops = decode_dot_flops(rs, rung=rung)
    analytic = analytic_decode_flops(cfg, spec.batch_size)
    out["decode_dot_flops"] = flops
    out["analytic_flops"] = analytic
    ratio = flops / max(analytic, 1.0)
    out["flops_ratio"] = ratio
    if not (1.0 / flops_ratio_max) <= ratio <= flops_ratio_max:
        violations.append(Violation(
            E_COST_DRIFT, f"decode_flops[{mode}]",
            f"compiled decode performs {flops:.3g} dot FLOPs vs "
            f"{analytic:.3g} analytic active-param FLOPs (ratio "
            f"{ratio:.2f} outside 1/{flops_ratio_max:g}.."
            f"{flops_ratio_max:g}) — dense dispatch compute crept onto "
            f"the decode step").asdict())
    if reference_flops is not None:
        rel = abs(flops - reference_flops) / max(reference_flops, 1.0)
        out["vs_modeled"] = rel
        if rel > tol_mode_flops:
            violations.append(Violation(
                E_COST_DRIFT, f"decode_flops[{mode}]",
                f"physical-mode decode FLOPs ({flops:.3g}) drift "
                f"{rel:.1%} from the modeled baseline "
                f"({reference_flops:.3g}) — the slot path must not "
                f"change the step's compute beyond {tol_mode_flops:.0%}"
            ).asdict())
    out["ok"] = not violations
    return out
