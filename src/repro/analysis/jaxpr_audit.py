"""Jaxpr/HLO walkers proving the serving hot path's graph invariants.

Four static checks per entry point (DESIGN.md §12):

* **callback allowlist** — every ``pure_callback`` / ``io_callback``
  equation must resolve to a seam registered via
  :func:`repro.models.moe.register_callback_seam` (matched on the
  underlying function object, so bound methods and ``_FallbackView``
  proxies resolve), with the declared kind;
* **cond guarding / sync census** — cond-required seams must sit under a
  ``lax.cond`` branch, so an all-hit step never leaves the device: the
  decode fast path performs ZERO unconditional host transfers;
* **weight capture** — no constant larger than the contract budget in
  any stripped-params graph (the graph-level proof that
  ``strip_expert_params`` stripped and nothing re-captured an expert
  row as a closure constant);
* **donation** — each ``donate_argnums`` argument of the store's
  streaming jits is ACTUALLY input->output aliased in the compiled
  executable (``input_output_alias``); XLA silently falls back to a
  copy on shape/dtype mismatch, which would turn the O(rows) commit
  into an O(pool) copy without failing any runtime test.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.contracts import (E_CALLBACK_KIND,
                                      E_CALLBACK_UNGUARDED,
                                      E_CALLBACK_UNREGISTERED,
                                      E_CONST_CAPTURE, E_DONATION_DROPPED,
                                      E_ENTRY_BUILD, E_SYNC_CENSUS,
                                      EntryPoint,
                                      GraphContract, Violation,
                                      default_rungs, maybe_raise)
from repro.launch.hloparse import donated_params
from repro.models.moe import lookup_callback_seam

try:                                    # moved in newer jax
    from jax.extend.core import ClosedJaxpr, Jaxpr
except ImportError:                     # pragma: no cover
    from jax.core import ClosedJaxpr, Jaxpr

_CALLBACK_PRIMS = {"pure_callback": "pure", "io_callback": "io"}
#: host-sync primitives that are NOT seam callbacks: a stray
#: ``jax.debug.print`` lowers to one of these and stalls every step
#: (JAX 0.9 traces it as its own ``debug_print`` primitive)
_SYNC_PRIMS = ("debug_callback", "debug_print")


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _sub_jaxprs(v):
    """Yield the jaxprs nested inside one eqn-param value (cond carries a
    tuple of branches, scan/pjit/while carry Closed/raw jaxprs)."""
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def iter_eqns(jaxpr, under_cond: bool = False):
    """Yield ``(eqn, under_cond)`` over a jaxpr and every nested jaxpr,
    tracking whether the equation sits inside any ``lax.cond`` branch."""
    for eqn in jaxpr.eqns:
        yield eqn, under_cond
        nested_under = under_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub, nested_under)


def _callback_target(eqn):
    cb = eqn.params.get("callback")
    return getattr(cb, "callback_func", cb)


def _target_name(target) -> str:
    fn = target
    while True:
        if hasattr(fn, "__func__"):
            fn = fn.__func__
        elif isinstance(fn, functools.partial):
            fn = fn.func
        else:
            break
    return getattr(fn, "__qualname__", repr(fn))


@dataclasses.dataclass
class CallbackSite:
    """One callback equation found in a graph."""
    kind: str                   # "pure" | "io"
    guarded: bool               # sits under some lax.cond branch
    target: str                 # qualname of the host function
    seam: Optional[Any]         # CallbackSeam or None (unregistered)


def callback_census(closed: ClosedJaxpr) -> List[CallbackSite]:
    """All callback equations in a closed jaxpr, resolved against the
    seam registry and classified by cond guarding."""
    sites = []
    for eqn, guarded in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        kind = _CALLBACK_PRIMS.get(name)
        if kind is None:
            if name in _SYNC_PRIMS:
                target = _callback_target(eqn)
                sites.append(CallbackSite(
                    kind="debug", guarded=guarded,
                    target=(_target_name(target) if target is not None
                            else f"{name}({eqn.params.get('fmt', '')!r})"),
                    seam=None))
            continue
        target = _callback_target(eqn)
        sites.append(CallbackSite(kind=kind, guarded=guarded,
                                  target=_target_name(target),
                                  seam=lookup_callback_seam(target)))
    return sites


def const_census(closed: ClosedJaxpr) -> List[Dict[str, Any]]:
    """Size/shape/dtype of every constant the graph closed over."""
    out = []
    for c in closed.consts:
        out.append({"nbytes": int(getattr(c, "nbytes", 0)),
                    "shape": tuple(getattr(c, "shape", ())),
                    "dtype": str(getattr(c, "dtype", type(c).__name__)),
                    "_obj": c})
    return out


# --------------------------------------------------------------------------
# per-entry audit
# --------------------------------------------------------------------------

def audit_entry(ep: EntryPoint) -> Dict[str, Any]:
    """Run every applicable static check on one entry point.  Returns
    ``{"name", "callbacks", "consts", "donated", "violations"}`` with
    violations as :class:`Violation` (never raises on contract failure —
    the caller aggregates; a TRACE failure is itself a violation, so a
    broken entry point fails loudly instead of vanishing)."""
    violations: List[Violation] = []
    record: Dict[str, Any] = {"name": ep.name, "callbacks": [],
                              "consts": [], "donated": sorted(ep.contract.donate),
                              "violations": violations}
    try:
        closed = jax.make_jaxpr(ep.fn,
                                static_argnums=ep.static_argnums)(*ep.args)
    except Exception as e:              # noqa: BLE001 — reported, not hidden
        violations.append(Violation(
            E_ENTRY_BUILD, ep.name,
            f"entry point failed to trace: {type(e).__name__}: {e}"))
        return record

    # callback allowlist + cond guarding (the sync census)
    sites = callback_census(closed)
    n_unguarded = 0
    for s in sites:
        record["callbacks"].append(
            {"kind": s.kind, "guarded": s.guarded, "target": s.target,
             "seam": getattr(s.seam, "name", None)})
        if s.kind == "debug":
            # not a seam at all: debug prints are host syncs the fast
            # path must not pay unconditionally
            if ep.contract.require_guarded and not s.guarded:
                n_unguarded += 1
                violations.append(Violation(
                    E_SYNC_CENSUS, ep.name,
                    f"unconditional host sync: {s.target} runs every "
                    f"step — drop the jax.debug.print or guard it "
                    f"under lax.cond"))
            continue
        if s.seam is None:
            violations.append(Violation(
                E_CALLBACK_UNREGISTERED, ep.name,
                f"{s.kind}_callback targets unregistered host function "
                f"{s.target!r} — register it via "
                f"repro.models.moe.register_callback_seam or remove the "
                f"host seam from the graph"))
            continue
        if s.seam.kind != s.kind:
            violations.append(Violation(
                E_CALLBACK_KIND, ep.name,
                f"seam {s.seam.name!r} registered as "
                f"{s.seam.kind}_callback but lowered as "
                f"{s.kind}_callback"))
        if (ep.contract.require_guarded and s.seam.cond_required
                and not s.guarded):
            n_unguarded += 1
            violations.append(Violation(
                E_CALLBACK_UNGUARDED, ep.name,
                f"seam {s.seam.name!r} ({s.target}) is NOT under a "
                f"lax.cond — every step would pay the host round trip; "
                f"guard the call so an all-hit step never leaves the "
                f"device"))
    record["n_callbacks"] = len(sites)
    record["n_unguarded"] = n_unguarded

    # weight-capture audit
    if ep.check_consts:
        for c in const_census(closed):
            obj = c.pop("_obj")
            record["consts"].append(c)
            if not ep.contract.const_allowed(obj):
                violations.append(Violation(
                    E_CONST_CAPTURE, ep.name,
                    f"graph closes over a {c['nbytes']}-byte constant "
                    f"{c['dtype']}{list(c['shape'])} (budget "
                    f"{ep.contract.max_const_bytes}B) — an expert weight "
                    f"captured as a jaxpr constant defeats "
                    f"strip_expert_params; thread it through params/state "
                    f"instead"))

    # donation verification (compile only when the contract asks for it)
    if ep.contract.donate:
        jitted = jax.jit(ep.fn, donate_argnums=ep.contract.donate,
                         static_argnums=ep.static_argnums)
        hlo = jitted.lower(*ep.args).compile().as_text()
        aliased = donated_params(hlo)
        record["aliased"] = sorted(aliased)
        missing = [i for i in ep.contract.donate if i not in aliased]
        for i in missing:
            violations.append(Violation(
                E_DONATION_DROPPED, ep.name,
                f"donate_argnums arg {i} is NOT input->output aliased in "
                f"the compiled executable (aliased set: "
                f"{sorted(aliased)}) — XLA fell back to a silent copy; "
                f"match the donated buffer's shape/dtype to an output"))
    return record


# --------------------------------------------------------------------------
# entry-point enumeration for a resolved server
# --------------------------------------------------------------------------

def _example_tokens(cfg, batch: int, seq: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, cfg.vocab, (batch, seq)), jnp.int32)


def build_entry_points(rs, rungs: Optional[Tuple[str, ...]] = None,
                       prompt_len: int = 8) -> List[EntryPoint]:
    """Enumerate every jitted serving function a :class:`ResolvedServe`
    can dispatch: the decode step per ladder rung, wave prefill,
    admission prefill, the admit scatter, the store's three streaming
    jits (with their donation contracts), and the policy ``step``."""
    from repro.models.model import init_caches
    from repro.serving.steps import ADMIT_KEYS, make_admit_step

    spec = rs.spec
    cfg = spec.cfg
    store = rs.store
    mode = spec.offload.mode
    B = spec.batch_size
    if rungs is None:
        rungs = default_rungs(mode)

    entries: List[EntryPoint] = []
    state = rs.init_state(per_slot=True)

    # decode per ladder rung (jaxpr-level: callbacks, consts, census)
    rd = rs.resilient_decode()
    for rung in rungs:
        if mode == "modeled" and rung != "healthy":
            continue
        allow = ()
        if store is not None and rung == "little":
            allow = tuple(store.little_view().values())
        entries.append(EntryPoint(
            name=f"decode[{mode}/{rung}]",
            fn=rd.variant(rung, jit=False),
            args=(rs.params, state, None),
            contract=GraphContract(allow_consts=allow)))

    # prefill + admission prefill (stripped params stream through waves)
    caches0 = init_caches(cfg, B, spec.max_len)
    toks = _example_tokens(cfg, B, prompt_len)
    off0 = state.get("offload")
    entries.append(EntryPoint(
        name=f"prefill[{mode}]", fn=rs.prefill_step(),
        args=(rs.params, toks, caches0, None, off0)))
    caches1 = init_caches(cfg, 1, spec.max_len)
    toks1 = _example_tokens(cfg, 1, max(prompt_len, spec.min_bucket))
    length = jnp.asarray(prompt_len - 1, jnp.int32)
    entries.append(EntryPoint(
        name=f"admit_prefill[{mode}]", fn=rs.admit_prefill(),
        args=(rs.params, toks1, caches1, length, off0)))

    # admit scatter (no params: consts must stay tiny, no callbacks)
    first_tok = jnp.zeros((1, 1), jnp.int32)
    entries.append(EntryPoint(
        name="admit_step", fn=make_admit_step(cfg),
        args=({k: state[k] for k in ADMIT_KEYS}, caches1, first_tok,
              jnp.asarray(0, jnp.int32), length)))

    # the store's streaming jits: the donation contract (silent copy
    # fallback here is exactly the O(pool)-copy regression the audit
    # exists to catch)
    if store is not None:
        L, S, E = store.n_layers, store.n_slots, store.E
        d, f = store.d, store.f
        dt = store.dtype
        sds = jax.ShapeDtypeStruct
        pools = (sds((L, S, d, f), dt), sds((L, S, d, f), dt),
                 sds((L, S, f, d), dt))
        R = 2
        entries.append(EntryPoint(
            name="store._apply", fn=store._apply,
            args=pools + (sds((L, S), jnp.int32),
                          sds((R, d, f), dt), sds((R, d, f), dt),
                          sds((R, f, d), dt), sds((R,), jnp.int32),
                          sds((R,), jnp.int32), sds((R,), jnp.int32),
                          sds((R,), bool)),
            contract=GraphContract(donate=(0, 1, 2, 3)),
            check_consts=False))
        Q, Bc = 2, store._buf_cap
        entries.append(EntryPoint(
            name="store._stage_inj",
            fn=functools.partial(store._stage_inj, S=S),
            args=(sds((Bc, d, f), dt), sds((Bc, d, f), dt),
                  sds((Bc, f, d), dt), sds((Q,), jnp.int32),
                  (sds((Q, d, f), dt), sds((Q, d, f), dt),
                   sds((Q, f, d), dt)), sds((L, S + E), jnp.int32)),
            contract=GraphContract(donate=(0, 1, 2)),
            check_consts=False))
        F = 2
        entries.append(EntryPoint(
            name="store._fold_inj", fn=store._fold_inj,
            args=pools + (sds((Bc, d, f), dt), sds((Bc, d, f), dt),
                          sds((Bc, f, d), dt), sds((3, F), jnp.int32)),
            contract=GraphContract(donate=(0, 1, 2)),
            check_consts=False))

    # the policy step (in-graph scheduling: no host seams at all)
    policy = rs.policy
    if getattr(policy, "schedules", False) and cfg.moe is not None \
            and "dali" in state:
        n_moe = (store.n_layers if store is not None
                 else _count_moe_layers(cfg))
        E = cfg.moe.n_routed
        workloads = jnp.zeros((n_moe, E), jnp.int32)
        from repro.core.policy import Observation
        obs = Observation(
            gate_in=jnp.zeros((n_moe, B, cfg.d_model), jnp.float32),
            routers=jnp.zeros((n_moe, cfg.d_model, E), jnp.float32),
            res_vecs=jnp.zeros((n_moe, cfg.d_model), jnp.float32),
            token_mask=jnp.zeros((B,), bool))
        entries.append(EntryPoint(
            name=f"policy.step[{type(policy).__name__}]", fn=policy.step,
            args=(state["dali"], workloads, obs)))
    return entries


def _count_moe_layers(cfg) -> int:
    from repro.models.config import layer_pattern
    return sum(1 for _, mlp in layer_pattern(cfg) if mlp == "moe")


# --------------------------------------------------------------------------
# the resolved-server audit (ResolvedServe.audit backs onto this)
# --------------------------------------------------------------------------

def audit_resolved(rs, rungs: Optional[Tuple[str, ...]] = None,
                   raise_on_violation: bool = True,
                   prompt_len: int = 8) -> Dict[str, Any]:
    """Audit every serving entry point of one resolved server against
    the graph contracts.  Returns the machine-readable report; raises
    :class:`GraphContractError` on any violation unless told not to."""
    mode = rs.spec.offload.mode
    entries = build_entry_points(rs, rungs=rungs, prompt_len=prompt_len)
    records, violations = [], []
    for ep in entries:
        rec = audit_entry(ep)
        violations.extend(rec.pop("violations"))
        records.append(rec)
    report = {"mode": mode,
              "rungs": list(rungs or default_rungs(mode)),
              "entries": records,
              "violations": [v.asdict() for v in violations]}
    report["ok"] = not violations
    return maybe_raise(report, raise_on_violation)
