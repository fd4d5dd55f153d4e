"""Typed serving construction: one spec, one ``resolve()``.

Before this module the serving stack was constructed through four
overlapping kwarg surfaces — ``make_store(offload, params, cfg, policy,
fallback, faults, cost_model)``, ``make_decode_step(cfg, dali_cfg,
moe_capacity, sample, temperature, policy, offload, fallback)``,
``init_serve_state(..., dali_cfg, policy, offload)`` and both server
constructors — each re-validating the same "physical offload requires a
scheduling policy" contract with its own wording.  :class:`ServeSpec`
(what to serve: config, server preset, policy, batch geometry, sampling)
plus :class:`OffloadSpec` (how expert weights reach the device: mode,
miss fallback, prefill streaming budget, faults) are frozen dataclasses
that carry the WHOLE construction surface; ``ServeSpec.resolve(params)``
is the single path that

  * validates the offload mode and the offload↔policy contract ONCE
    (``require_offload_policy`` — the error every legacy entry point now
    shares),
  * resolves the policy name against the registry,
  * builds the :class:`~repro.serving.expert_store.ExpertStore` for
    physical modes (sized to the policy's effective resident set, the
    logic that used to live in ``scheduler.make_store``),
  * strips the routed expert stacks out of ``params`` for physical modes
    (``strip_expert_params`` — prefill and decode both read the slot
    pool now, so a physically-offloaded server never materializes the
    on-device expert stacks), and
  * hands back a :class:`ResolvedServe` whose factory methods build the
    step functions / serve state / server the old call sites built by
    hand.

``launch/serve.py`` flags map 1:1 onto spec fields.  The legacy kwarg
surfaces keep working — they now route through the same validation and
emit a once-per-process :class:`DeprecationWarning`
(``benchmarks/serving_throughput.py`` and
``examples/offload_ablation.py`` deliberately stay on them as the
back-compat guard until the kwargs are removed in a later PR).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import Any, Optional

OFFLOAD_MODES = ("modeled", "blocking", "overlap", "pipelined")

# THE offload↔policy contract, stated once (previously triplicated with
# three wordings across make_store / make_decode_step / init_serve_state;
# tests assert this exact message from every entry point)
OFFLOAD_POLICY_ERROR = (
    "physical offload requires an MoE architecture and a scheduling "
    "policy (policy != 'none'): slot plans are lowered from the policy's "
    "decisions and its initial resident set seeds the slot pool")


def require_offload_policy(policy, cfg):
    """Raise the shared contract error unless ``policy`` schedules an MoE
    architecture — the one copy of the check every construction path
    (spec resolve + all legacy shims) funnels through."""
    if not (getattr(policy, "schedules", False) and cfg.moe is not None):
        raise ValueError(OFFLOAD_POLICY_ERROR)


# --------------------------------------------------------------------------
# deprecation shim plumbing
# --------------------------------------------------------------------------

_STATE = threading.local()
_WARNED: set = set()


@contextlib.contextmanager
def _internal():
    """Mark legacy-surface calls made BY the spec machinery itself (the
    resolve path is built on the same factories it deprecates) so they
    never warn — only direct legacy construction does."""
    prev = getattr(_STATE, "in_resolve", False)
    _STATE.in_resolve = True
    try:
        yield
    finally:
        _STATE.in_resolve = prev


def warn_legacy(api: str):
    """Once-per-process DeprecationWarning for a legacy construction
    entry point, suppressed under ``_internal()``."""
    if getattr(_STATE, "in_resolve", False) or api in _WARNED:
        return
    _WARNED.add(api)
    warnings.warn(
        f"{api} with legacy kwargs is deprecated; construct through "
        "ServeSpec.resolve() (repro/serving/spec.py)",
        DeprecationWarning, stacklevel=3)


# --------------------------------------------------------------------------
# the specs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OffloadSpec:
    """How expert weights reach the device.

    mode          — "modeled" | "blocking" | "overlap" | "pipelined"
                    (DESIGN.md §8–§9)
    fallback      — miss tier: "fetch" (bit-exact demand fetch) | "host"
                    (CPU FFN, allclose) | "little" (resident int8 twins)
    prefill_rows  — prefill streaming budget: experts per wave a prefill
                    layer sweep stages (DESIGN.md §11; None = pool size)
    strip_params  — remove the on-device expert stacks from the served
                    params (None = auto: stripped for physical modes)
    faults        — fault-injection schedule (serving/faults.py); the
                    grammar takes an optional link selector,
                    ``link_degrade[0>3]:x8@6-18`` (DESIGN.md §13)
    cost_model    — link constants for the watchdog (None = the running
                    device's profile, cost_model.DEVICE_PROFILES)
    topology      — per-link fabric spec (core/cost_model.parse_topology:
                    "flat", "island:K", "SRC>DST:xF" overrides, or a
                    LinkTopology) attached to the cost model so per-link
                    watchdogs and EP placement price each pair honestly
    """
    mode: str = "modeled"
    fallback: str = "fetch"
    prefill_rows: Optional[int] = None
    strip_params: Optional[bool] = None
    faults: Any = None
    cost_model: Any = None
    topology: Any = None

    @property
    def physical(self) -> bool:
        return self.mode != "modeled"


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """What to serve and how — the single construction surface.

    ``launch/serve.py`` flags map 1:1: --server → ``server``, --policy →
    ``policy``, --batch → ``batch_size``, --offload/--faults →
    ``offload.mode``/``offload.faults``.
    """
    cfg: Any
    server: str = "continuous"
    policy: Any = None                  # name | OffloadPolicy | None
    dali_cfg: Any = None
    batch_size: int = 8
    max_len: int = 256
    eos_id: int = 1
    min_bucket: int = 16
    moe_capacity: Optional[int] = None
    sample: bool = False
    temperature: float = 1.0
    offload: OffloadSpec = dataclasses.field(default_factory=OffloadSpec)

    @classmethod
    def from_legacy(cls, cfg, *, server: str = "continuous", policy=None,
                    dali_cfg=None, batch_size: int = 8, max_len: int = 256,
                    eos_id: int = 1, min_bucket: int = 16,
                    moe_capacity=None, sample: bool = False,
                    temperature: float = 1.0, offload="modeled",
                    fallback: str = "fetch", faults=None, cost_model=None,
                    prefill_rows=None, strip_params=None) -> "ServeSpec":
        """Adapter from the legacy kwarg surface (server constructors,
        ``make_server``) onto the spec — the deprecation shim's body."""
        off = offload if isinstance(offload, OffloadSpec) else OffloadSpec(
            mode=offload or "modeled", fallback=fallback, faults=faults,
            cost_model=cost_model, prefill_rows=prefill_rows,
            strip_params=strip_params)
        return cls(cfg=cfg, server=server, policy=policy, dali_cfg=dali_cfg,
                   batch_size=batch_size, max_len=max_len, eos_id=eos_id,
                   min_bucket=min_bucket, moe_capacity=moe_capacity,
                   sample=sample, temperature=temperature, offload=off)

    def resolve(self, params) -> "ResolvedServe":
        """Validate + build: policy, store, (stripped) params — the one
        path every serving entry point constructs through."""
        from repro.serving.steps import resolve_policy
        off = self.offload
        with _internal():
            policy = resolve_policy(self.policy, self.cfg, self.dali_cfg)
            store = build_store(off.mode, params, self.cfg, policy,
                                fallback=off.fallback, faults=off.faults,
                                cost_model=off.cost_model,
                                prefill_rows=off.prefill_rows,
                                topology=off.topology)
        use_params = params
        if store is not None and off.strip_params is not False:
            from repro.serving.expert_store import strip_expert_params
            use_params = strip_expert_params(params, self.cfg)
        return ResolvedServe(spec=self, policy=policy, store=store,
                             params=use_params)


def build_store(offload: str, params, cfg, policy, fallback: str = "fetch",
                faults=None, cost_model=None, prefill_rows=None,
                topology=None):
    """Build the ExpertStore for a physical offload mode (None for
    "modeled") — the store-sizing logic ``scheduler.make_store`` used to
    own.  The pool is sized to the policy's maximum effective resident
    set (cache ∪ prefetch) and the per-step copy budget to its churn."""
    from repro.serving.expert_store import ExpertStore
    if offload not in OFFLOAD_MODES:
        raise ValueError(f"offload must be one of "
                         f"{'|'.join(OFFLOAD_MODES)}, got {offload!r}")
    if offload == "modeled":
        if faults is not None:
            raise ValueError('faults need a physical offload mode '
                             '("blocking" | "overlap" | "pipelined"); '
                             '"modeled" has no streaming path to inject '
                             'into')
        return None
    require_offload_policy(policy, cfg)
    if topology is not None:
        # attach the per-link fabric to the store's cost model so its
        # watchdog (and anything reading CostModel.for_link) prices each
        # directed pair, not one homogeneous link (DESIGN.md §13)
        import jax
        from repro.core.cost_model import CostModel, parse_topology
        cm = cost_model if cost_model is not None else CostModel.for_config(cfg)
        cost_model = cm.with_topology(
            parse_topology(topology, len(jax.devices()), cm.profile))
    dcfg = policy.dcfg
    moves = max(2, dcfg.prefetch_size + dcfg.u_size)
    # pool = max effective resident set (cache ∪ prefetch) + one plan of
    # slack: in-flight inserts land in slack instead of evicting experts
    # the lagged plan still wants, and evicted-but-not-overwritten
    # experts keep serving hits until their slot is reused
    return ExpertStore(
        params, cfg,
        n_slots=min(cfg.moe.n_routed,
                    dcfg.cache_size + dcfg.prefetch_size + moves),
        max_moves=moves, fallback=fallback, mode=offload,
        faults=faults, cost_model=cost_model, prefill_rows=prefill_rows)


@dataclasses.dataclass
class ResolvedServe:
    """A resolved spec: policy + store + (stripped) params, with factory
    methods for every step/state/server the legacy surfaces built by
    hand.  All factories run under ``_internal()`` so the shared legacy
    implementations they delegate to never emit the deprecation
    warning for spec-driven construction."""
    spec: ServeSpec
    policy: Any
    store: Any
    params: Any

    def decode_step(self, fallback: Optional[str] = None):
        from repro.serving.steps import make_decode_step
        s = self.spec
        with _internal():
            return make_decode_step(s.cfg, moe_capacity=s.moe_capacity,
                                    sample=s.sample,
                                    temperature=s.temperature,
                                    policy=self.policy, offload=self.store,
                                    fallback=fallback)

    def resilient_decode(self):
        from repro.serving.steps import ResilientDecode
        s = self.spec
        with _internal():
            return ResilientDecode(s.cfg, moe_capacity=s.moe_capacity,
                                   sample=s.sample,
                                   temperature=s.temperature,
                                   policy=self.policy, offload=self.store)

    def prefill_step(self, max_len: Optional[int] = None):
        """Wave prefill; with a physical store the sweep streams through
        the offload path (call with ``off=state['offload']``)."""
        from repro.serving.steps import make_prefill_step
        s = self.spec
        return make_prefill_step(s.cfg, max_len or s.max_len,
                                 moe_capacity=s.moe_capacity,
                                 offload=self.store)

    def admit_prefill(self):
        from repro.serving.steps import make_admit_prefill
        s = self.spec
        return make_admit_prefill(s.cfg, moe_capacity=s.moe_capacity,
                                  offload=self.store)

    def init_state(self, per_slot: bool = False, seed: int = 0,
                   batch: Optional[int] = None,
                   max_len: Optional[int] = None):
        from repro.serving.steps import init_serve_state
        s = self.spec
        with _internal():
            return init_serve_state(s.cfg, batch or s.batch_size,
                                    max_len or s.max_len,
                                    policy=self.policy, per_slot=per_slot,
                                    seed=seed, offload=self.store)

    def audit(self, rungs=None, raise_on_violation: bool = True,
              with_costs: bool = False):
        """Static graph-contract audit of THIS resolution's serving
        entry points (repro/analysis, DESIGN.md §12): callback seams,
        cond guarding, donation aliasing, weight-capture budget.
        Returns the machine-readable report dict; raises
        :class:`repro.analysis.GraphContractError` on any violation
        unless ``raise_on_violation=False``.  ``with_costs=True``
        additionally cross-checks HLO-extracted H2D bytes/FLOPs against
        the :class:`~repro.core.cost_model.CostModel` (compiles the
        decode step, so it is off by default for interactive use)."""
        from repro.analysis.jaxpr_audit import audit_resolved
        report = audit_resolved(self, rungs=rungs,
                                raise_on_violation=raise_on_violation)
        if with_costs:
            from repro.analysis.cost_audit import audit_costs
            from repro.analysis.contracts import maybe_raise
            report["costs"] = audit_costs(self)
            report["violations"].extend(report["costs"]["violations"])
            report["ok"] = not report["violations"]
            maybe_raise(report, raise_on_violation)
        return report

    def server(self, res_vecs=None):
        """The server the spec names, constructed from this resolution
        (no re-resolve, no legacy warning)."""
        from repro.serving.scheduler import SERVER_PRESETS
        try:
            cls = SERVER_PRESETS[self.spec.server]
        except KeyError:
            raise ValueError(
                f"unknown server preset {self.spec.server!r}; choose "
                f"from {sorted(SERVER_PRESETS)}") from None
        return cls(self.params, resolved=self, res_vecs=res_vecs)
