"""Serving step functions: prefill / decode, with optional in-graph
offload-policy scheduling for MoE architectures.

Scheduling is pluggable: ``policy=`` accepts a registered policy name
("dali" | "static" | "all_gpu" | "lru" | "score" | "statistical" |
"random" | "none"), an :class:`repro.core.policy.OffloadPolicy`
instance, or None
(legacy: "dali" when a DaliConfig is supplied, else off).  The policy's
state rides in ``state["dali"]`` (key name kept for compat) and its
``step`` runs in-graph each decode step — swapping policies swaps pure
functions over a stable state pytree, so no step function ever retraces
per policy decision (DESIGN.md §7).

The decode step is the unit the dry-run lowers for ``decode_32k`` /
``long_500k`` shapes: ONE new token against a KV cache of ``max_len``.
All functions are pure and jit/pjit-friendly; state is an explicit pytree.

Two serve-state layouts share the same decode step (DESIGN.md §3):

wave (shared position — the compat preset)::

  ServeState = {
    "tokens":     (B, 1) int32   — last generated token per sequence
    "pos":        ()     int32   — current position (synchronised batch)
    "caches":     model caches pytree
    "dali":       DALI scheduler state (MoE archs with engine enabled)
    "offload":    device slot pools + slot table (physical offload only,
                  see serving/expert_store.py; a pipelined store adds
                  "inject" — this step's staged per-layer insert rows)
    "rng":        PRNG key
  }

per-slot (continuous batching)::

  ServeState = {
    "tokens":     (B, 1) int32
    "pos":        (B,)   int32   — every slot at its own sequence offset
    "active":     (B,)   bool    — live slots (admitted, not yet retired)
    "caches" / "dali" / "rng" as above
  }

The decode step dispatches on ``state["pos"].ndim`` (static under jit), so
one compiled function serves a batch whose composition changes every step.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.engine import DaliConfig
from repro.models.config import ModelConfig
from repro.models.model import (apply_model, collect_policy_obs,
                                init_caches)
from repro.serving.spec import (_internal, require_offload_policy,
                                warn_legacy)


def resolve_policy(policy, cfg: ModelConfig,
                   dali_cfg: Optional[DaliConfig] = None):
    """str | OffloadPolicy | None -> OffloadPolicy.

    ``None`` keeps the legacy contract: "dali" when a ``DaliConfig`` is
    supplied, scheduling off otherwise.  String names are validated here —
    i.e. at server/step construction — against the policy registry, and a
    missing ``dali_cfg`` is filled from ``default_dali_config``.  Non-MoE
    architectures have nothing to schedule and resolve to the null
    policy whatever was asked."""
    from repro.core.policy import make_policy, policy_names
    if policy is None:
        policy = "dali" if dali_cfg is not None else "none"
    if isinstance(policy, str):
        names = policy_names()
        if policy not in names:
            raise ValueError(f"policy must be one of {'|'.join(names)}, "
                             f"got {policy!r}")
        if policy == "none" or cfg.moe is None:
            return make_policy("none")
        if dali_cfg is None:
            dali_cfg = default_dali_config(cfg)
        return make_policy(policy, dali_cfg, top_k=cfg.moe.top_k,
                           router_type=cfg.moe.router_type)
    return policy


def _offload_consts(offload, fallback):
    """The trace-time constants a slot-reading step closes over: the
    fallback-presenting store view and (for the little tier) the resident
    int8 twin pool.  Shared by the decode and both prefill factories."""
    slot_fetch = offload
    slot_little = None
    if offload is not None:
        if fallback is not None and fallback != offload.fallback:
            slot_fetch = _FallbackView(offload, fallback)
        if (fallback or offload.fallback) == "little":
            slot_little = offload.little_view()
    return slot_fetch, slot_little


def make_prefill_step(cfg: ModelConfig, max_len: int,
                      moe_capacity: Optional[int] = None,
                      offload=None, fallback=None):
    """Returns prefill(params, tokens (B,S), caches, cross_src, off) ->
    (next_token (B,1), caches, logits (B, V) at the sampled position).

    ``offload`` (an :class:`~repro.serving.expert_store.ExpertStore`)
    runs the prefill layer sweep through the physical slot path
    (DESIGN.md §11): call with ``off=state["offload"]`` and params that
    may be stripped of expert stacks — each MoE layer assembles its
    dense sweep from the pool plus wave-streamed misses, bit-identical
    to full-resident prefill.  Without ``offload`` the trailing ``off``
    argument is ignored and the legacy signature is unchanged."""
    slot_fetch, slot_little = _offload_consts(offload, fallback)

    def prefill(params, tokens, caches, cross_src=None, off=None):
        S = tokens.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        slot_kw = {}
        if offload is not None:
            slot_kw = dict(expert_slots=offload.build_view(off),
                           slot_fetch=slot_fetch, slot_phase="prefill")
            if slot_little is not None:
                slot_kw["slot_little"] = slot_little
        logits, caches, _ = apply_model(params, tokens, cfg,
                                        positions=positions, caches=caches,
                                        cross_src=cross_src,
                                        moe_capacity=moe_capacity,
                                        last_logit_only=True, **slot_kw)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, caches, logits[:, -1]

    return prefill


def make_admit_prefill(cfg: ModelConfig,
                       moe_capacity: Optional[int] = None,
                       offload=None, fallback=None):
    """Prefill for admission into a continuous batch: the prompt arrives
    RIGHT-padded to a bucket length, so positions 0..length-1 are real and
    the first generated token samples from the logit at ``length - 1``
    (identical to running the unpadded prompt alone — per-slot position
    correctness).  Returns prefill(params, tokens (1,Sb), caches, length,
    off) -> (next_token (1,1), caches, logits (1, V) at ``length - 1``).
    Compiles once per bucket length.

    ``offload`` streams the admission sweep through the physical slot
    path exactly like ``make_prefill_step`` — right-pad tokens route and
    stream like real ones (bit-parity with the full-resident admission,
    which also routes them)."""
    slot_fetch, slot_little = _offload_consts(offload, fallback)

    def prefill(params, tokens, caches, length, off=None):
        S = tokens.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        slot_kw = {}
        if offload is not None:
            slot_kw = dict(expert_slots=offload.build_view(off),
                           slot_fetch=slot_fetch, slot_phase="prefill")
            if slot_little is not None:
                slot_kw["slot_little"] = slot_little
        logits, caches, _ = apply_model(params, tokens, cfg,
                                        positions=positions, caches=caches,
                                        moe_capacity=moe_capacity,
                                        logit_index=length - 1, **slot_kw)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, caches, logits[:, -1]

    return prefill


# the serve-state entries an admission rewrites (see make_admit_step)
ADMIT_KEYS = ("caches", "tokens", "pos", "active")


def make_admit_step(cfg: ModelConfig):
    """Returns admit(state, fresh_caches, first_tok, slot, length) -> state'
    inserting a freshly-prefilled request (B=1 caches) into batch ``slot``.

    Cache rows are written with dynamic_update_slice along the batch axis
    (axis 0 for prefix blocks, axis 1 for scanned stacks whose leading axis
    is the super-block).  ``pos`` rows are re-masked so cache slots holding
    right-pad garbage (absolute position >= length) read as empty (-1) —
    future decode masks then never attend to them.  ``slot`` and ``length``
    are traced, so one compilation serves every admission.

    Jitted callers pass only the ``ADMIT_KEYS`` entries of the serve state
    and merge the result back: a jitted call copies every output it passes
    through unchanged, and ``state["offload"]`` holds the whole slot pool."""

    def admit(state, fresh_caches, first_tok, slot, length):
        def ins(path, big, small):
            axis = 1 if (hasattr(path[0], "key")
                         and path[0].key == "scan") else 0
            leaf = path[-1]
            if hasattr(leaf, "key") and leaf.key == "pos":
                small = jnp.where((small >= 0) & (small < length), small, -1)
            idx = [jnp.zeros((), jnp.int32)] * big.ndim
            idx[axis] = slot
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), tuple(idx))

        caches = jax.tree_util.tree_map_with_path(
            ins, state["caches"], fresh_caches)
        tokens = jax.lax.dynamic_update_slice(
            state["tokens"], first_tok.astype(jnp.int32), (slot, 0))
        pos = jax.lax.dynamic_update_slice(
            state["pos"], jnp.full((1,), length, jnp.int32), (slot,))
        active = jax.lax.dynamic_update_slice(
            state["active"], jnp.ones((1,), bool), (slot,))
        return dict(state, caches=caches, tokens=tokens, pos=pos,
                    active=active)

    return admit


def retire_slot(state, slot: int):
    """Mark a slot free; its cache rows are overwritten on next admit."""
    return dict(state, active=state["active"].at[slot].set(False))


class _FallbackView:
    """Proxy over an ExpertStore presenting a different ``fallback``.

    ``slot_fetch.fallback`` is read at trace time inside the jitted
    decode, so one physical store can back several compiled decode
    variants (full-quality "fetch" vs. the degraded "little" rung)
    without being rebuilt — the proxy swaps the constant, every other
    attribute (callbacks, counters) delegates to the real store."""

    def __init__(self, store, fallback: str):
        from repro.serving.expert_store import FALLBACKS
        if fallback not in FALLBACKS:
            raise ValueError(f"fallback must be one of "
                             f"{'|'.join(FALLBACKS)}, got {fallback!r}")
        self._store = store
        self.fallback = fallback

    def __getattr__(self, name):
        return getattr(self._store, name)


def make_decode_step(cfg: ModelConfig, dali_cfg: Optional[DaliConfig] = None,
                     moe_capacity: Optional[int] = None,
                     sample: bool = False, temperature: float = 1.0,
                     policy=None, offload=None, fallback=None):
    """Returns decode(params, state, res_vecs=None) -> (state', logits,
    telemetry).  ``policy`` (name, OffloadPolicy, or None — see
    ``resolve_policy``) selects the in-graph offloading scheduler; the
    legacy ``dali_cfg``-only call builds the "dali" policy (greedy
    assignment + residual prefetch + workload cache, paper §4).

    ``offload`` (an :class:`repro.serving.expert_store.ExpertStore`)
    switches MoE layers to the physical slot-pool path: expert weights
    are read from ``state["offload"]`` device pools (gathered by slot
    id), misses fall back to the store's host tier, and the serving loop
    streams pool updates between steps (DESIGN.md §8).  A pipelined
    store additionally rides this step's staged inject rows in
    ``state["offload"]["inject"]`` — ``build_view`` threads them through
    the scan per layer, so the step reads the freshest plan without any
    extra step-function plumbing (DESIGN.md §9).  Requires a scheduling
    policy — the slot plans are lowered from its decisions.

    Works for both serve-state layouts: a scalar ``pos`` decodes the wave
    way (shared position); a per-slot ``pos`` (B,) uses per-row positions
    and, when scheduling is on, masks routing observables by
    ``state["active"]`` so the policy sees the actual per-step token mix.

    ``fallback`` overrides the store's own miss fallback for THIS decode
    variant (a trace-time constant — see ``_FallbackView``); with the
    effective fallback "little" the store's resident int8 twin pool is
    closed over as ``slot_little``."""
    policy = resolve_policy(policy, cfg, dali_cfg)
    use_policy = policy.schedules and cfg.moe is not None
    if offload is not None:
        # legacy offload-kwarg construction; ServeSpec.resolve() builds
        # this variant via ResolvedServe.decode_step() without warning
        warn_legacy("make_decode_step(offload=...)")
        require_offload_policy(policy, cfg)
    slot_fetch, slot_little = _offload_consts(offload, fallback)

    def decode(params, state, res_vecs=None):
        per_slot = state["pos"].ndim == 1
        if per_slot:
            positions = state["pos"][:, None]            # (B, 1)
            active = state["active"]
        else:
            positions = state["pos"] + jnp.arange(1, dtype=jnp.int32)
            active = None
        slot_kw = {}
        if offload is not None:
            slot_kw = dict(expert_slots=offload.build_view(state["offload"]),
                           slot_fetch=slot_fetch, slot_live=active)
            if slot_little is not None:
                slot_kw["slot_little"] = slot_little
        logits, caches, infos = apply_model(
            params, state["tokens"], cfg, positions=positions,
            caches=state["caches"], moe_capacity=moe_capacity,
            trace=use_policy, **slot_kw)
        if sample:
            rng, sub = jax.random.split(state["rng"])
            nxt = jax.random.categorical(
                sub, logits[:, -1] / temperature, axis=-1)[:, None]
        else:
            rng = state["rng"]
            nxt = jnp.argmax(logits[:, -1:], axis=-1)
        if per_slot:
            # retired/empty slots hold position (their cache row is dead
            # weight until the next admission overwrites it)
            new_pos = state["pos"] + active.astype(jnp.int32)
        else:
            new_pos = state["pos"] + 1
        new_state = dict(state, tokens=nxt.astype(jnp.int32),
                         pos=new_pos, caches=caches, rng=rng)
        telemetry = {}
        if use_policy:
            workloads, obs = collect_policy_obs(
                params, infos, cfg, token_mask=active, res_vecs=res_vecs)
            new_pstate, decisions = policy.step(state["dali"], workloads,
                                                obs)
            telemetry = decisions.tel
            new_state["dali"] = new_pstate
        return new_state, logits, telemetry

    return decode


class ResilientDecode:
    """Decode-variant switchboard driven by the store's degradation
    ladder (DESIGN.md §10).

    ``slot_fetch.fallback`` and the policy's DaliConfig cost constants
    are trace-time facts, so the ladder's reactions cannot be switched
    in-graph — instead the serving tier keeps at most THREE jitted
    decode variants and selects one per step:

      * ``healthy``  — the base policy, the store's own fallback;
      * ``degraded`` — the policy re-solved with the watchdog's re-fit
        ``t_trans`` and a zeroed prefetch budget
        (``ExpertStore.degraded_policy`` — the paper's workload-aware
        assignment reacting to hardware state);
      * ``little``   — the degraded policy plus ``fallback="little"``
        (misses read the resident int8 twins; streaming is suspended by
        the store itself).

    Variants compile lazily on first entry into each rung, so a healthy
    run pays exactly one compile — same as before this class existed.
    The policy state pytree is structurally identical across variants
    (only cost constants change), so ``state["dali"]`` flows through
    transitions untouched.  ``react()`` aligns the active variant with
    the ladder after each ``pre_step``; with no ladder (no faults) the
    switchboard collapses to the single healthy variant."""

    RUNGS = ("healthy", "degraded", "little")

    def __init__(self, cfg: ModelConfig,
                 dali_cfg: Optional[DaliConfig] = None,
                 moe_capacity: Optional[int] = None, sample: bool = False,
                 temperature: float = 1.0, policy=None, offload=None,
                 jit: bool = True):
        self.cfg = cfg
        self.offload = offload
        self.policy = resolve_policy(policy, cfg, dali_cfg)
        self._kw = dict(moe_capacity=moe_capacity, sample=sample,
                        temperature=temperature)
        self._jit = jit
        self._variants = {}
        self.active = "healthy"

    def _build(self, rung: str, jit: Optional[bool] = None):
        if rung not in self.RUNGS:
            raise ValueError(f"rung must be one of {'|'.join(self.RUNGS)}, "
                             f"got {rung!r}")
        if rung == "healthy" or self.offload is None:
            pol, fb = self.policy, None
        else:
            pol = self.offload.degraded_policy(self.policy)
            fb = "little" if rung == "little" else None
        with _internal():      # variant builds are not legacy call sites
            step = make_decode_step(self.cfg, policy=pol,
                                    offload=self.offload, fallback=fb,
                                    **self._kw)

        def fn(params, state, res_vecs=None):
            # the slot pool goes in and never comes out: it is the
            # store's, and an undonated output would make XLA copy the
            # whole pool every step (7 GB at Mixtral widths); __call__
            # hands the caller's pool back on the new state
            new_state, logits, tel = step(params, state, res_vecs)
            new_state.pop("offload", None)
            return new_state, logits, tel

        jit = self._jit if jit is None else jit
        return jax.jit(fn) if jit else fn

    def variant(self, rung: str, jit: Optional[bool] = None):
        """A freshly built (uncached) decode variant for ``rung`` — the
        graph auditor's enumeration hook (repro/analysis).  ``jit=False``
        returns the raw python callable for jaxpr-level analysis without
        touching the serving cache in ``_variants``."""
        return self._build(rung, jit=jit)

    def react(self):
        """Align the active variant with the store's ladder state.
        Returns the (from, to) rung transition when it changed, None
        otherwise.  Call after ``store.pre_step`` (where the ladder
        advances) and before dispatching the decode."""
        store = self.offload
        if store is None or getattr(store, "ladder", None) is None:
            return None
        want = store.ladder.state
        if want == self.active:
            return None
        frm, self.active = self.active, want
        return (frm, want)

    def __call__(self, params, state, res_vecs=None):
        rung = self.active
        fn = self._variants.get(rung)
        if fn is None:
            fn = self._variants[rung] = self._build(rung)
        new_state, logits, tel = fn(params, state, res_vecs)
        if "offload" in state:
            new_state["offload"] = state["offload"]
        return new_state, logits, tel


def init_serve_state(cfg: ModelConfig, batch: int, max_len: int,
                     dali_cfg: Optional[DaliConfig] = None,
                     dtype=None, n_cross: Optional[int] = None, seed: int = 0,
                     per_slot: bool = False, policy=None, offload=None):
    state = {
        "tokens": jnp.zeros((batch, 1), jnp.int32),
        "pos": (jnp.zeros((batch,), jnp.int32) if per_slot
                else jnp.zeros((), jnp.int32)),
        "caches": init_caches(cfg, batch, max_len, dtype=dtype,
                              n_cross=n_cross),
        "rng": jax.random.PRNGKey(seed),
    }
    if per_slot:
        state["active"] = jnp.zeros((batch,), bool)
    policy = resolve_policy(policy, cfg, dali_cfg)
    if policy.schedules and cfg.moe is not None:
        state["dali"] = policy.init()
    if offload is not None:
        # legacy offload-kwarg construction; ServeSpec.resolve() reaches
        # this via ResolvedServe.init_state() without warning
        warn_legacy("init_serve_state(offload=...)")
        require_offload_policy(policy, cfg)
        import numpy as np
        state["offload"] = offload.init_device_state(
            np.asarray(state["dali"]["resident"]))
    return state


def default_dali_config(cfg: ModelConfig, cache_ratio: float = 0.25,
                        prefetch_size: int = 1, w_size: int = 4,
                        u_size: int = 1) -> Optional[DaliConfig]:
    """Paper defaults: cache 25-50% of experts/layer; (w,u)=(4,1) Mixtral-
    like, (4,8) for many-expert models (§6.4).  Cost constants come from
    the running device's profile (``cost_model.profile_for_device``)."""
    if cfg.moe is None:
        return None
    from repro.core.cost_model import CostModel
    from repro.models.config import layer_pattern
    n_moe = sum(1 for _, mlp in layer_pattern(cfg) if mlp == "moe")
    E = cfg.moe.n_routed
    cm = CostModel.for_config(cfg)          # priced for the device in use
    return DaliConfig.from_cost_model(
        cm, n_moe_layers=n_moe, n_experts=E,
        cache_size=max(1, int(E * cache_ratio)),
        prefetch_size=prefetch_size, w_size=w_size,
        u_size=min(u_size, max(1, E // 2)))
