"""Full-model assembly: embedding -> (prefix blocks + scanned super-block
stacks) -> final norm -> unembedding.  Optionally an encoder stack (enc-dec
archs) whose output feeds decoder cross-attention.

HLO size is O(pattern period): homogeneous super-blocks are stacked along a
leading axis and executed under ``lax.scan`` (essential for the 126-layer
llama3-405b dry-run and standard practice at production scale).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .blocks import apply_block, init_block, init_block_cache
from .config import ModelConfig, scan_pattern
from .layers import embed, init_embedding, init_norm, apply_norm, unembed


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_stack(key, cfg: ModelConfig, pattern, n_super: int):
    """Stacked params: tuple over pattern positions, leaves (n_super, ...)."""
    out = []
    for p, kinds in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, p), n_super)
        out.append(jax.vmap(lambda k: init_block(k, cfg, kinds))(keys))
    return tuple(out)


def init_model(key, cfg: ModelConfig):
    prefix_pat, period_pat, n_super = scan_pattern(cfg)
    ks = jax.random.split(key, 6)
    params = {
        "embed": init_embedding(ks[0], cfg),
        "final_norm": init_norm(ks[1], cfg),
        "prefix": tuple(init_block(jax.random.fold_in(ks[2], i), cfg, kinds)
                        for i, kinds in enumerate(prefix_pat)),
        "scan": _init_stack(ks[3], cfg, period_pat, n_super),
    }
    if cfg.encoder is not None:
        enc_cfg = cfg
        params["encoder"] = {
            "stack": _init_stack(ks[4], enc_cfg, (("attn", "dense"),),
                                 cfg.encoder.n_layers),
            "final_norm": init_norm(ks[5], cfg),
        }
    return params


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None, n_cross: Optional[int] = None):
    prefix_pat, period_pat, n_super = scan_pattern(cfg)
    mk = lambda kinds: init_block_cache(cfg, kinds, batch, max_len,
                                        dtype=dtype, n_cross=n_cross)
    stack = lambda c: jax.tree.map(
        lambda a: jnp.repeat(a[None], n_super, axis=0), c)
    return {
        "prefix": tuple(mk(kinds) for kinds in prefix_pat),
        "scan": tuple(stack(mk(kinds)) for kinds in period_pat),
    }


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------

def apply_encoder(params, src, cfg: ModelConfig):
    """Bidirectional encoder over precomputed frame embeddings (B,T,d)."""
    T = src.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    x = src

    def body(x, p_slice):
        x, _, _ = apply_block(p_slice, x, cfg, ("attn", "dense"),
                              positions=positions, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["stack"][0])
    return apply_norm(params["final_norm"], x, cfg)


def apply_model(params, tokens, cfg: ModelConfig, *, positions=None,
                caches=None, cross_src=None, moe_capacity=None,
                count_overlap=None,
                trace: bool = False, last_logit_only: bool = False,
                logit_index=None, expert_slots=None, slot_fetch=None,
                slot_live=None, slot_little=None,
                slot_phase: str = "decode"):
    """tokens (B, S) int32.  Returns (logits, new_caches, infos) where infos
    is a list (prefix layers) + list (scan stacks, leaves stacked (n_super,
    ...)) of MoE routing observables (None for non-MoE blocks).

    ``positions`` is (S,) shared across the batch, or (B, S) per-slot
    offsets for continuous batching (see attention.py).  ``logit_index``
    (traced scalar) unembeds only that sequence position — the
    prefill-on-admit path where the last *real* token of a right-padded
    prompt sits at ``length - 1``, not at ``S - 1``.

    ``expert_slots`` (an ``ExpertStore.build_view`` pytree: per-MoE-layer
    slot tables, scan entries stacked (n_super, ...), and the whole
    device slot pool under ``"pool_rows"``, a scan constant) plus
    ``slot_fetch`` (the store, for miss fallbacks) switch MoE layers to
    the physical-offload slot path; slot slices thread through the scan
    exactly like caches — with a pipelined store the view additionally
    carries per-layer expert→inject-row maps in the xs plus the staged
    insert rows themselves under ``"inject_rows"``, a scan CONSTANT the
    FFN indexes ``[lid, row]`` (each layer resolves this step's plan
    without the buffers being sliced through the scan, DESIGN.md §9).
    ``slot_live`` (B·S,) bool marks live batch slots so dead rows never
    trigger miss fallbacks (invariant across layers — a scan constant,
    not an xs).  ``slot_little`` (``ExpertStore.little_view``: resident
    int8 twins of every (L, E) expert, indexed ``[lid, e]``) feeds the
    ``fallback="little"`` degradation rung — also a scan constant.
    ``slot_phase`` ("decode" | "prefill") selects the slot execution
    regime per apply_moe: prefill-sized inputs assemble dense sweeps
    with wave-streamed misses instead of the per-slot gathered path
    (DESIGN.md §11).  ``count_overlap`` threads to apply_moe's EP
    exchange (hoist the count all_to_all ahead of the dispatch math)."""
    prefix_pat, period_pat, n_super = scan_pattern(cfg)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)

    if cfg.encoder is not None and cross_src is not None:
        cross_src = apply_encoder(params["encoder"], cross_src, cfg)

    from repro.launch.sharding import hint
    x = hint(embed(params["embed"], tokens, cfg),
             "batch", "res_seq", "embed")
    slots_prefix = (expert_slots["prefix"] if expert_slots is not None
                    else tuple(None for _ in prefix_pat))
    slots_scan = (expert_slots["scan"] if expert_slots is not None
                  else tuple(None for _ in period_pat))
    # a pipelined store's staged insert rows: one (buf_cap, ...) buffer
    # set shared by every layer — closed over by the scan body as a
    # CONSTANT (indexed [row] inside slot_expert_ffn), never sliced
    # through the scan's xs
    slot_inject = (expert_slots.get("inject_rows")
                   if expert_slots is not None else None)
    # likewise the whole (L, n_slots, ...) slot pool: each layer's slot
    # dict gets it as a constant, indexed [lid, slot] by the FFN
    pool = (expert_slots.get("pool_rows")
            if expert_slots is not None else None)

    def with_pool(s):
        return s if s is None or pool is None else dict(s, pool=pool)

    infos = []
    new_prefix_caches = []
    for i, kinds in enumerate(prefix_pat):
        c = caches["prefix"][i] if caches is not None else None
        x, c, info = apply_block(params["prefix"][i], x, cfg, kinds,
                                 positions=positions, cache=c,
                                 cross_src=cross_src,
                                 moe_capacity=moe_capacity,
                                 count_overlap=count_overlap,
                                 slots=with_pool(slots_prefix[i]),
                                 slot_fetch=slot_fetch,
                                 slot_live=slot_live,
                                 slot_inject=slot_inject,
                                 slot_little=slot_little,
                                 slot_phase=slot_phase)
        new_prefix_caches.append(c)
        infos.append(_trim_info(info, trace))

    def body(x, sliced):
        p_slices, c_slices, s_slices = sliced
        step_infos = []
        new_cs = []
        for p, kinds in enumerate(period_pat):
            c = c_slices[p] if c_slices is not None else None
            x, c, info = apply_block(p_slices[p], x, cfg, kinds,
                                     positions=positions, cache=c,
                                     cross_src=cross_src,
                                     moe_capacity=moe_capacity,
                                     count_overlap=count_overlap,
                                     slots=with_pool(s_slices[p]),
                                     slot_fetch=slot_fetch,
                                     slot_live=slot_live,
                                     slot_inject=slot_inject,
                                     slot_little=slot_little,
                                     slot_phase=slot_phase)
            x = hint(x, "batch", "res_seq", "embed")
            new_cs.append(c)
            step_infos.append(_trim_info(info, trace))
        return x, (tuple(new_cs), tuple(step_infos))

    if cfg.remat:
        body = jax.checkpoint(body)

    scan_caches = caches["scan"] if caches is not None else None
    xs = (params["scan"], scan_caches, slots_scan)
    x, (new_scan_caches, scan_infos) = jax.lax.scan(body, x, xs)
    infos.append(scan_infos)

    if logit_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    elif last_logit_only:
        x = x[:, -1:]      # serving prefill: only the last position samples
    x = apply_norm(params["final_norm"], x, cfg)
    logits = hint(unembed(params["embed"], x, cfg), "batch", "seq", "vocab")
    new_caches = None
    if caches is not None:
        new_caches = {"prefix": tuple(new_prefix_caches),
                      "scan": new_scan_caches}
    return logits, new_caches, infos


def _trim_info(info, trace: bool):
    if info is None:
        return None
    if trace:
        return info
    return {k: info[k] for k in ("workload", "aux_loss", "z_loss", "dropped")}


# --------------------------------------------------------------------------
# info reduction helpers
# --------------------------------------------------------------------------

def collect_moe_scalars(infos):
    """Sum aux/z losses over all MoE blocks (prefix + scanned stacks)."""
    aux = z = jnp.zeros((), jnp.float32)
    dropped = jnp.zeros((), jnp.int32)
    for info in infos:
        if info is None:
            continue
        if isinstance(info, tuple):        # scan stack: tuple per position
            for sub in info:
                if sub is None:
                    continue
                aux += jnp.sum(sub["aux_loss"])
                z += jnp.sum(sub["z_loss"])
                dropped += jnp.sum(sub["dropped"])
        else:
            aux += info["aux_loss"]
            z += info["z_loss"]
            dropped += info["dropped"]
    return {"aux_loss": aux, "z_loss": z, "dropped": dropped}


def collect_field(infos, field):
    """Stack a per-MoE-layer info field -> (n_moe_layers, ...) in true layer
    order (prefix first, then scanned stacks super-block-major)."""
    rows = []
    for info in infos:
        if info is None:
            continue
        if isinstance(info, tuple):
            per_pos = [sub[field] for sub in info if sub is not None]
            if not per_pos:
                continue
            stacked = jnp.stack(per_pos, axis=1)  # (n_super, n_moe_pos, ...)
            rows.append(stacked.reshape((-1,) + stacked.shape[2:]))
        else:
            rows.append(info[field][None])
    return jnp.concatenate(rows, axis=0) if rows else None


def stack_routers(params, cfg: ModelConfig):
    """Router weights stacked (n_moe_layers, d, E) in the same layer order
    as ``collect_field`` (prefix MoE layers, then scan super-block-major)."""
    prefix_pat, period_pat, n_super = scan_pattern(cfg)
    rows = []
    for i, (_, mlp) in enumerate(prefix_pat):
        if mlp == "moe":
            rows.append(params["prefix"][i]["mlp"]["router"][None])
    per_pos = [params["scan"][p]["mlp"]["router"]
               for p, (_, mlp) in enumerate(period_pat) if mlp == "moe"]
    if per_pos:
        stacked = jnp.stack(per_pos, axis=1)      # (n_super, n_pos, d, E)
        rows.append(stacked.reshape((-1,) + stacked.shape[2:]))
    return jnp.concatenate(rows, axis=0) if rows else None


def collect_policy_obs(params, infos, cfg: ModelConfig, token_mask=None,
                       res_vecs=None):
    """Build ``(workloads, Observation)`` for an OffloadPolicy step from a
    traced forward's infos (``apply_model(trace=True)``).

    With a ``token_mask`` (continuous batching: (T,) live-slot bools) the
    per-expert workloads are recounted from per-token routing choices so
    the policy sees only real traffic; otherwise the layer-summed workload
    field is used directly.  ``res_vecs`` defaults to zeros (uncalibrated
    residual correction)."""
    from repro.core.engine import masked_workloads
    from repro.core.policy import Observation
    gate_in = collect_field(infos, "gate_in")               # (L, T, d)
    routers = stack_routers(params, cfg)                    # (L, d, E)
    if token_mask is not None:
        topk = collect_field(infos, "topk_idx")             # (L, T, K)
        workloads = masked_workloads(topk, cfg.moe.n_routed, token_mask)
    else:
        workloads = collect_field(infos, "workload")        # (L, E)
    if res_vecs is None:
        res_vecs = jnp.zeros((workloads.shape[0], cfg.d_model), jnp.float32)
    return workloads, Observation(gate_in=gate_in, routers=routers,
                                  res_vecs=res_vecs, token_mask=token_mask)


def collect_workloads(infos):
    """Stack per-MoE-layer workload vectors -> (n_moe_layers, E) in layer
    order (prefix first, then scan stacks position-major per super-block)."""
    rows = []
    for info in infos:
        if info is None:
            continue
        if isinstance(info, tuple):
            # scan infos: each position p has leaves stacked (n_super, ...)
            per_pos = [sub["workload"] for sub in info if sub is not None]
            if not per_pos:
                continue
            # interleave in true layer order: super-block major
            n_super = per_pos[0].shape[0]
            stacked = jnp.stack(per_pos, axis=1)   # (n_super, n_moe_pos, E)
            rows.append(stacked.reshape(-1, stacked.shape[-1]))
        else:
            rows.append(info["workload"][None])
    return jnp.concatenate(rows, axis=0) if rows else None
