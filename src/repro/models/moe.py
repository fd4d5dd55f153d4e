"""Mixture-of-Experts layer with workload-aware execution paths.

Dispatch is sort/gather based (MegaBlocks-style, adapted to static shapes):
tokens are ordered by assigned expert via argsort, sliced into per-expert
capacity buckets of static size C, run through the expert FFNs as one
batched (E, C, d) computation, and scatter-added back.  This avoids the
O(T·E·C·d) one-hot dispatch matmuls of the classic Switch formulation —
dispatch/combine are pure data movement, so compiled FLOPs stay ~the useful
expert FLOPs (visible in the roofline's MODEL_FLOPS/HLO_FLOPs ratio).

Two execution paths share that routing front-end (DESIGN.md §4):

* **dense** — the (E, C, d) capacity-bucket sweep above.  Right for
  prefill/training where most experts see real traffic; on TPU the bucket
  compute routes through the grouped Pallas kernel with per-expert counts
  so empty capacity blocks skip their MXU work.
* **sparse decode fast path** — when a step activates few enough
  (token, k) slots to undercut the dense sweep's minimum bucket work by
  the measured gather-overhead break-even (``T·K·O < E·C_min``, see
  ``use_sparse_path``), gather the activated experts' weight slices and
  run a per-token grouped SwiGLU.  No zero buckets, no drops by
  construction; cost scales with the *actual* workload — the same
  observable DALI schedules on.  The rule is static in shapes, so it
  jits into the existing serving decode step.

The layer also returns the per-expert *workload* vector (token counts) and
per-token routing choices — exactly the quantities DALI's scheduler,
prefetcher and cache operate on (paper §4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from .config import ModelConfig, MoEConfig
from .layers import _ACTS, dense_init, init_mlp, apply_mlp


# --------------------------------------------------------------------------
# Callback seam registry (DESIGN.md §12)
# --------------------------------------------------------------------------
# Host callbacks are the ONLY host<->device seams a serving graph may
# contain, and every one must be declared here so the graph-contract
# auditor (repro/analysis/jaxpr_audit.py) can match each pure_callback /
# io_callback equation in a lowered serving graph back to a known seam —
# an unmatched callback in a serving graph is an audit failure.  Seams
# are keyed on the underlying FUNCTION object (bound methods register
# their ``__func__``): that is what jax's callback closure exposes, and
# it survives proxies like ``steps._FallbackView`` that re-bind the same
# class function to a different receiver.

@dataclasses.dataclass(frozen=True)
class CallbackSeam:
    """One registered host<->device seam.

    kind           — "pure" (jax.pure_callback) | "io" (io_callback)
    cond_required  — the call site must sit under a ``lax.cond`` so an
                     all-hit step never leaves the device (the decode
                     fast-path contract)
    """
    name: str
    kind: str
    cond_required: bool = True
    module: str = ""


CALLBACK_SEAMS: dict = {}


def register_callback_seam(name: str, func, *, kind: str = "pure",
                           cond_required: bool = True) -> CallbackSeam:
    """Declare ``func`` (a function or bound/unbound method) as a legal
    callback target for serving graphs.  Idempotent per function."""
    fn = getattr(func, "__func__", func)
    seam = CallbackSeam(name=name, kind=kind, cond_required=cond_required,
                        module=getattr(fn, "__module__", ""))
    CALLBACK_SEAMS[fn] = seam
    return seam


def lookup_callback_seam(func):
    """The :class:`CallbackSeam` registered for ``func`` (unwrapping
    bound methods and ``functools.partial`` chains), or None."""
    fn = func
    while True:
        if hasattr(fn, "__func__"):
            fn = fn.__func__
        elif hasattr(fn, "func") and callable(getattr(fn, "func")):
            fn = fn.func                     # functools.partial
        else:
            break
    return CALLBACK_SEAMS.get(fn)


def expert_capacity(cfg_m: MoEConfig, n_tokens: int) -> int:
    if cfg_m.capacity_factor <= 0:          # "full": no token ever dropped
        return n_tokens
    c = int(np.ceil(n_tokens * cfg_m.top_k / cfg_m.n_routed
                    * cfg_m.capacity_factor))
    return max(4, int(np.ceil(c / 4)) * 4)  # pad to tiling-friendly multiple


# the dense sweep never runs buckets smaller than this (the max(4, ...)
# floor above)
SPARSE_CMIN = 4
# the sparse path pays a per-slot weight-slice gather on top of its FLOPs,
# so it must undercut the dense sweep's minimum rows by this factor to
# win; measured break-even across E x batch in benchmarks/moe_dispatch.py
SPARSE_OVERHEAD = 4


def use_sparse_path(m: MoEConfig, n_tokens: int,
                    capacity: Optional[int]) -> bool:
    """Static path-selection rule (DESIGN.md §4): take the gathered sparse
    path when the activated (token, k) slots undercut the dense sweep's
    minimum bucket work E·C_min by the gather-overhead factor.  Shape-only,
    so each jitted step function compiles exactly one path.  An explicit
    ``capacity`` pins the dense path — its drop semantics are part of the
    caller's contract (dry-run shape lowering, chunked prefill)."""
    return (capacity is None
            and n_tokens * m.top_k * SPARSE_OVERHEAD
            < m.n_routed * SPARSE_CMIN)


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    de = m.d_expert or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)

    def stack_init(k, shape):
        kk = jax.random.split(k, m.n_routed)
        return jax.vmap(lambda k_: dense_init(k_, shape, dt))(kk)

    p = {
        "router": dense_init(ks[0], (d, m.n_routed), jnp.float32),
        "gate": stack_init(ks[1], (d, de)),
        "up": stack_init(ks[2], (d, de)),
        "down": stack_init(ks[3], (de, d)),
    }
    if m.n_shared:
        ds = m.d_shared or m.n_shared * de
        shared_cfg = cfg.replace()
        p["shared"] = init_mlp(ks[4], shared_cfg, d_ff=ds)
    return p


def route(params, x_flat, m: MoEConfig):
    """x_flat (T, d) -> (gates (T,k), idx (T,k), probs (T,E), logits)."""
    logits = (x_flat.astype(jnp.float32) @ params["router"])     # (T,E)
    if m.router_type == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(probs, m.top_k)
    elif m.router_type == "topk_softmax":                        # Mixtral
        top_logits, idx = jax.lax.top_k(logits, m.top_k)
        gates = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
    else:                                                        # softmax_topk
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.renormalize and m.router_type != "topk_softmax":
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    return gates, idx, probs, logits


def expert_ffn_dense(params, xe, cfg: ModelConfig, counts=None):
    """Batched per-expert SwiGLU: xe (E, C, d) -> (E, C, d).

    On TPU (single device, no active mesh) this routes through the grouped
    Pallas kernel in repro.kernels.expert_ffn, passing per-expert
    ``counts`` so empty/partial capacity blocks skip their MXU work
    (skip-empty, MegaBlocks-style).  Elsewhere the jnp einsum path below
    runs — it is also the kernel's oracle.  Rows at or beyond ``counts[e]``
    are zero on both paths (the dispatch zero-fills them)."""
    from repro.launch.sharding import active, hint
    if jax.default_backend() == "tpu" and active()["mesh"] is None:
        from repro.kernels.expert_ffn.ops import expert_ffn_op
        return expert_ffn_op(xe, params["gate"], params["up"],
                             params["down"], act=cfg.act, counts=counts)
    act = _ACTS[cfg.act]
    h = act(jnp.einsum("ecd,edf->ecf", xe, params["gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, params["up"])
    h = hint(h, "experts", "cap", "expert_ffn")
    return jnp.einsum("ecf,efd->ecd", h, params["down"])


def _grouped_ffn_rows(xf, wg, wu, wd, cfg: ModelConfig):
    """Per-(token, k) SwiGLU over gathered weight slices: xf (T, d),
    wg/wu (T·K, d, f), wd (T·K, f, d) -> ys (T·K, d).  The single
    contraction body shared by the full-resident sparse path and the
    slot-pool path — byte-identical weight rows therefore produce
    bit-identical outputs whichever store they were gathered from.

    Operands and result pass an optimization barrier.  Without it XLA
    fuses each caller's own weight producer (a stack gather; a pool
    gather under inject/fetch selects) into the contractions, and on TPU
    the fused reduction order, hence the bf16 rounding, follows the
    producer: the two paths then differ in the last bits."""
    xf, wg, wu, wd = jax.lax.optimization_barrier((xf, wg, wu, wd))
    K = wg.shape[0] // xf.shape[0]
    xs = jnp.repeat(xf, K, axis=0)                 # (T*K, d)
    act = _ACTS[cfg.act]
    h = act(jnp.einsum("td,tdf->tf", xs, wg)) \
        * jnp.einsum("td,tdf->tf", xs, wu)
    ys = jnp.einsum("tf,tfd->td", h, wd)           # (T*K, d)
    return jax.lax.optimization_barrier(ys)


def _combine_topk(ys, gates):
    """Weighted sum of per-(token, k) rows back to (T, d)."""
    T, K = gates.shape
    return jnp.sum(ys.reshape(T, K, -1)
                   * gates.astype(ys.dtype)[..., None], axis=1)


def grouped_expert_ffn(params, xf, idx, gates, cfg: ModelConfig):
    """Sparse decode fast path: per-(token, k) gathered-weight SwiGLU.

    Gathers the T·K activated experts' weight slices and contracts each
    (token, k) slot against its own slice — no capacity buckets, no
    zero-bucket compute, and no drops by construction (every slot keeps
    its expert).  Cost scales with the actual activated workload T·K
    instead of the dense E·C sweep.  xf (T, d), idx/gates (T, K) ->
    combined output (T, d)."""
    flat_e = idx.reshape(-1)                       # (T*K,) activated experts
    ys = _grouped_ffn_rows(xf, params["gate"][flat_e], params["up"][flat_e],
                           params["down"][flat_e], cfg)
    return _combine_topk(ys, gates)


def slot_expert_ffn(slots, slot_fetch, xf, idx, gates, cfg: ModelConfig,
                    live=None, slot_inject=None, slot_little=None):
    """Physical-offload decode path: weights come from the device slot
    pool instead of a full (E, ...) stack (serving/expert_store.py).

    ``slots`` is one layer's slot view: slot_of (E,) int32 expert->slot
    (-1 = not pooled), lid () int32 layer id, and ``pool`` — the whole
    pool, gate/up (L, n_slots, d, f), down (L, n_slots, f, d), indexed
    ``[lid, slot]``.  Pooled experts gather their slot rows;
    misses fall back to the host tier via ``slot_fetch`` (an ExpertStore)
    under ``lax.cond`` so fully-resident steps never leave the device:

      * fallback "fetch" — each distinct missing expert's weights
        stream once from the host store (one pure_callback H2D per
        expert, ``_fetch_distinct_misses``) into the (token, k) rows
        that miss it, and the FFN stays on device, so the output is
        bit-identical to the full-resident gather;
      * fallback "host" — missing rows' FFN executes on the host (CPU
        tier) and only (d,)-sized outputs cross back;
      * fallback "little" — missing rows read ``slot_little``, the
        always-resident int8 twin pool of EVERY (L, E) expert
        (ExpertStore.little_view, DESIGN.md §10): a pure device
        gather + dequantize, no callback and no cond, so a persistent
        miss costs int8 quality instead of a host round trip.

    ``live`` (T,) bool marks real tokens (continuous batching: live batch
    slots).  Dead rows never count as misses — a retired slot's garbage
    token must not trigger host round trips for experts the policy (which
    sees only masked workloads) will never cache; its output rows are
    computed from whatever pool row the clipped gather lands on and are
    discarded by the server anyway.
    """
    T, d = xf.shape
    K = idx.shape[1]
    flat_e = idx.reshape(-1)                       # (T*K,)
    slot = slots["slot_of"][flat_e]
    hit = slot >= 0
    if live is not None:
        hit = hit | ~jnp.repeat(live, K)
    srow = jnp.clip(slot, 0)
    pool, lid = slots["pool"], slots["lid"]
    wg = pool["gate"][lid, srow]                   # (T*K, d, f)
    wu = pool["up"][lid, srow]
    wd = pool["down"][lid, srow]
    if slot_inject is not None:
        # pipelined offload (DESIGN.md §9): an inserted expert reads its
        # freshly staged inject row (slot_of, built from the post-plan
        # table, already points at its slot; the pool row underneath
        # stays stale until the buffer folds).  The (buf_cap, ...)
        # inject buffers hold GLOBAL rows shared by all layers and are
        # a scan CONSTANT — the per-layer expert→row map inj_of rides
        # the xs, so only the activated rows are ever gathered
        ipos = slots["inj_of"][flat_e]             # (T*K,) inject row or -1
        use_inj = (ipos >= 0)[:, None, None]
        irow = jnp.clip(ipos, 0)
        wg = jnp.where(use_inj, slot_inject["gate"][irow], wg)
        wu = jnp.where(use_inj, slot_inject["up"][irow], wu)
        wd = jnp.where(use_inj, slot_inject["down"][irow], wd)
    any_miss = jnp.any(~hit)
    if slot_fetch.fallback == "little":
        if slot_little is None:
            raise ValueError('fallback="little" needs the slot_little '
                             "twin pool (ExpertStore.little_view())")
        # the twins are read fully in-graph, so miss accounting can't
        # ride a weights callback like the other tiers — io_callback is
        # effectful (never DCEd) and only fires on actual-miss steps
        jax.lax.cond(
            any_miss,
            lambda h: io_callback(slot_fetch.little_miss_cb,
                                  jax.ShapeDtypeStruct((), jnp.int32), h),
            lambda h: jnp.int32(0), hit)
        lid = slots["lid"]
        dt = wg.dtype

        def deq(qk, sk):
            q = slot_little[qk][lid, flat_e].astype(jnp.float32)
            s = slot_little[sk][lid, flat_e]       # (T*K, 1, out) scales
            return (q * s).astype(dt)

        hw = hit[:, None, None]
        ys = _grouped_ffn_rows(
            xf,
            jnp.where(hw, wg, deq("gate_q", "gate_s")),
            jnp.where(hw, wu, deq("up_q", "up_s")),
            jnp.where(hw, wd, deq("down_q", "down_s")), cfg)
    elif slot_fetch.fallback == "host":
        hm = hit[:, None]
        ys = _grouped_ffn_rows(xf, jnp.where(hit[:, None, None], wg, 0),
                               jnp.where(hit[:, None, None], wu, 0),
                               jnp.where(hit[:, None, None], wd, 0), cfg)
        shape = jax.ShapeDtypeStruct(ys.shape, ys.dtype)
        ys_host = jax.lax.cond(
            any_miss,
            lambda a: jax.pure_callback(slot_fetch.host_ffn_cb, shape, *a),
            lambda a: jnp.zeros(ys.shape, ys.dtype),
            (slots["lid"], xf, flat_e, hit))
        ys = jnp.where(hm, ys, ys_host)
    else:                                          # "fetch"
        wg, wu, wd = _fetch_distinct_misses(
            slot_fetch, slots["lid"], flat_e, hit,
            slots["slot_of"].shape[0], (wg, wu, wd))
        ys = _grouped_ffn_rows(xf, wg, wu, wd, cfg)
    return _combine_topk(ys, gates)


def _fetch_distinct_misses(slot_fetch, lid, flat_e, hit, E, rows):
    """Demand-fetch each distinct missing expert of one layer once and
    write it into every (token, k) row that misses it.

    ``flat_e``/``hit`` (T·K,) name the rows' experts and whether each is
    served on device; ``rows`` are the (T·K, ...) gate/up/down weights
    gathered from the pool (and inject buffers).  The missing experts,
    ``n_miss`` ≤ min(T·K, E) of them in ascending id order, are fetched
    by one ``fetch_weights_cb`` call each — a ``fori_loop`` of
    ``n_miss`` trips under ``lax.cond(n_miss > 0)``, so an all-hit layer
    never leaves the device — and each lands in its rows of ``rows`` in
    place: no device buffer beyond the gathered rows, and the bytes a
    row ends with are those a full-resident gather reads."""
    miss = ~hit
    rows_of_e = jnp.zeros((E,), jnp.int32).at[flat_e].add(
        miss.astype(jnp.int32))
    n_miss = jnp.sum(rows_of_e > 0)
    e_at = jnp.nonzero(rows_of_e > 0, size=E, fill_value=0)[0]
    one = tuple(jax.ShapeDtypeStruct(w.shape[1:], w.dtype) for w in rows)

    def body(i, rows):
        e = e_at[i]
        got = jax.pure_callback(slot_fetch.fetch_weights_cb, one,
                                lid, e, rows_of_e[e])
        at = (miss & (flat_e == e))[:, None, None]
        return tuple(jnp.where(at, g[None], w) for g, w in zip(got, rows))

    return jax.lax.cond(n_miss > 0,
                        lambda r: jax.lax.fori_loop(0, n_miss, body, r),
                        lambda r: r, tuple(rows))


def slot_expert_stacks(slots, slot_fetch, counts, cfg: ModelConfig,
                       slot_inject=None, slot_little=None):
    """Assemble FULL (E, ...) gate/up/down stacks for a prefill-sized
    dense sweep from the physical-offload tiers (DESIGN.md §11).

    Pooled experts copy their device slot rows in (a pipelined store's
    inject rows override the stale pool rows, §9); activated-but-missing
    experts stream from the host store in rank-compacted waves of at
    most ``slot_fetch.prefill_rows`` experts — each wave is one
    ``lax.cond``-guarded ``pure_callback`` (an all-hit layer never pays
    a host round trip) that scatters its rows into the stacks inside the
    firing branch, so waves run one after another.  Every source lands
    by in-place row copies into ONE (E, ...) buffer per matrix: at
    Mixtral widths a layer's stacks are 2.8 GB, and a gather/select
    formulation kept two or three such buffers live.  Non-activated
    experts keep
    zero rows: their capacity buckets are empty and the dense combine
    never gathers their output rows (``se == e`` implies
    ``counts[e] > 0``), so zeros are bit-safe and the assembled sweep is
    bit-identical to full-resident prefill.

    ``fallback="little"`` dequantizes the resident int8 twins into the
    missing rows instead (no callback, rel-err-bounded);
    ``fallback="host"`` leaves the missing rows zero and returns them in
    ``need`` so the caller can run their (token, k) rows on the host.
    Returns ``(stack_params, need)`` — ``need`` is all-False except for
    the host tier."""
    E = slots["slot_of"].shape[0]
    pool, lid = slots["pool"], slots["lid"]
    dt = pool["gate"].dtype
    d, f = pool["gate"].shape[2], pool["gate"].shape[3]
    slot_of = slots["slot_of"]
    pooled = slot_of >= 0
    names = ("gate", "up", "down")

    def put_rows(stacks, n, row_of, take):
        """Copy source row i — ``take(k, i)``, one (d, f) block of matrix
        k — into the stacks at the expert whose ``row_of`` (E,) entry is
        i (-1 = none).  Row-at-a-time in-place copies keep ONE (E, ...)
        buffer per matrix live and never materialize a source slice: at
        Mixtral widths each stack is 0.94 GB."""
        e_of = jnp.full((n,), E, jnp.int32).at[
            jnp.where(row_of >= 0, row_of, n)].set(
            jnp.arange(E, dtype=jnp.int32), mode="drop")

        def body(i, stacks):
            e = e_of[i]
            at = (jnp.minimum(e, E - 1), 0, 0)
            out = []
            for st, k in zip(stacks, names):
                keep = jax.lax.dynamic_slice(st, at, (1,) + st.shape[1:])
                out.append(jax.lax.dynamic_update_slice(
                    st, jnp.where(e < E, take(k, i)[None], keep), at))
            return tuple(out)

        return jax.lax.fori_loop(0, n, body, tuple(stacks))

    wg, wu, wd = put_rows(
        (jnp.zeros((E, d, f), dt), jnp.zeros((E, d, f), dt),
         jnp.zeros((E, f, d), dt)),
        pool["gate"].shape[1], slot_of, lambda k, i: pool[k][lid, i])
    if slot_inject is not None and "inj_of" in slots:
        ipos = slots["inj_of"]                     # (E,) inject row or -1
        # inject rows override the stale pool rows underneath (§9)
        wg, wu, wd = put_rows((wg, wu, wd), slot_inject["gate"].shape[0],
                              ipos, lambda k, i: slot_inject[k][i])
        pooled = pooled | (ipos >= 0)
    need = (counts > 0) & ~pooled
    none = jnp.zeros((E,), bool)
    if slot_fetch.fallback == "little":
        if slot_little is None:
            raise ValueError('fallback="little" needs the slot_little '
                             "twin pool (ExpertStore.little_view())")
        jax.lax.cond(
            jnp.any(need),
            lambda h: io_callback(slot_fetch.little_miss_cb,
                                  jax.ShapeDtypeStruct((), jnp.int32), h),
            lambda h: jnp.int32(0), ~need)

        def deq(qk, sk):
            q = slot_little[qk][lid].astype(jnp.float32)   # (E, ..., out)
            return (q * slot_little[sk][lid]).astype(dt)

        nw = need[:, None, None]
        wg = jnp.where(nw, deq("gate_q", "gate_s"), wg)
        wu = jnp.where(nw, deq("up_q", "up_s"), wu)
        wd = jnp.where(nw, deq("down_q", "down_s"), wd)
        return {"gate": wg, "up": wu, "down": wd}, none
    if slot_fetch.fallback == "host":
        return {"gate": wg, "up": wu, "down": wd}, need
    # "fetch": stream the missing activated experts in pool-budget waves
    P = int(slot_fetch.prefill_rows)
    n_waves = -(-E // P)                           # static unroll
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1  # (E,) rank among needed
    shapes = (jax.ShapeDtypeStruct((P, d, f), dt),
              jax.ShapeDtypeStruct((P, d, f), dt),
              jax.ShapeDtypeStruct((P, f, d), dt))
    def wave(args):
        rows, *stacks = args
        staged = dict(zip(names, jax.pure_callback(
            slot_fetch.prefill_fetch_cb, shapes, lid, rows)))
        return put_rows(stacks, P, rows, lambda k, i: staged[k][i])

    for w in range(n_waves):
        in_wave = need & (rank >= w * P) & (rank < (w + 1) * P)
        # expert -> staging row of this wave (-1 = not in it); the
        # staging buffer lives only inside the firing branch, and each
        # wave consumes the previous one's stacks, so one wave's
        # (P, ...) staging is live at a time
        rows = jnp.where(in_wave, rank - w * P, -1).astype(jnp.int32)
        wg, wu, wd = jax.lax.cond(jnp.any(in_wave), wave,
                                  lambda a: tuple(a[1:]),
                                  (rows, wg, wu, wd))
    return {"gate": wg, "up": wu, "down": wd}, none


# token-chunked execution: data-dependent dispatch gathers make GSPMD
# replicate token-sized buffers, so bound them by scanning over chunks of
# at most this many tokens (per-chunk capacity keeps the same expected
# per-expert throughput; standard long-sequence MoE practice).
MOE_CHUNK_TOKENS = 16384


def _workload_counts(flat_e, E, valid_rep):
    """Per-expert token counts over the activated (token, k) slots.  With a
    validity mask, padded slots are binned into a virtual expert E and
    sliced off, so they never count toward the workload."""
    if valid_rep is None:
        return jnp.bincount(flat_e, length=E)
    return jnp.bincount(jnp.where(valid_rep, flat_e, E), length=E + 1)[:E]


def local_dispatch(xf, idx, E, K, C, valid_rep=None):
    """Sort/gather capacity-bucket dispatch (the one copy of the index
    math — the single-device dense path and the EP shard body both use
    it).  Invalid (token, k) slots sort into a virtual expert E so they
    never occupy a capacity slot nor count toward the workload.

    Returns ``(xe, counts, se, rank, inv)``: the (E, C, d) buckets with
    rows at/beyond the packed count zero-filled, the raw per-expert
    demand, and the combine contract — sorted-slot expert keys ``se``
    (E for invalid slots), in-expert ranks ``rank``, and the inverse
    permutation ``inv`` mapping sorted slots back to (token, k) order —
    so callers never re-derive the argsort inversion."""
    T = xf.shape[0]
    flat_e = idx.reshape(-1)                   # (T*K,) expert ids, k-minor
    flat_t = jnp.repeat(jnp.arange(T), K)      # source token per slot
    key = flat_e if valid_rep is None else jnp.where(valid_rep, flat_e, E)
    order = jnp.argsort(key, stable=True)      # group by expert
    se, st = key[order], flat_t[order]
    counts_ext = jnp.bincount(key, length=E + 1)
    counts = counts_ext[:E]                                       # workload
    offsets = jnp.concatenate([jnp.zeros((1,), counts_ext.dtype),
                               jnp.cumsum(counts_ext)[:-1]])
    rank = jnp.arange(T * K) - offsets[se]     # rank within expert group
    # gather tokens into (E, C) capacity buckets
    pos = offsets[:E, None] + jnp.arange(C)[None, :]              # (E, C)
    bucket_valid = jnp.arange(C)[None, :] < jnp.minimum(counts[:, None], C)
    src = st[jnp.clip(pos, 0, T * K - 1)]                         # (E, C)
    xe = jnp.where(bucket_valid[..., None], xf[src], 0)
    inv = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    return xe, counts, se, rank, inv


def apply_moe(params, x, cfg: ModelConfig, *, capacity: Optional[int] = None,
              valid=None, force_path: Optional[str] = None,
              force_exchange: Optional[str] = None,
              count_overlap: Optional[bool] = None,
              placement=None, demand_view: bool = False,
              slots=None, slot_fetch=None, slot_live=None,
              slot_inject=None, slot_little=None,
              slot_phase: str = "decode"):
    """Returns (y, info) where info carries DALI's routing observables.

    ``valid`` (T,) bool marks real tokens (None = all real): padded tokens
    are excluded from capacity buckets, workload counts and aux losses,
    and their combined output rows are zero (shared-expert output for them
    is garbage the caller slices off — the chunked path below does).
    ``force_path`` pins the execution path ("dense" | "sparse") for tests
    and benchmarks; by default ``use_sparse_path`` selects statically from
    shapes.  ``force_exchange`` pins the expert-parallel exchange flavor
    ("dense" | "ragged", see moe_ep.apply_moe_ep) and only matters when
    the EP path is taken; so does ``count_overlap`` (None = on), which
    hoists the ragged exchange's tiny count all_to_all ahead of the
    dispatch index math so its round trip overlaps adjacent compute
    (DESIGN.md §9).  ``placement`` / ``demand_view`` thread the
    topology-aware expert re-route controls through to the EP path
    (moe_ep.apply_moe_ep, DESIGN.md §13) and error off it.
    ``slots`` + ``slot_fetch`` (an ExpertStore)
    select the physical-offload slot-pool path; ``slot_live`` (T,) bool
    keeps dead batch slots from triggering miss fallbacks;
    ``slot_inject`` carries a pipelined store's staged insert rows
    (scan-constant global-row (buf_cap, ...) buffers, §9); routing/
    workload observables stay identical to the other paths (DESIGN.md
    §8).  ``slot_phase`` picks the slot execution regime: "decode"
    (default) forces the gathered per-(token, k) path sized to a step's
    activated slots; "prefill" keeps the normal ``use_sparse_path``
    rule — prefill-sized inputs run the dense capacity sweep against
    full (E, ...) stacks assembled from the pool plus wave-streamed
    misses (``slot_expert_stacks``, DESIGN.md §11), and may chunk via
    the scan below."""
    from repro.launch.sharding import hint
    from repro.models.moe_ep import apply_moe_ep, ep_applicable
    if force_path not in (None, "dense", "sparse"):
        raise ValueError(f"force_path must be None|'dense'|'sparse', "
                         f"got {force_path!r}")
    m = cfg.moe
    B, S, d = x.shape
    T_all = B * S
    if (slots is None and force_path is None and valid is None
            and ep_applicable(cfg, B, S)):
        # production path under an active mesh: shard_map expert-parallel
        # all-to-all dispatch (see moe_ep.py / EXPERIMENTS.md §Perf)
        return apply_moe_ep(params, x, cfg, capacity=capacity,
                            force_exchange=force_exchange,
                            count_overlap=count_overlap,
                            placement=placement,
                            demand_view=demand_view)
    if placement is not None or demand_view:
        raise ValueError("placement / demand_view are expert-parallel "
                         "re-route controls (models/moe_ep.py) and "
                         "require the EP path to be applicable")
    if (slots is not None and T_all > MOE_CHUNK_TOKENS
            and slot_phase != "prefill"):
        raise ValueError("the slot-pool path serves decode-sized steps; "
                         f"{T_all} tokens exceed MOE_CHUNK_TOKENS "
                         "(prefill-sized inputs stream with "
                         "slot_phase='prefill')")
    if T_all > MOE_CHUNK_TOKENS:
        n_chunks = -(-T_all // MOE_CHUNK_TOKENS)
        T_pad = n_chunks * MOE_CHUNK_TOKENS
        cap_c = (capacity + n_chunks - 1) // n_chunks \
            if capacity is not None else None
        xf_all = x.reshape(T_all, d)
        if T_pad != T_all:       # ragged tail: pad + mask, stay bounded
            xf_all = jnp.concatenate(
                [xf_all, jnp.zeros((T_pad - T_all, d), x.dtype)])
        if valid is None:
            vmask = jnp.arange(T_pad) < T_all
        else:                    # caller mask: pad slots are also invalid
            vmask = jnp.concatenate(
                [valid, jnp.zeros((T_pad - T_all,), bool)])
        xc = xf_all.reshape(n_chunks, 1, MOE_CHUNK_TOKENS, d)
        vc = vmask.reshape(n_chunks, MOE_CHUNK_TOKENS)

        def body(_, xv):
            x_chunk, v_chunk = xv
            # slot state threads straight through: each chunk re-derives
            # its own exact activated set and streams its own waves
            y, info = apply_moe(params, x_chunk, cfg, capacity=cap_c,
                                valid=v_chunk, force_path=force_path,
                                slots=slots, slot_fetch=slot_fetch,
                                slot_inject=slot_inject,
                                slot_little=slot_little,
                                slot_phase=slot_phase)
            return None, (y, info)

        _, (yc, infos) = jax.lax.scan(body, None, (xc, vc))
        y = yc.reshape(T_pad, d)[:T_all].reshape(B, S, d)
        # per-chunk aux/z are means over that chunk's VALID tokens; weight
        # by valid count so the tail chunk doesn't dilute the average
        w_chunk = vc.sum(1).astype(jnp.float32) \
            / jnp.maximum(vc.sum(), 1).astype(jnp.float32)
        info = {
            "workload": infos["workload"].sum(0),
            "topk_idx": infos["topk_idx"].reshape(T_pad, -1)[:T_all],
            "gates": infos["gates"].reshape(T_pad, -1)[:T_all],
            "probs": infos["probs"].reshape(T_pad, -1)[:T_all],
            "gate_in": infos["gate_in"].reshape(T_pad, d)[:T_all],
            "aux_loss": jnp.sum(infos["aux_loss"] * w_chunk),
            "z_loss": jnp.sum(infos["z_loss"] * w_chunk),
            "dropped": infos["dropped"].sum(),
        }
        return y, info
    T = T_all
    E, K = m.n_routed, m.top_k
    xf = hint(x.reshape(T, d), "tokens", "embed")

    gates, idx, probs, logits = route(params, xf, m)
    vrep = None if valid is None else jnp.repeat(valid, K)      # (T*K,)

    # decode-phase slot inputs always take the gathered path (a step's
    # activated slots are few); prefill-phase slot inputs follow the same
    # static rule as full-resident execution, so the offloaded sweep
    # shares the full-resident numerics path shape-for-shape
    sparse = (force_path == "sparse" if force_path is not None
              else ((slots is not None and slot_phase == "decode")
                    or use_sparse_path(m, T, capacity)))
    if sparse:
        # ---- decode fast path: gathered grouped SwiGLU ------------------
        if slots is not None:
            # physical offload: weights from the device slot pool, misses
            # from the host tier (serving/expert_store.py).  Prefill
            # chunks reuse the dead-slot seam for their pad tokens:
            # invalid rows must not trigger host round trips (their
            # outputs are zeroed below either way)
            live = slot_live if slot_live is not None else \
                (valid if slot_phase == "prefill" else None)
            y = slot_expert_ffn(slots, slot_fetch, xf, idx, gates, cfg,
                                live=live, slot_inject=slot_inject,
                                slot_little=slot_little)
        else:
            y = grouped_expert_ffn(params, xf, idx, gates, cfg)
        counts = _workload_counts(idx.reshape(-1), E, vrep)
        if valid is not None:
            y = jnp.where(valid[:, None], y, 0)
        dropped = jnp.zeros((), jnp.int32)         # no buckets, no drops
    else:
        C = capacity if capacity is not None else expert_capacity(m, T)
        # ---- sort-based dispatch (gather-only; no float scatters) -------
        xe, counts, se, rank, inv = local_dispatch(xf, idx, E, K, C,
                                                   valid_rep=vrep)

        xe = hint(xe, "experts", "cap", "embed")
        if slots is not None:
            # physical-offload prefill sweep (DESIGN.md §11): assemble
            # full stacks from pool + wave-streamed misses, then run the
            # UNCHANGED dense FFN — output bucket [e, c] depends only on
            # expert e's (byte-identical) rows, so the sweep is
            # bit-identical to full-resident prefill
            wps, host_need = slot_expert_stacks(
                slots, slot_fetch, counts, cfg, slot_inject=slot_inject,
                slot_little=slot_little)
            ye = expert_ffn_dense(wps, xe, cfg, counts=counts)    # (E,C,d)
            if slot_fetch.fallback == "host":
                # CPU tier at (token, k)-row granularity — the decode
                # host tier's proven callback contract; the device
                # sweep already yields zero rows for missing experts
                # (their assembled weights are zero), so host rows
                # substitute into the combine below
                host_hit = ~host_need[idx.reshape(-1)]
                if vrep is not None:
                    host_hit = host_hit | ~vrep
                hshape = jax.ShapeDtypeStruct((T * K, d), ye.dtype)
                ys_host = jax.lax.cond(
                    jnp.any(~host_hit),
                    lambda a: jax.pure_callback(
                        slot_fetch.prefill_host_cb, hshape, *a),
                    lambda a: jnp.zeros(hshape.shape, hshape.dtype),
                    (slots["lid"], xf, idx.reshape(-1), host_hit))
        else:
            ye = expert_ffn_dense(params, xe, cfg, counts=counts) # (E,C,d)
        ye = hint(ye, "experts", "cap", "embed")

        # gather results back in sorted-slot order, zero dropped/invalid
        # slots (se == E marks padding), un-sort via inv, then
        # weighted-sum over the K choices.
        keep_s = (rank < C) & (se < E)
        contrib = ye[jnp.clip(se, 0, E - 1), jnp.clip(rank, 0, C - 1)]
        contrib = hint(jnp.where(keep_s[:, None], contrib, 0)[inv],
                       "tokens", "embed")
        if slots is not None and slot_fetch.fallback == "host":
            # host rows replace their (zero) device contributions; the
            # keep mask applies the same capacity drops as full-resident
            contrib = jnp.where((~host_hit & keep_s[inv])[:, None],
                                ys_host.astype(contrib.dtype), contrib)
        y = jnp.sum(contrib.reshape(T, K, d)
                    * gates.astype(contrib.dtype)[..., None], axis=1)
        dropped = jnp.sum((se < E) & (rank >= C)).astype(jnp.int32)
    y = hint(y.astype(x.dtype), "tokens", "embed")

    if m.n_shared:
        y = y + apply_mlp(params["shared"], xf, cfg)

    # ---- aux losses + DALI observables --------------------------------------
    if valid is None:
        frac_tokens = counts.astype(jnp.float32) / (T * K)
        mean_prob = jnp.mean(probs, axis=0)
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    else:
        n_valid = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
        frac_tokens = counts.astype(jnp.float32) / (n_valid * K)
        vf = valid.astype(jnp.float32)
        mean_prob = jnp.sum(probs * vf[:, None], axis=0) / n_valid
        z_loss = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2
                         * vf) / n_valid
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)
    info = {
        "workload": counts,                        # (E,) tokens per expert
        "topk_idx": idx,                           # (T, K)
        "gates": gates,                            # (T, K)
        "probs": probs,                            # (T, E) router scores
        "gate_in": xf,                             # (T, d) gate input (trace)
        "aux_loss": aux_loss * m.aux_loss_weight,
        "z_loss": z_loss * m.router_z_weight,
        "dropped": dropped,
    }
    return y.reshape(B, S, d), info
