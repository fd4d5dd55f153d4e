"""Expert-parallel MoE via shard_map + all_to_all (the production dispatch
path; EXPERIMENTS.md §Perf), with a workload-sized ragged exchange.

GSPMD cannot partition data-dependent gather/scatter dispatch — it falls
back to replicating token- and bucket-sized buffers and all-gathering them
per layer (measured: the dominant roofline term for every MoE train/prefill
pair).  This module instead expresses the dispatch *per device*:

  1. tokens are split (batch over data/pod, sequence over model),
  2. each device routes its own tokens and packs per-expert capacity
     buckets locally (sort/gather, zero collectives),
  3. a tiny ``(tp, E/tp)`` int32 ``all_to_all`` ships every device's
     per-expert demand to the expert owners FIRST; its global max picks
     the smallest rung of a static capacity ladder (DESIGN.md §6), and
     only ``(E/tp, C_x, d)`` of each bucket goes through the data
     ``all_to_all`` — link bytes scale with the actual workload instead
     of the worst-case capacity C,
  4. experts compute their received buckets; on TPU the ragged grouped
     kernel (kernels/expert_ffn) takes the exchanged counts plus a
     group→expert id map so fully-empty (group, ci) blocks skip their
     MXU work; a second, symmetric ``all_to_all`` ships results back,
  5. results combine locally; the (B, S, d) output re-enters the GSPMD
     world through the out_specs.

Collectives per layer drop from O(all-gather everything) to
2 x all_to_all(E·C_x·d / tp) + one (tp, E/tp) int32 count exchange + the
output reshard, where C_x = next_pow2(global max per-(device, expert)
demand) clamped to C — a fraction of C for decode/skewed traffic.

Used automatically by ``apply_moe`` when sharding rules are active,
E % tp == 0 and the token dims divide; decode and single-device runs keep
the dense path.  Differentiable (each all_to_all transposes to an
all_to_all inside its own ladder branch), so train_step uses it too.
FSDP expert weights are all-gathered over 'data' once per layer inside
the shard (explicit, instead of per-buffer GSPMD gathers).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


# --------------------------------------------------------------------------
# topology-aware expert placement (DESIGN.md §13)
# --------------------------------------------------------------------------

def solve_placement(demand, topology, tp: Optional[int] = None) -> np.ndarray:
    """Greedy expert→device placement against a link topology.

    ``demand`` is the count exchange's global demand view — (E,) summed
    per-expert token counts or the (tp, E) per-source matrix
    (``info["ep_counts"]``).  Devices are ranked by
    ``topology.device_quality()`` (bidirectional bottleneck bandwidth to
    peers) and the hottest E/tp experts land on the best-connected
    device, the next E/tp on the next, etc. — so a degraded link's
    endpoints end up hosting the coldest experts and the traffic that
    must cross the bad pair shrinks.  Ties (and a uniform topology) keep
    the canonical identity layout so the healthy fast path never moves
    weights for nothing.

    Returns ``perm`` (E,) int32 with ``perm[p]`` = logical expert stored
    at physical slot p; device k owns slots [k·E/tp, (k+1)·E/tp).
    """
    demand = np.asarray(demand, np.float64)
    per_e = demand.sum(axis=0) if demand.ndim == 2 else demand
    n = int(tp if tp is not None else topology.n)
    E = per_e.size
    if E % n:
        raise ValueError(f"n_experts {E} must divide over {n} devices")
    e_loc = E // n
    if topology.is_uniform():
        return np.arange(E, dtype=np.int32)
    q = topology.device_quality()[:n]
    dev_order = np.argsort(-q, kind="stable")      # best-connected first
    hot = np.argsort(-per_e, kind="stable")        # hottest expert first
    perm = np.empty(E, np.int32)
    for rank, k in enumerate(dev_order):
        # sort each device's expert list so equal-demand workloads keep
        # a deterministic layout
        mine = np.sort(hot[rank * e_loc:(rank + 1) * e_loc])
        perm[k * e_loc:(k + 1) * e_loc] = mine
    return perm


def permute_expert_params(params, placement):
    """Reorder the stacked expert weights to physical slot order (slot p
    holds logical expert ``placement[p]``).  Applied OUTSIDE the jitted
    step at re-route time, so placement changes swap an input array
    instead of re-tracing or gathering weights in-graph; the router (and
    shared experts) keep logical expert ids."""
    perm = np.asarray(placement)
    out = dict(params)
    for k in ("gate", "up", "down"):
        out[k] = jnp.asarray(params[k])[perm]
    return out


def placement_pair_bytes(demand, placement, d_model: int,
                         itemsize: int) -> np.ndarray:
    """Analytic directed per-pair exchange bytes under a placement.

    ``jax.lax.all_to_all`` physically ships EQUAL-size blocks to every
    peer, so per-pair wire bytes are accounted from demand (the repo's
    ``link_bytes`` convention, DESIGN.md §2): tokens from source s to an
    expert owned by device k cross s->k once at dispatch and k->s once
    on the return.  ``demand`` is the (tp, E) per-source count matrix
    (``info["ep_counts"]``); returns a (tp, tp) int64 byte matrix with a
    zero diagonal (local traffic is free).
    """
    demand = np.asarray(demand, np.int64)
    tp, E = demand.shape
    e_loc = E // tp
    perm = np.asarray(placement, np.int64)
    owner = np.empty(E, np.int64)
    owner[perm] = np.arange(E, dtype=np.int64) // e_loc
    onehot = np.zeros((E, tp), np.int64)
    onehot[np.arange(E), owner] = 1
    disp = (demand @ onehot) * (d_model * itemsize)   # (src, dst) tokens
    np.fill_diagonal(disp, 0)
    return disp + disp.T


def ep_applicable(cfg: ModelConfig, B: int, S: int) -> bool:
    from repro.launch import sharding as shd
    st = shd.active()
    mesh = st["mesh"]
    if mesh is None or "model" not in mesh.axis_names:
        return False
    tp = mesh.shape["model"]
    E = cfg.moe.n_routed
    if E < tp or E % tp:
        return False
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    if B % dp or S % tp:
        return False
    if (B // dp) * (S // tp) < 64:       # decode / tiny shards: dense path
        return False
    return True


def exchange_ladder(C: int) -> List[int]:
    """Static bucket capacities the ragged exchange can ship: powers of two
    from the dispatch bucket floor (4) upward, clamped to C.  Each rung is
    one jitted exchange shape; the per-step pick is the smallest rung
    covering the global max per-(device, expert) demand, so XLA always
    sees static shapes while the common skewed/decode case ships a
    fraction of C (DESIGN.md §6)."""
    caps, c = [], 4
    while c < C:
        caps.append(c)
        c *= 2
    caps.append(C)
    return caps


def _ep_expert_ffn(xa, wg, wu, wd, cnt_rx, cfg: ModelConfig):
    """Expert FFN over received buckets xa (E/tp, tp, C_x, d).

    With exchanged counts ``cnt_rx`` (tp, E/tp) on TPU, the ragged grouped
    kernel runs with one (source, expert) group per bucket and a
    group→expert id map, so blocks holding no real tokens skip their MXU
    work.  Elsewhere (and with ``cnt_rx=None``, the dense exchange) the
    einsum sweep runs — bucket rows beyond the packed count are exact
    zeros, so both paths agree on every kept row."""
    from repro.models.layers import _ACTS
    E_loc, tp, Cx, d = xa.shape
    if cnt_rx is not None and jax.default_backend() == "tpu":
        from repro.kernels.expert_ffn.ops import expert_ffn_op
        groups = xa.reshape(E_loc * tp, Cx, d)
        gcnt = jnp.transpose(cnt_rx).reshape(-1).astype(jnp.int32)
        eids = jnp.repeat(jnp.arange(E_loc, dtype=jnp.int32), tp)
        y = expert_ffn_op(groups, wg, wu, wd, act=cfg.act, counts=gcnt,
                          expert_ids=eids)
        return y.reshape(E_loc, tp, Cx, d)
    act = _ACTS[cfg.act]
    xr = xa.reshape(E_loc, tp * Cx, d)
    h = act(jnp.einsum("ecd,edf->ecf", xr, wg)) \
        * jnp.einsum("ecd,edf->ecf", xr, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc, tp, Cx, d)


def apply_moe_ep(params, x, cfg: ModelConfig, *,
                 capacity: Optional[int] = None,
                 force_exchange: Optional[str] = None,
                 count_overlap: Optional[bool] = None,
                 placement=None,
                 demand_view: bool = False):
    """shard_map expert-parallel MoE.  x (B,S,d) -> (y, info).

    ``capacity`` (stated for the full batch, like apply_moe's) scales to
    each device's token share; None derives the per-device capacity from
    the shard size.  ``force_exchange`` pins the exchange flavor for
    tests/benchmarks:
    "dense" ships the full (E/tp, C, d) buckets (the pre-ragged path,
    bit-identical combine), "ragged"/None sizes the exchange to the
    workload via the count exchange + capacity ladder.  Observables
    (workload / aux / z / dropped) are identical either way; the ragged
    path additionally reports the shipped capacity as ``info["ep_cx"]``.

    ``count_overlap`` (None = on) moves the ragged path's count
    all_to_all to the FRONT of the shard body — counts only need the
    routing choices, which exist the moment attention hands the layer
    its input, so the tiny exchange plus its pmax/ladder-select round
    trip is dispatched before (and overlaps with) the dispatch index
    math, the FSDP weight gathers and the shared-expert MLP instead of
    stalling the bucket exchange (DESIGN.md §9).  The counts are the
    same ``bincount`` ``local_dispatch`` later computes, so outputs,
    ``ep_cx`` and drops are bit-identical with the overlap off.

    ``placement`` re-routes expert ownership across the 'model' axis
    (DESIGN.md §13): an (E,) int32 permutation with ``placement[p]`` =
    logical expert hosted at physical slot p, whose expert weight
    stacks the caller has already reordered via
    ``permute_expert_params`` (host-level, so a re-route swaps input
    arrays without re-tracing).  In-graph the send blocks and exchanged
    counts are permuted to physical order before the all_to_alls and
    the returned buckets un-permuted after — every expert still sees
    exactly its own tokens and weights, so outputs are bit-identical to
    the identity placement; only WHICH device computes each expert (and
    therefore which fabric links its traffic crosses) changes.
    ``placement=None`` is the identity fast path (no permute gathers).

    ``demand_view`` adds ``info["ep_counts"]``, the (tp, E) per-source
    capped demand matrix (one tiny int32 all_gather of the counts the
    exchange already computes) — the global demand view the topology
    placement solver and the per-link byte accounting consume."""
    from repro.launch import sharding as shd
    from repro.models.layers import apply_mlp
    from repro.models.moe import expert_capacity, local_dispatch, route

    if force_exchange not in (None, "dense", "ragged"):
        raise ValueError(f"force_exchange must be None|'dense'|'ragged', "
                         f"got {force_exchange!r}")
    st = shd.active()
    mesh = st["mesh"]
    fsdp = st["wmode"] == "fsdp"
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_routed, m.top_k
    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    T_my = (B // dp) * (S // tp)
    if capacity is None:
        C = expert_capacity(m, T_my)
    else:
        # an explicit capacity is stated for the full (B, S) batch
        # (apply_moe's contract; dry-run shape lowering pins it) — each
        # device packs its T_my-token share, so scale the pin to the
        # shard, keeping the 4-row tiling floor
        share = -(-capacity * T_my // (B * S))
        C = max(4, -(-share // 4) * 4)
    dpa = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    ragged = force_exchange != "dense"
    overlap = True if count_overlap is None else count_overlap
    caps = exchange_ladder(C)
    placed = placement is not None
    # always an operand (spec'd replicated): when the identity fast path
    # is taken it is simply unused and DCE'd, and when a re-route lands
    # the new permutation is a fresh input to the SAME compiled step
    perm_arr = jnp.asarray(placement if placed else np.arange(E),
                           jnp.int32)

    fs = "data" if fsdp else None
    w_spec = P("model", None, fs)
    w_spec_dn = P("model", fs, None)
    # shared experts evaluate each device's OWN tokens -> weights must be
    # replicated over 'model' inside the shard (fsdp: 'data'-sharded with
    # an explicit in-body gather)
    shared_specs = None
    if m.n_shared:
        shared_specs = {k: P(None, fs) if k in ("gate", "up")
                        else P(fs, None)
                        for k in params["shared"]}

    def body(router, wg, wu, wd, shared, xb, perm):
        # xb: (B/dp, S/tp, d) — this device's tokens
        xf = xb.reshape(-1, d)
        gates, idx, probs, logits = route({"router": router}, xf, m)
        # perm: physical slot -> logical expert; inv_p: logical -> slot
        # (distinct from local_dispatch's row inverse `inv` below). The
        # permutes are tiny E-row takes on count vectors / bucket
        # stacks, applied only on the placed path.
        inv_p = jnp.argsort(perm) if placed else None

        cnt_rx = sel = caps_arr = None
        if ragged and overlap:
            # (1, hoisted) the count exchange needs only the routing
            # choices — dispatch it NOW, before the sort/gather index
            # math, so the all_to_all + pmax round trip runs under the
            # dispatch / weight-gather / shared-expert compute below.
            # Same bincount local_dispatch computes → bit-identical.
            cnt = jnp.minimum(jnp.bincount(idx.reshape(-1), length=E + 1)
                              [:E], C).astype(jnp.int32)
            cnt_tx = jnp.take(cnt, perm, axis=0) if placed else cnt
            cnt_rx = jax.lax.all_to_all(cnt_tx.reshape(tp, E // tp),
                                        "model",
                                        split_axis=0, concat_axis=0)
            gmax = jax.lax.pmax(jnp.max(cnt), ("model",) + dp_axes)
            caps_arr = jnp.asarray(caps, jnp.int32)
            sel = jnp.minimum(jnp.searchsorted(caps_arr, gmax),
                              len(caps) - 1)

        xe, counts, se, rank, inv = local_dispatch(xf, idx, E, K, C)

        if fsdp:    # materialise full expert weights once, explicitly
            wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)

        y_shared = None
        if m.n_shared and ragged and overlap:
            # hoist the shared-expert MLP between the count dispatch and
            # the ladder select: dense compute with no data dependence on
            # the exchange, exactly what hides the select's round trip
            sh = dict(shared)
            if fsdp:
                sh = {k: jax.lax.all_gather(
                    v, "data", axis=(1 if k in ("gate", "up") else 0),
                    tiled=True) for k, v in sh.items()}
            y_shared = apply_mlp(sh, xf, cfg)

        def exchange(cx, cnt_rx):
            """Ship cx-row buckets to expert owners, compute, ship back.
            split==concat axis keeps each all_to_all self-transposing
            (AD-safe): dim0 switches meaning from destination-block to
            source-block.  Returns per-slot contributions in sorted
            order, a shape shared by every ladder rung."""
            def run(xe_):
                xs = xe_[:, :cx]
                if placed:          # logical bucket order -> slot order
                    xs = jnp.take(xs, perm, axis=0)
                xa = jax.lax.all_to_all(
                    xs.reshape(tp, E // tp, cx, d), "model",
                    split_axis=0, concat_axis=0)
                ye = _ep_expert_ffn(jnp.moveaxis(xa, 0, 1), wg, wu, wd,
                                    cnt_rx, cfg)       # (E/tp, tp, cx, d)
                # symmetric return exchange to the original token owner
                ya = jax.lax.all_to_all(jnp.moveaxis(ye, 1, 0), "model",
                                        split_axis=0, concat_axis=0)
                ye_loc = ya.reshape(E, cx, d)
                if placed:          # slot order -> logical bucket order
                    ye_loc = jnp.take(ye_loc, inv_p, axis=0)
                return ye_loc[se, jnp.clip(rank, 0, cx - 1)]   # (T*K, d)
            return run

        if not ragged:
            contrib_s = exchange(C, None)(xe)
            cx_used = jnp.asarray(C, jnp.int32)
        else:
            if not overlap:
                # (1) tiny count exchange: every expert owner learns each
                # source device's per-expert demand before bucket data
                # moves
                cnt = jnp.minimum(counts, C).astype(jnp.int32)
                cnt_tx = jnp.take(cnt, perm, axis=0) if placed else cnt
                cnt_rx = jax.lax.all_to_all(cnt_tx.reshape(tp, E // tp),
                                            "model",
                                            split_axis=0, concat_axis=0)
                # (2) workload-sized capacity: smallest ladder rung
                # covering the global max demand; pmax over every mesh
                # axis so all devices take the SAME branch (collectives
                # inside a branch are only correct if all participants
                # agree on it)
                gmax = jax.lax.pmax(jnp.max(cnt), ("model",) + dp_axes)
                caps_arr = jnp.asarray(caps, jnp.int32)
                sel = jnp.minimum(jnp.searchsorted(caps_arr, gmax),
                                  len(caps) - 1)
            if len(caps) == 1:
                contrib_s = exchange(C, cnt_rx)(xe)
            else:
                contrib_s = jax.lax.switch(
                    sel, [exchange(c, cnt_rx) for c in caps], xe)
            cx_used = caps_arr[sel]

        # combine: rows the dense C-bucket would drop stay dropped (the
        # ladder rung always covers every kept rank, so cx never drops
        # more — keep/dropped are bit-identical to the dense exchange)
        keep_s = rank < C
        contrib = jnp.where(keep_s[:, None], contrib_s, 0)[inv]
        y = jnp.sum(contrib.reshape(-1, K, d)
                    * gates.astype(contrib.dtype)[..., None], axis=1)
        y = y.astype(xb.dtype)
        if m.n_shared:
            if y_shared is None:
                sh = dict(shared)
                if fsdp:
                    sh = {k: jax.lax.all_gather(
                        v, "data", axis=(1 if k in ("gate", "up") else 0),
                        tiled=True) for k, v in sh.items()}
                y_shared = apply_mlp(sh, xf, cfg)
            y = y + y_shared

        # global observables
        g_counts = jax.lax.psum(counts, ("model",) + dp_axes)
        frac = counts.astype(jnp.float32) / (xf.shape[0] * K)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, ("model",) + dp_axes)
        z = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            ("model",) + dp_axes)
        dropped = jax.lax.psum(jnp.sum(~keep_s).astype(jnp.int32),
                               ("model",) + dp_axes)
        Bl, Sl = xb.shape[0], xb.shape[1]
        info = {
            "workload": g_counts,
            "topk_idx": idx.reshape(Bl, Sl, K),
            "gates": gates.reshape(Bl, Sl, K),
            "probs": probs.reshape(Bl, Sl, E),
            "gate_in": xf.reshape(Bl, Sl, d),
            "aux_loss": aux * m.aux_loss_weight,
            "z_loss": z * m.router_z_weight,
            "dropped": dropped,
            "ep_cx": cx_used,
        }
        if demand_view:
            # (tp, E) per-source capped demand: the same counts the
            # exchange ships, gathered so every host sees the global
            # view the placement solver / per-link byte accounting use
            dv = jnp.minimum(counts, C).astype(jnp.int32)
            dv = jax.lax.all_gather(dv, "model")
            if dp_axes:
                dv = jax.lax.psum(dv, dp_axes)
            info["ep_counts"] = dv
        return y.reshape(Bl, Sl, d), info

    tok3 = P(dpa, "model", None)
    info_specs = {
        "workload": P(None), "topk_idx": tok3, "gates": tok3,
        "probs": tok3, "gate_in": tok3,
        "aux_loss": P(), "z_loss": P(), "dropped": P(), "ep_cx": P(),
    }
    if demand_view:
        info_specs["ep_counts"] = P(None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), w_spec, w_spec, w_spec_dn,
                  shared_specs, tok3, P(None)),
        out_specs=(tok3, info_specs),
        check_vma=False)
    y, info = fn(params["router"], params["gate"], params["up"],
                 params["down"], params.get("shared"), x, perm_arr)
    T_all = B * S
    info = dict(info,
                topk_idx=info["topk_idx"].reshape(T_all, K),
                gates=info["gates"].reshape(T_all, K),
                probs=info["probs"].reshape(T_all, E),
                gate_in=info["gate_in"].reshape(T_all, d))
    return y, info
