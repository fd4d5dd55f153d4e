"""Physical expert residency: host weight store + device slot pool.

Until this module existed the offload was *modeled* — every expert's
weights sat in device memory and the OffloadPolicy's ``resident`` /
``prefetch_set`` decisions fed telemetry only (DESIGN.md §2).  The
:class:`ExpertStore` makes the paper's memory layout real:

  * **Host store** — the routed experts' gate/up/down stacks are pulled
    out of ``params`` into host (numpy) arrays ``(L, E, ...)``; the
    device never needs to hold them all.
  * **Device slot pool** — fixed-size pools ``(L, n_slots, ...)`` per
    matrix plus a slot table ``cur (L, n_slots) int32`` (expert id per
    slot, -1 = free).  ``n_slots`` defaults to ``cache_size +
    prefetch_size`` — exactly the policy's maximum effective resident
    set ``cache ∪ prefetch``.
  * **Slot plan lowering** — a policy step's decisions (the effective
    resident set it wants on device next) are lowered to a bounded
    evict-slot → insert-expert plan.  ``lower_slot_plan`` is the
    jit-compatible lowering (vmapped over layers, used by the parity
    tests and available in-graph); ``lower_slot_plan_np`` is the NumPy
    mirror the serving loop actually drives — planning on the host
    mirror of the slot table keeps the tiny plan math off the device
    execution queue, where it would serialize behind the in-flight
    decode step (DESIGN.md §8).  Both produce identical plans
    (tests/test_expert_store.py).
  * **Double-buffered streaming** — the store keeps TWO pool
    generations and ping-pongs between them, split into two halves the
    serving loop schedules around the in-flight decode:

      - ``stage(target)`` — plan, gather the insert rows from the host
        store into a workload-sized staging buffer (rows bucketed to
        powers of two so the scatter compiles O(log) times) and issue
        the host→device copy.  Pure host work + transfer, nothing on
        the device execution queue — the overlap mode calls it right
        after dispatching a decode step, so the copy hides behind the
        step's compute.
      - ``commit(off)`` — scatter the staged rows IN PLACE into the
        spare generation (buffer donation: XLA aliases the donated
        pool, so the scatter costs O(rows), not a pool copy) and swap
        generations.  Donation makes the dispatch wait for in-flight
        work, so commit runs at the step boundary, when the queue is
        idle (right after the loop's token sync).  The spare's last
        reader was the decode step one full sync ago, which makes the
        in-place write race-free; because the spare is one plan behind,
        each commit re-applies the previous plan's rows (deduped
        against the new plan) before its own.

    ``step_update`` = stage + commit back-to-back — the ``--offload
    blocking`` baseline, which keeps the whole copy on the decode
    critical path and thereby measures exactly what overlap hides.

  * **Pipelined per-layer streaming** (``--offload pipelined``,
    DESIGN.md §9) — overlap's double-buffer hides the copy but delays
    decisions: a plan staged behind step t+1 is only committed (and
    readable) at t+2.  The pipelined mode instead ships the plan as
    *inject buffers* ``(buf_cap, ...)`` BEFORE the dispatch: a small
    pool of GLOBAL weight rows shared by all layers, closed over by the
    decode step's ``lax.scan`` body as scan constants (indexed
    ``[row]``, no per-layer slice copies, like the pool itself) while the
    tiny per-layer expert→row map ``inj_of`` rides the xs.  Each
    MoE layer resolves its own inserts in-graph right where it gathers
    (``models/moe.py::slot_expert_ffn``), so a decision made after
    step t's sync is readable at step t+1 and the per-step device work
    is O(insert rows) — the big pool arrays never enter the per-step
    program.  Inserted rows ACCUMULATE in the buffers across steps and
    fold into the single pool generation by one donated scatter only
    when the buffer fills, so injection never re-ships rows and the
    O(pool) touch is amortized over ~buf_cap/insert-rate steps.

    Ownership note: the ``state["offload"]`` pytree is owned by the
    store between updates — after ``commit`` returns, the PREVIOUS
    generation's arrays become the spare and are donated (invalidated)
    at the next commit; callers must not stash old offload states.

Misses — experts a step activates that are not pooled — fall back to the
host tier:

  * ``fallback="fetch"`` (default): each distinct missing expert of a
    layer is demand-fetched once from the host store via
    ``jax.pure_callback`` (``fetch_weights_cb`` returns views of the
    store's blocks, no host copy; a real host→device transfer on the
    critical path, the cost the paper's Eq. 5 charges for non-resident
    GPU execution); the device writes each into the (token, k) rows
    that miss it and the FFN computes on device — bit-identical to
    full-resident decode.
  * ``fallback="host"``: the missing (token, expert) slots' FFN runs on
    the host (numpy) and only the (d,)-sized outputs cross the link —
    the paper's CPU execution tier.  Host BLAS and XLA round
    differently, so this mode is allclose- rather than bit-tested.

Both callbacks sit under ``lax.cond(any_miss, ...)`` so a fully-resident
step never pays a host round trip.

  * ``fallback="little"``: misses read an ALWAYS-RESIDENT int8 twin of
    every (L, E) expert (MoBiLE's "little" experts, DESIGN.md §10) — a
    pure device gather + dequant, no host callback, no cond.  Quality
    degrades (int8 rounding) but latency does not; this is the bottom
    rung of the degradation ladder.

Robustness (DESIGN.md §10): when constructed with ``faults=...`` the
store wraps its host gathers and H2D transfers with a seeded
:class:`~repro.serving.faults.FaultInjector`, times every staging
transfer against a :class:`~repro.serving.faults.LinkWatchdog` deadline
budgeted from the cost model's link constants, checksums staged rows
against the host store, and drives a
:class:`~repro.serving.faults.DegradationLadder`:

  healthy → degraded (halve the move budget; the serving tier swaps in
  a re-solved policy with the re-fit ``t_trans`` and zero prefetch) →
  little (streaming suspended, misses served by the int8 twins) →
  healthy again once an expert-sized health probe sees the link heal.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation, annotate_function

from repro.core.cost_model import CostModel
from repro.models.config import ModelConfig, scan_pattern
from repro.models.moe import register_callback_seam
from repro.serving.faults import (DEGRADED, HEALTHY, LITTLE,
                                  DegradationLadder, FaultInjector,
                                  HostReadError, LinkWatchdog,
                                  TransientFault)


FALLBACKS = ("fetch", "host", "little")
STORE_MODES = ("blocking", "overlap", "pipelined")


def _np_act(name: str):
    """NumPy activations matching models.layers._ACTS (jax.nn defaults:
    gelu is the tanh approximation)."""
    if name == "silu":
        return lambda x: x / (1.0 + np.exp(-x))
    if name == "gelu":
        c = np.sqrt(2.0 / np.pi).astype(np.float32)
        return lambda x: 0.5 * x * (1.0 + np.tanh(c * (x + 0.044715 * x**3)))
    if name == "relu":
        return lambda x: np.maximum(x, 0.0)
    raise ValueError(f"unknown activation {name!r}")


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------------------------
# Row checksums (host truth vs. staged device buffers)
# --------------------------------------------------------------------------
# Cheap per-row integrity check: xor-fold of the raw bit pattern.  The
# NumPy and jax versions reduce the SAME uint16/uint32 words in the SAME
# uint32 domain, so a staged row matches its host source bit-for-bit iff
# the checksums match — float NaN payloads and -0.0 included.

def _row_checksums_np(*arrs) -> np.ndarray:
    """(R,) uint32 xor-fold over each leading-axis row of all arrays."""
    out = None
    for a in arrs:
        bits = np.uint16 if a.dtype.itemsize == 2 else np.uint32
        v = np.ascontiguousarray(a).reshape(a.shape[0], -1).view(bits)
        x = np.bitwise_xor.reduce(v.astype(np.uint32), axis=1)
        out = x if out is None else out ^ x
    return out


def _row_bits(a):
    bits = jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32
    v = jax.lax.bitcast_convert_type(a, bits)
    flat = v.reshape(a.shape[0], -1).astype(jnp.uint32)
    return jax.lax.reduce(flat, np.uint32(0), jax.lax.bitwise_xor, (1,))


@jax.jit
def _staged_checksum(sg, su, sd):
    """(R,) uint32 per-row checksum of a staged (gate, up, down) triple."""
    return _row_bits(sg) ^ _row_bits(su) ^ _row_bits(sd)


@jax.jit
def _rowsbuf_checksum(rowsbuf):
    """(Q,) uint32 per-row checksum of a (gate, up, down) rows triple."""
    return _staged_checksum(*rowsbuf)


def moe_layer_layout(cfg: ModelConfig):
    """(prefix_moe_blocks, scan_moe_positions, n_super): which prefix
    blocks / scan pattern positions are MoE, in the canonical layer order
    every (L, ...) stack in this repo uses (prefix first, then scan
    super-block-major — see models.model.collect_field)."""
    prefix_pat, period_pat, n_super = scan_pattern(cfg)
    prefix_moe = [i for i, (_, mlp) in enumerate(prefix_pat) if mlp == "moe"]
    scan_moe = [p for p, (_, mlp) in enumerate(period_pat) if mlp == "moe"]
    return prefix_moe, scan_moe, n_super


# --------------------------------------------------------------------------
# Slot-plan lowering (JAX + NumPy mirrors)
# --------------------------------------------------------------------------

_BIG = np.int32(1 << 30)


def lower_slot_plan(cur, target, max_moves: int):
    """Lower a per-layer target resident set to a bounded slot plan.

    cur (L, S) int32 — expert id per slot (-1 free); target (L, E) bool —
    the experts the policy wants pooled.  Returns ``(new_cur, ins_experts,
    ins_slots, valid)`` with plan arrays (L, max_moves): up to
    ``max_moves`` inserts per layer, each pairing a wanted-but-missing
    expert (ascending id) with an available slot — free slots first, then
    slots whose expert fell out of the target (ascending slot id).
    Experts evicted from the target but not overwritten stay physically
    pooled (free extra hits until their slot is reused).  Jit-compatible;
    ``lower_slot_plan_np`` mirrors it plan-for-plan."""
    S = cur.shape[1]
    E = target.shape[1]
    M = max_moves

    def layer(c, want):
        pooled = jnp.zeros((E + 1,), bool).at[jnp.where(c >= 0, c, E)].set(
            True)[:E]
        # available slots: free first (key = slot), then evictable
        # (key = S + slot); kept-resident slots are unavailable
        keep = jnp.where(c >= 0, want[jnp.clip(c, 0)], False)
        skey = jnp.where(keep, _BIG,
                         jnp.where(c < 0, jnp.arange(S),
                                   S + jnp.arange(S))).astype(jnp.int32)
        sorder = jnp.argsort(skey)
        slots = sorder[:M]
        s_ok = skey[slots] < _BIG
        # wanted-but-missing experts, ascending id
        ekey = jnp.where(want & ~pooled, jnp.arange(E), _BIG).astype(
            jnp.int32)
        eorder = jnp.argsort(ekey)
        exps = eorder[:M]
        e_ok = ekey[exps] < _BIG
        valid = s_ok & e_ok
        ins_e = jnp.where(valid, exps, -1).astype(jnp.int32)
        ins_s = jnp.where(valid, slots, S).astype(jnp.int32)  # S = dropped
        new_c = c.at[ins_s].set(ins_e, mode="drop")
        return new_c, ins_e, ins_s, valid

    return jax.vmap(layer)(cur, target)


def lower_slot_plan_np(cur, target, max_moves: int):
    """NumPy mirror of ``lower_slot_plan`` (identical plans; the serving
    loop plans here so the host never waits on the device queue)."""
    cur = np.asarray(cur)
    target = np.asarray(target, bool)
    L, S = cur.shape
    M = max_moves
    new_cur = cur.copy()
    ins_e = np.full((L, M), -1, np.int32)
    ins_s = np.full((L, M), S, np.int32)
    valid = np.zeros((L, M), bool)
    for l in range(L):
        c = cur[l]
        want = target[l]
        pooled = np.zeros(target.shape[1], bool)
        pooled[c[c >= 0]] = True
        free = np.where(c < 0)[0]
        evict = np.where((c >= 0) & ~want[np.clip(c, 0, None)])[0]
        slots = np.concatenate([free, evict])[:M]
        exps = np.where(want & ~pooled)[0][:M]
        n = min(len(slots), len(exps), M)
        ins_e[l, :n] = exps[:n]
        ins_s[l, :n] = slots[:n]
        valid[l, :n] = True
        new_cur[l, slots[:n]] = exps[:n]
    return new_cur, ins_e, ins_s, valid


# --------------------------------------------------------------------------
# The store
# --------------------------------------------------------------------------

class ExpertStore:
    """Host expert weights + device slot pool for one model's MoE layers.

    Construct once per server/benchmark run; ``init_device_state`` seeds
    the pool from a policy's initial resident set and returns the
    ``state["offload"]`` pytree (``{"gate","up","down","cur"}``) the
    slot-indexed decode step consumes via ``build_view``.  The store
    keeps a host mirror of the slot table (``_cur``) so planning never
    reads the device; ``step_update`` keeps mirror and device table in
    lockstep (both apply the same deterministic plan)."""

    def __init__(self, params, cfg: ModelConfig, n_slots: int,
                 max_moves: int = 4, fallback: str = "fetch",
                 mode: str = "overlap", faults=None, cost_model=None,
                 watchdog=None, ladder=None, little=None, verify=None,
                 max_retries: int = 3, retry_backoff_s: float = 2e-3,
                 probe_interval: int = 3, seed: int = 0,
                 prefill_rows=None):
        if cfg.moe is None:
            raise ValueError("ExpertStore needs an MoE architecture")
        if fallback not in FALLBACKS:
            raise ValueError(f"fallback must be one of "
                             f"{'|'.join(FALLBACKS)}, got {fallback!r}")
        if mode not in STORE_MODES:
            raise ValueError(f"mode must be one of "
                             f"{'|'.join(STORE_MODES)}, got {mode!r}")
        self.mode = mode
        self.cfg = cfg
        m = cfg.moe
        self.E = m.n_routed
        self.d = cfg.d_model
        self.f = m.d_expert or cfg.d_ff
        self.n_slots = n_slots
        self.max_moves = max_moves
        self.fallback = fallback
        # prefill streaming budget (DESIGN.md §11): a prefill layer sweep
        # ships its activated-but-unpooled experts in waves of at most
        # this many rows, so the transient staging stays pool-budget
        # sized no matter how many experts the chunk activates
        self.prefill_rows = int(prefill_rows) if prefill_rows else n_slots
        if not 0 < self.prefill_rows <= self.E:
            raise ValueError(f"prefill_rows={self.prefill_rows} must be in "
                             f"1..n_experts={self.E}")
        self._act = _np_act(cfg.act)

        prefix_moe, scan_moe, n_super = moe_layer_layout(cfg)
        self._prefix_moe = prefix_moe
        self._scan_moe = scan_moe
        self._n_super = n_super
        self.n_layers = len(prefix_moe) + n_super * len(scan_moe)

        # host store: (L, E, ...) per matrix, canonical layer order.
        # Filled one source array at a time, and taken as-is when one scan
        # position already holds every layer, so host memory peaks at the
        # store plus one source (none when the caller passes host arrays,
        # see ``host_expert_params``)
        def stack(name):
            pre = [params["prefix"][i]["mlp"][name] for i in prefix_moe]
            per_pos = [params["scan"][p]["mlp"][name]
                       for p in scan_moe]                 # (n_super, E, ..)
            if not pre and len(per_pos) == 1:
                return np.asarray(per_pos[0])
            first = pre[0] if pre else per_pos[0][0]
            out = np.empty((self.n_layers,) + tuple(first.shape),
                           first.dtype)
            for l, a in enumerate(pre):
                out[l] = np.asarray(a)
            P, n_pre = len(per_pos), len(pre)
            for j, a in enumerate(per_pos):
                out[n_pre + j::P] = np.asarray(a)
            return out

        self.host = {k: stack(k) for k in ("gate", "up", "down")}
        self.dtype = self.host["gate"].dtype
        if self.n_slots > self.E:
            raise ValueError(f"n_slots={n_slots} exceeds n_experts={self.E}")
        self.expert_bytes = int(sum(self.host[k][0, 0].nbytes
                                    for k in self.host))
        # telemetry: a single lock-guarded counter dict.  pure_callback
        # targets (fetch_weights_cb / host_ffn_cb) mutate counters from
        # the runtime's callback thread, so every bump goes through
        # _bump().  stats() is the one read path: monotonic totals
        # (benchmarks snapshot-diff them); drain() returns the deltas
        # since the last drain and resets that baseline.
        self._tel_lock = threading.Lock()
        self._tel = {
            "routed_rows": 0,        # decode (token, k) slots routed here
            "fallback_rows": 0,      # (token, k) slots served by misses
            "fallback_fetches": 0,   # experts demand-fetched
            "h2d_rows": 0,           # experts streamed into the pool
            "h2d_bytes": 0,
            "fetch_s": 0.0,          # host time inside fetch_weights_cb
            "fetch_bytes": 0,        # bytes it returns: distinct misses
            "stage_s": 0.0,          # host time in stage()/inject build
            "commit_s": 0.0,         # host time in commit()/inject fold
            "retries": 0,            # transient-fault retries that fired
            "stalls": 0,             # injected stage stalls hit
            "read_errors": 0,        # injected host read errors hit
            "stage_aborts": 0,       # plans dropped after retry exhaustion
            "corrupt_caught": 0,     # rows the checksum verify flagged
            "restaged_rows": 0,      # flagged rows re-gathered + re-shipped
            "probes": 0,             # health-probe transfers issued
            "little_steps": 0,       # steps served with streaming suspended
            # prefill streaming (DESIGN.md §11) — separate from the
            # decode h2d/fallback counters so per-phase breakdowns and
            # per-request decode fallback rates stay clean
            "prefill_fetch_rows": 0,   # experts wave-streamed into sweeps
            "prefill_h2d_bytes": 0,    # bus bytes of those waves (padded)
            "prefill_waves": 0,        # cond-fired waves
            "prefill_host_rows": 0,    # (token, k) rows the host tier ran
            "prefill_stage_s": 0.0,    # host time in prefill gathers
        }
        self._drained = dict(self._tel)
        self._cur = np.full((self.n_layers, n_slots), -1, np.int32)
        # -- robustness seam (DESIGN.md §10) -------------------------------
        self.injector = (faults if isinstance(faults, FaultInjector)
                         else FaultInjector(faults, seed=seed)
                         if faults is not None else None)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.probe_interval = max(1, int(probe_interval))
        if watchdog is None and self.injector is not None:
            cm = cost_model or CostModel.for_config(cfg)
            gbps = (cm.link_gbps if cm.link_gbps is not None
                    else cm.profile.link_gbps)
            lat = (cm.link_latency_s if cm.link_latency_s is not None
                   else cm.profile.link_latency_s)
            watchdog = LinkWatchdog(self.expert_bytes, gbps, lat)
        self.watchdog = watchdog
        if ladder is None and self.watchdog is not None:
            ladder = DegradationLadder(self.watchdog,
                                       enable_little=little is not False)
        self.ladder = ladder
        self._verify = bool(verify if verify is not None
                            else self.injector is not None)
        self._move_cap = None        # max_moves override while DEGRADED
        self._suspended = False      # streaming off while LITTLE
        self._steps_since_obs = 0
        self._little = None
        if little is True or fallback == "little":
            self._build_little()
        # ping-pong generation state: the spare pool buffers (donated in
        # place by the next step_update) and the plan rows the spare is
        # missing relative to the logical pool state (an (n, 3) int32 of
        # (layer, slot, expert) — re-applied, deduped, at the next swap)
        self._spare = None
        self._spare_lag = np.zeros((0, 3), np.int32)
        self._staged = None                   # device staging of next plan
        self._staged_rows = None
        # donate the pool + slot-table args: the scatter aliases them in
        # place (O(rows), not a pool copy) — safe because the spare's
        # last reader retired a full step ago (see module docstring)
        self._apply_jit = jax.jit(self._apply, donate_argnums=(0, 1, 2, 3))
        # pipelined: inserted rows accumulate in PERSISTENT device inject
        # buffers (allocated once, updated in place by a donated row
        # scatter — each step ships only its valid insert rows) and are
        # selected by inj_of until the buffer fills, when they fold
        # into the pool in one amortized scatter.  _live is the host
        # ledger of unfolded rows: (layer, buf_row, dst, expert).
        self._live = []
        # buffer capacity in GLOBAL rows shared by all layers: the
        # decode closes over the buffers as scan constants, so its cost
        # scales with their size — max_moves rows keep them ~pool/S
        # sized while still amortizing folds over a few steps (heavy
        # plans stage in ≤cap chunks with a fold between chunks)
        self._buf_cap = self.max_moves
        self._idle_inj = None
        self._inject_bufs = None
        self._stage_inj_jit = jax.jit(
            functools.partial(self._stage_inj, S=self.n_slots),
            donate_argnums=(0, 1, 2))
        self._fold_inj_jit = jax.jit(self._fold_inj,
                                     donate_argnums=(0, 1, 2))
        if self.mode == "pipelined":
            self._prewarm_pipeline()

    # -- telemetry ---------------------------------------------------------

    def _bump(self, name: str, v=1):
        with self._tel_lock:
            self._tel[name] += v

    def book_routed(self, live: int):
        """Book one decode step's routed (token, k) rows: ``live`` slots
        times top-k in each of the store's layers (the denominator of the
        pool's hit share; ``fallback_rows`` counts the ones that missed)."""
        self._bump("routed_rows", live * self.cfg.moe.top_k * self.n_layers)

    def stats(self) -> dict:
        """Monotonic counter totals (numeric only — benchmarks diff
        snapshots of this dict)."""
        with self._tel_lock:
            out = dict(self._tel)
        out.update(expert_bytes=self.expert_bytes, n_slots=self.n_slots,
                   n_layers=self.n_layers)
        return out

    def drain(self) -> dict:
        """Counter deltas since the previous drain (snapshot-and-reset).
        Safe against concurrent pure_callback bumps: the baseline moves
        under the same lock the bumps take, so every increment lands in
        exactly one drain window — this is what lets the servers report
        per-request fallback rates without double- or under-counting."""
        with self._tel_lock:
            out = {k: self._tel[k] - self._drained[k] for k in self._tel}
            self._drained = dict(self._tel)
        return out

    def health(self) -> dict:
        """Ladder / watchdog view for reports (non-numeric OK here)."""
        out = {"ladder_state": self.ladder.state if self.ladder else HEALTHY,
               "transitions": list(self.ladder.transitions)
               if self.ladder else [],
               "suspended": self._suspended,
               "move_cap": self._move_cap}
        if self.watchdog is not None:
            out.update(link_gbps=self.watchdog.gbps,
                       link_latency_s=self.watchdog.latency_s,
                       deadline_misses=self.watchdog.deadline_misses,
                       # per-link counter snapshot, same shape as the EP
                       # WatchdogBank.report() — ServeMetrics.fold_links
                       # merges either source
                       links={self.watchdog.name: self.watchdog.report()})
        return out

    # -- robustness seam (DESIGN.md §10) -----------------------------------

    def _observe(self, nbytes: int, seconds: float):
        if self.watchdog is not None:
            self.watchdog.observe(nbytes, seconds)
        self._steps_since_obs = 0

    def _fault_sleep(self, nbytes: int):
        """Model an injected link slowdown: pad the just-finished
        transfer to ``factor ×`` the healthy baseline.  The baseline is
        the watchdog's calibrated expectation (floored at its observed
        median) so the slowdown is detectable relative to the deadline
        regardless of how fast the actual machine's link is."""
        if self.injector is None or self.watchdog is None:
            return
        k = self.injector.link_factor()
        if k > 1.0:
            base = max(self.watchdog.expected_s(nbytes),
                       self.watchdog.floor_s)
            time.sleep(base * (k - 1.0))

    def _guard_transient(self, what: str) -> bool:
        """Run the injected transient checks with bounded retry+backoff.
        Returns True once clear; False when retries are exhausted — the
        caller then SKIPS this step's plan, which is always safe (the
        mirror has not advanced, so misses fall back correctly)."""
        if self.injector is None:
            return True
        delay = self.retry_backoff_s
        for _ in range(self.max_retries + 1):
            try:
                self.injector.maybe_stall()
                self.injector.maybe_read_error()
                return True
            except HostReadError:
                self._bump("read_errors")
            except TransientFault:
                self._bump("stalls")
            self._bump("retries")
            time.sleep(delay)
            delay *= 2.0
        self._bump("stage_aborts")
        return False

    def _probe(self):
        """One expert-sized H2D transfer, timed under the injected link
        factor — keeps the watchdog observed when regular staging is
        idle or suspended.  Expert-sized on purpose: a token-sized probe
        would be latency-dominated and a bandwidth slowdown would hide
        inside the deadline floor."""
        t0 = time.perf_counter()
        buf = (self.host["gate"][0, :1], self.host["up"][0, :1],
               self.host["down"][0, :1])
        jax.block_until_ready(jax.device_put(buf))
        self._fault_sleep(self.expert_bytes)
        self._bump("probes")
        self._observe(self.expert_bytes, time.perf_counter() - t0)

    def _health_tick(self):
        """Once per serving step, from ``pre_step``: advance the injector
        clock, keep the watchdog fed (probe when staging has gone quiet
        or is suspended), and drive the ladder.  Ladder transitions only
        flip cheap store-side switches here — the serving tier reacts to
        the state change by swapping decode variants (steps.py)."""
        if self.injector is not None:
            self.injector.tick()
        if self.watchdog is None or self.ladder is None:
            return
        self._steps_since_obs += 1
        # probes fire on the observation cadence whether staging is idle
        # or suspended — NOT every suspended step, or the little tier
        # would pay a (fault-padded) transfer per step, defeating it
        if self._steps_since_obs >= self.probe_interval:
            self._probe()
        if self._suspended:
            self._bump("little_steps")
        step = (self.injector.step if self.injector is not None
                else len(self.watchdog._samples))
        tr = self.ladder.on_step(step)
        if tr is None:
            return
        _, to = tr
        if to == DEGRADED:
            self._move_cap = max(1, self.max_moves // 2)
        elif to == LITTLE:
            self._suspended = True
        elif to == HEALTHY:
            self._move_cap = None
            self._suspended = False

    def _effective_moves(self) -> int:
        return (self.max_moves if self._move_cap is None
                else min(self.max_moves, self._move_cap))

    def degraded_dcfg(self, dcfg):
        """The DaliConfig the serving tier re-solves with while DEGRADED:
        ``t_trans`` from the watchdog's online re-fit of the link as it
        is NOW (never below the healthy value) and a zeroed prefetch
        budget — the paper's workload-aware assignment reacting to
        hardware state (HybriMoE-style re-balancing)."""
        t_deg = dcfg.t_trans
        if self.watchdog is not None:
            gbps, lat, _rejected = self.watchdog.refit()
            t_deg = lat + self.expert_bytes / (gbps * 1e9)
        return dataclasses.replace(dcfg,
                                   t_trans=max(float(t_deg), dcfg.t_trans),
                                   prefetch_size=0)

    def degraded_policy(self, policy):
        """``policy`` with its DaliConfig swapped for the degraded one
        (no-op for policies without cost constants, e.g. "none")."""
        if not hasattr(policy, "with_dcfg"):
            return policy
        return policy.with_dcfg(self.degraded_dcfg(policy.dcfg))

    # -- the little tier (MoBiLE int8 twins, DESIGN.md §10) ----------------

    def _build_little(self):
        """Quantize EVERY (L, E) expert to a per-output-column symmetric
        int8 twin and park it on device.  Layout matches the host store
        (``*_q`` int8 same shape, ``*_s`` f32 scales broadcast over the
        contraction axis), so the little tier costs ~dtype_bytes/1 of
        the full store's bytes but is always resident — a persistent
        miss becomes an int8-quality FFN instead of a host round trip."""
        if self._little is not None:
            return

        def q(w):
            s = np.max(np.abs(w.astype(np.float32)), axis=-2,
                       keepdims=True) / 127.0
            s = np.maximum(s, 1e-8).astype(np.float32)
            qv = np.clip(np.round(w.astype(np.float32) / s),
                         -127, 127).astype(np.int8)
            return qv, s

        out = {}
        for k in ("gate", "up", "down"):
            qv, s = q(self.host[k])
            out[k + "_q"] = jax.device_put(qv)
            out[k + "_s"] = jax.device_put(s)
        self._little = out

    def little_view(self):
        """The resident int8 twin pool for ``slot_expert_ffn``'s
        ``fallback="little"`` branch (closed over by the jitted decode
        as constants, like the pipelined inject buffers)."""
        self._build_little()
        return self._little

    # -- device state ------------------------------------------------------

    def init_device_state(self, resident):
        """Seed the pool from an initial (L, E) bool resident set (the
        policy's random initial cache) and return ``state["offload"]``."""
        resident = np.asarray(resident, bool)
        L, S = self.n_layers, self.n_slots
        if resident.shape != (L, self.E):
            raise ValueError(
                f"resident set must be (n_layers, n_experts) = "
                f"({L}, {self.E}), got {resident.shape} — pass the "
                f"policy's initial (L, E) bool cache mask")
        cur = np.full((L, S), -1, np.int32)
        ids_of = []
        for l in range(L):
            ids = np.where(resident[l])[0].astype(np.int32)
            if len(ids) > S:
                raise ValueError(
                    f"layer {l}: {len(ids)} initial residents exceed "
                    f"n_slots={S} (size the pool to cache+prefetch)")
            cur[l, :len(ids)] = ids
            ids_of.append(ids)
        self._cur = cur.copy()
        names = ("gate", "up", "down")

        def seeded():
            # built on the device: zero pools, then each layer's residents
            # scattered in by the donated commit scatter, so the host
            # stages one layer's rows at a time, never an (L, S, ...)
            # copy of the pool (7 GB at Mixtral widths)
            pool = [jnp.zeros((L, S) + self.host[k].shape[2:], self.dtype)
                    for k in names]
            c = jnp.full((L, S), -1, jnp.int32)
            for l, ids in enumerate(ids_of):
                n = len(ids)
                if n:
                    *pool, c = self._apply_jit(
                        *pool, c, *(self.host[k][l, ids] for k in names),
                        np.full(n, l, np.int32), np.arange(n, dtype=np.int32),
                        ids, np.ones(n, bool))
            return dict(zip(names, pool), cur=c)

        off = seeded()
        # second generation for the streaming ping-pong (same contents).
        # pipelined is single-generation — its inject buffers replace
        # the spare — so it skips the extra O(pool) allocation
        self._spare = seeded() if self.mode != "pipelined" else None
        self._spare_lag = np.zeros((0, 3), np.int32)
        self._staged = None
        self._staged_rows = None
        self._live = []
        self._idle_inj = None
        if self.mode == "pipelined":
            # the inject seam rides in state["offload"] from step 0 so
            # the decode (and admit) pytree structure never changes
            off["inject"] = self._build_inj()
            self._idle_inj = off["inject"]
        return off

    def state_shapes(self):
        """``jax.ShapeDtypeStruct`` tree of ``init_device_state``'s result,
        for lowering a step against this store without seeding a pool."""
        L, S, E, d, f = self.n_layers, self.n_slots, self.E, self.d, self.f
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        off = {"gate": sds((L, S, d, f), self.dtype),
               "up": sds((L, S, d, f), self.dtype),
               "down": sds((L, S, f, d), self.dtype),
               "cur": sds((L, S), i32)}
        if self.mode == "pipelined":
            B = self._buf_cap
            off["inject"] = {"gate": sds((B, d, f), self.dtype),
                             "up": sds((B, d, f), self.dtype),
                             "down": sds((B, f, d), self.dtype),
                             "inj_of": sds((L, E), i32),
                             "cur": sds((L, S), i32)}
        return off

    # -- the slot-indexed view the model consumes --------------------------

    def build_view(self, off):
        """params-shaped per-layer slot view for ``apply_model``:
        ``{"prefix": (...), "scan": (...), "pool_rows": {...}}`` with
        per-MoE-layer entries ``{"slot_of","lid"}`` (scan entries carry a
        leading n_super axis and ride the scan's xs exactly like caches)
        and the WHOLE ``(L, n_slots, ...)`` pool once under
        ``"pool_rows"``, a scan constant the FFN indexes ``[lid, slot]``
        — slicing it through the xs would materialize each layer's
        ``(n_slots, d, f)`` slice (1.8 GB at Mixtral widths).
        Traced-friendly — called inside the jitted decode step.

        With a pipelined ``off["inject"]`` present (DESIGN.md §9) the
        slot table is read from the inject's post-plan ``cur`` — so
        ``slot_of`` already resolves this step's inserts — each layer's
        entry additionally carries its expert→inject-row map ``inj_of``
        (E,) through the scan's xs, and the staged insert rows ride the
        view ONCE as ``view["inject_rows"]`` ((buf_cap, ...) GLOBAL
        rows shared by all layers — a scan constant
        ``slot_expert_ffn`` indexes ``[row]``, so the buffers are never
        sliced per super-block and stay tiny); inserted experts read
        inject rows instead of the (stale until the fold) pool rows."""
        E, S = self.E, self.n_slots
        inj = off.get("inject")
        cur = inj["cur"] if inj is not None else off["cur"]    # (L, S)

        def invert(c):
            idx = jnp.where(c >= 0, c, E)
            return jnp.full((E + 1,), -1, jnp.int32).at[idx].set(
                jnp.arange(S, dtype=jnp.int32))[:E]

        slot_of = jax.vmap(invert)(cur)                        # (L, E)
        n_pre = len(self._prefix_moe)
        prefix_pat, period_pat, _ = scan_pattern(self.cfg)

        prefix = [None] * len(prefix_pat)
        for l, i in enumerate(self._prefix_moe):
            prefix[i] = {"slot_of": slot_of[l],
                         "lid": jnp.asarray(l, jnp.int32)}
            if inj is not None:
                prefix[i]["inj_of"] = inj["inj_of"][l]

        scan = [None] * len(period_pat)
        P = len(self._scan_moe)
        if P:
            def per_pos(a, j):
                r = a[n_pre:].reshape((self._n_super, P) + a.shape[1:])
                return r[:, j]
            for j, p in enumerate(self._scan_moe):
                lids = n_pre + np.arange(self._n_super) * P + j
                scan[p] = {"slot_of": per_pos(slot_of, j),
                           "lid": jnp.asarray(lids, jnp.int32)}
                if inj is not None:
                    scan[p]["inj_of"] = per_pos(inj["inj_of"], j)
        view = {"prefix": tuple(prefix), "scan": tuple(scan),
                "pool_rows": {k: off[k] for k in ("gate", "up", "down")}}
        if inj is not None:
            view["inject_rows"] = {"gate": inj["gate"], "up": inj["up"],
                                   "down": inj["down"]}
        return view

    # -- miss fallbacks (host callbacks, see module docstring) -------------

    def fetch_weights_cb(self, lid, expert, miss_rows):
        """pure_callback target: demand-fetch ONE missing expert.

        ``slot_expert_ffn`` calls it once per distinct missing expert of
        a layer (``miss_rows`` of that layer's (token, k) rows route to
        it) and writes it into those rows on device.  Returns the expert's
        gate/up/down blocks of the host store as they are — contiguous
        views, no copy; the runtime ships them to the device after this
        returns.  ``fetch_s`` books the host time spent here and
        ``fetch_bytes`` the bytes returned."""
        t0 = time.perf_counter()
        l, e, n = int(lid), int(expert), int(miss_rows)
        with TraceAnnotation("dali:store.fetch_weights", layer=l, expert=e,
                             miss_rows=n, bytes=self.expert_bytes):
            self._guard_transient("fetch")   # injected read errors retry
            out = tuple(self.host[k][l, e] for k in ("gate", "up", "down"))
        self._bump("fallback_rows", n)
        self._bump("fallback_fetches", 1)
        self._bump("fetch_bytes", self.expert_bytes)
        self._bump("fetch_s", time.perf_counter() - t0)
        return out

    @staticmethod
    def _gather_rows(layer, src, n):
        """(n, ...) stack whose row r is ``layer[src[r]]`` where ``src``
        names one, zero elsewhere: one copy per shipped row (fancy
        indexing would copy twice, and zero-filling first writes each
        byte once more; at full width these rows are hundreds of MB)."""
        out = np.empty((n,) + layer.shape[1:], layer.dtype)
        for r in range(n):
            if r in src:
                out[r] = layer[src[r]]
            else:
                out[r] = 0
        return out

    def host_ffn_cb(self, lid, xf, flat_e, hit):
        """pure_callback target: run missing (token, k) slots' expert FFN
        on the host (numpy, float32) — the CPU execution tier.  Returns
        (T·K, d) with miss rows filled, hit rows zero."""
        l = int(lid)
        xf = np.asarray(xf)
        e = np.asarray(flat_e)
        K = e.shape[0] // xf.shape[0]
        ys = np.zeros((e.shape[0], self.d), xf.dtype)
        rows = np.nonzero(~np.asarray(hit))[0]
        with TraceAnnotation("dali:store.host_ffn", layer=l,
                             miss_rows=len(rows)):
            self._guard_transient("host-ffn")
            for r in rows:
                x = xf[r // K].astype(np.float32)
                wg = self.host["gate"][l, e[r]].astype(np.float32)
                wu = self.host["up"][l, e[r]].astype(np.float32)
                wd = self.host["down"][l, e[r]].astype(np.float32)
                ys[r] = ((self._act(x @ wg) * (x @ wu)) @ wd).astype(
                    ys.dtype)
        self._bump("fallback_rows", len(rows))
        return ys

    def little_miss_cb(self, hit):
        """io_callback target for the in-graph little tier: the twins are
        read without any host round trip, so miss accounting arrives as
        this effect-only counter bump (moe.py fires it on miss steps)."""
        h = np.asarray(hit)
        n = int(h.size - np.count_nonzero(h))
        if n:
            self._bump("fallback_rows", n)
        return np.int32(n)

    # -- prefill streaming (DESIGN.md §11) ---------------------------------

    def prefill_fetch_cb(self, lid, rows):
        """pure_callback target for one prefill wave: gather the wave's
        activated-but-unpooled experts from the host store into a
        (prefill_rows, ...) staging triple.  ``rows (E,)`` int32 maps
        expert id -> staging row for this wave (-1 = not in this wave);
        padding staging rows stay zero and are dropped by the caller's
        scatter.  The whole padded buffer crosses the link, so the bytes
        counter charges the full wave (like ``stage``'s pow2 padding)."""
        t0 = time.perf_counter()
        l = int(lid)
        rows = np.asarray(rows)
        ids = np.nonzero(rows >= 0)[0]
        P = self.prefill_rows
        with TraceAnnotation("dali:store.prefill_fetch", layer=l,
                             rows=len(ids), bytes=P * self.expert_bytes):
            self._guard_transient("prefill-fetch")
            src = {int(rows[i]): i for i in ids}
            g, u, dn = (self._gather_rows(self.host[k][l], src, P)
                        for k in ("gate", "up", "down"))
        self._bump("prefill_fetch_rows", len(ids))
        self._bump("prefill_h2d_bytes", P * self.expert_bytes)
        self._bump("prefill_waves", 1)
        self._bump("prefill_stage_s", time.perf_counter() - t0)
        return g, u, dn

    def prefill_host_cb(self, lid, xf, flat_e, hit):
        """pure_callback target for the prefill "host" tier: the decode
        tier's row-wise contract (``host_ffn_cb``) accounted under the
        prefill counters — run missing (token, k) slots' expert FFN on
        the host (numpy, float32) and return (T·K, d) with miss rows
        filled, hit rows zero.  Row granularity keeps the callback
        operands small and layout-trivial (shipping the (E, C, d)
        capacity buckets through the callback deadlocks the CPU
        callback runtime); the caller applies the same capacity-drop
        mask as the full-resident sweep."""
        t0 = time.perf_counter()
        l = int(lid)
        xf = np.asarray(xf)
        e = np.asarray(flat_e)
        K = e.shape[0] // xf.shape[0]
        ys = np.zeros((e.shape[0], self.d), xf.dtype)
        rows = np.nonzero(~np.asarray(hit))[0]
        with TraceAnnotation("dali:store.prefill_host", layer=l,
                             miss_rows=len(rows)):
            self._guard_transient("prefill-host")
            for r in rows:
                x = xf[r // K].astype(np.float32)
                wg = self.host["gate"][l, e[r]].astype(np.float32)
                wu = self.host["up"][l, e[r]].astype(np.float32)
                wd = self.host["down"][l, e[r]].astype(np.float32)
                ys[r] = ((self._act(x @ wg) * (x @ wu)) @ wd).astype(
                    ys.dtype)
        self._bump("prefill_host_rows", len(rows))
        self._bump("fallback_rows", len(rows))
        self._bump("prefill_stage_s", time.perf_counter() - t0)
        return ys

    def prefill_barrier(self, off):
        """Make the pool generation coherent before a prefill reads it.
        Overlap keeps a staged-but-uncommitted plan between steps —
        commit it now (admission happens at the step boundary, when the
        device queue is idle, exactly where commit is safe); blocking is
        always coherent and pipelined's fresh rows ride the inject seam
        the prefill assembly also reads, so both are no-ops."""
        if self._staged is not None:
            return self.commit(off)
        return off

    def memory_layout(self) -> dict:
        """Analytic device-bytes accounting for prefill-phase reports:
        the resident pool, the transient per-layer (E, ...) stack one
        prefill sweep assembles, the (prefill_rows, ...) staging buffer
        a wave ships, the little twins (when built), and the
        full-resident stack the offload replaces."""
        pool = self.n_layers * self.n_slots * self.expert_bytes
        stack = self.E * self.expert_bytes
        staging = self.prefill_rows * self.expert_bytes
        little = 0
        if self._little is not None:
            little = sum(int(np.asarray(v).nbytes)
                         for v in self._little.values())
        return {"pool_bytes": pool,
                "prefill_stack_bytes": stack,
                "prefill_staging_bytes": staging,
                "little_bytes": little,
                "prefill_peak_bytes": pool + stack + staging + little,
                "full_resident_bytes": self.n_layers * self.E
                * self.expert_bytes}

    # -- streaming updates -------------------------------------------------

    @staticmethod
    def _apply(pool_g, pool_u, pool_d, cur, sg, su, sd, lay, slot, exp, ok):
        """Scatter staged expert rows into the pool (functional: returns
        new pool arrays — the previous generation stays readable by any
        in-flight decode step, which is what makes overlap safe)."""
        S = cur.shape[1]
        slot_eff = jnp.where(ok, slot, S)              # OOB rows dropped
        pool_g = pool_g.at[lay, slot_eff].set(sg, mode="drop")
        pool_u = pool_u.at[lay, slot_eff].set(su, mode="drop")
        pool_d = pool_d.at[lay, slot_eff].set(sd, mode="drop")
        cur = cur.at[lay, slot_eff].set(exp, mode="drop")
        return pool_g, pool_u, pool_d, cur

    # -- pipelined per-layer streaming (DESIGN.md §9) ----------------------

    @staticmethod
    def _stage_inj(buf_g, buf_u, buf_d, pos, rowsbuf, meta, *, S):
        """Per-step pipelined stage, ONE dispatch that touches ONLY the
        small persistent inject buffers — the (L, S, d, f) pool arrays
        never enter this program, so the per-step cost is O(insert
        rows), not an O(pool) donate/alias round trip.

        The host args: ``pos (Q,)`` int32 = global buffer rows of this
        step's inserts; ``rowsbuf`` = their (gate (Q, d, f), up (Q, d, f),
        down (Q, f, d)) weights in the buffers' own shapes (a flattened
        (Q, d*f) packing made the chip's compiler spend minutes and
        ~16 GB of host memory on the reshape at Q = 2); ``meta (L, S+E)``
        int32 = post-plan ``cur`` | ``inj_of``, split back out in-graph.  Padding rows carry pos = B and drop on
        scatter.  Buffer rows not overwritten keep earlier steps'
        weights — the point: unfolded rows ACCUMULATE here until
        ``_fold_inj``."""
        rg, ru, rd = rowsbuf
        buf_g = buf_g.at[pos].set(rg, mode="drop")
        buf_u = buf_u.at[pos].set(ru, mode="drop")
        buf_d = buf_d.at[pos].set(rd, mode="drop")
        return buf_g, buf_u, buf_d, meta[:, :S], meta[:, S:]

    @staticmethod
    def _fold_inj(pool_g, pool_u, pool_d, buf_g, buf_u, buf_d, fidx):
        """Occasional buffer→pool fold: gather the live unfolded rows
        out of the inject buffers (``fidx (3, F)`` int32 = lay, row,
        dst; padding rows carry layer L — the row gather clamps and the
        scatter drops them) and scatter them into the donated pool.
        This is the only pipelined program that touches the pool; it
        runs when the buffer fills (~every buf_cap/insert-rate steps),
        so its cost is amortized instead of paid per step."""
        flay, frow, fdst = fidx
        pool_g = pool_g.at[flay, fdst].set(buf_g[frow], mode="drop")
        pool_u = pool_u.at[flay, fdst].set(buf_u[frow], mode="drop")
        pool_d = pool_d.at[flay, fdst].set(buf_d[frow], mode="drop")
        return pool_g, pool_u, pool_d

    def _inject_buffers(self):
        B = self._buf_cap
        if self._inject_bufs is None:
            self._inject_bufs = (
                jnp.zeros((B, self.d, self.f), self.dtype),
                jnp.zeros((B, self.d, self.f), self.dtype),
                jnp.zeros((B, self.f, self.d), self.dtype))
        return self._inject_bufs

    def _prewarm_pipeline(self):
        """Compile every pow2 row-bucket variant of the two pipelined
        programs up front (throwaway donated dummies; the jit cache keys
        on shapes only).  The bucket set is tiny — Q ≤ pow2(L·max_moves)
        for the stage, F ≤ pow2(L·buf_cap) for the fold — and paying the
        compiles at construction keeps them out of serving steps, where
        a single in-loop compile would dwarf the latency the pipelining
        saves."""
        L, S, B = self.n_layers, self.n_slots, self._buf_cap
        d, f = self.d, self.f
        rdt = self.host["gate"].dtype

        def bufs():
            return (jnp.zeros((B, d, f), self.dtype),
                    jnp.zeros((B, d, f), self.dtype),
                    jnp.zeros((B, f, d), self.dtype))

        q = 1
        while True:
            pos = np.full(q, B, np.int32)
            rowsbuf = (np.zeros((q, d, f), rdt), np.zeros((q, d, f), rdt),
                       np.zeros((q, f, d), rdt))
            meta = np.zeros((L, S + self.E), np.int32)
            jax.block_until_ready(self._stage_inj_jit(
                *bufs(), pos, rowsbuf, meta))
            if q >= B:
                break
            q <<= 1
        q = 1
        while True:
            pools = (jnp.zeros((L, S, d, f), self.dtype),
                     jnp.zeros((L, S, d, f), self.dtype),
                     jnp.zeros((L, S, f, d), self.dtype))
            fidx = np.full((3, q), [[L], [0], [S]], np.int32)
            jax.block_until_ready(self._fold_inj_jit(*pools, *bufs(), fidx))
            if q >= B:
                break
            q <<= 1

    def _build_inj(self):
        """The inject pytree for the CURRENT ledger state (inj_of over
        the live unfolded rows, cur = the host mirror) — the decode
        step's pytree structure never depends on whether the policy
        moved anything.  Rows inj_of does not select are never read, so
        building this ships only two small int32 tables."""
        buf_g, buf_u, buf_d = self._inject_buffers()
        return {"gate": buf_g, "up": buf_u, "down": buf_d,
                "inj_of": jax.device_put(self._inj_of()),
                "cur": jax.device_put(self._cur.copy())}

    @functools.partial(annotate_function, name="dali:store.stage")
    def _pipeline_pre_step(self, off, target):
        """Pipelined ``pre_step``: plan toward ``target`` against the
        host mirror, gather ONLY the valid insert rows — a compact
        (Q, ...) copy, Q = next pow2 of the insert count (the same
        bucketing ``stage`` uses) — and write them into the persistent
        inject buffers with one small ``_stage_inj`` dispatch.  The
        mirror advances immediately: the plan is readable by the VERY
        NEXT decode (t → t+1 freshness), not after a generation swap.

        Inserted rows live in the buffers (selected by ``inj_of``)
        across steps and are folded into the pool only when the buffer
        would overflow — ``_fold_inj``, the one program that touches
        the O(pool)-sized arrays, amortized over ~buf_cap/insert-rate
        steps.  Plans larger than the buffer (rare: init bursts, forced
        resets) stage in ≤buf_cap chunks with a fold between chunks.
        ``self._live`` is the host ledger of unfolded rows as
        (layer, buf_row, dst_slot, expert); a row dies when the mirror
        no longer maps its expert to its slot (evicted or replaced).

        Fast path: a step with no plan changes nothing — pool, buffers
        and mirror are all as the previous step left them — so it
        reuses the cached inject and costs zero dispatches."""
        t0 = time.perf_counter()
        L, S = self.n_layers, self.n_slots
        # suspended (LITTLE rung) or retries exhausted: drop the plan —
        # the mirror has not advanced, so the decode just sees misses
        if self._suspended or not self._guard_transient("pipeline-stage"):
            target = None
        n = 0
        if target is not None:
            new_cur, ins_e, ins_s, valid = self.plan(target)
            n = int(valid.sum())
        if n == 0:
            if self._idle_inj is None:
                self._idle_inj = self._build_inj()
            self._bump("stage_s", time.perf_counter() - t0)
            return dict(off, inject=self._idle_inj)
        self._cur = new_cur
        lr, mc = np.nonzero(valid)
        ee = ins_e[lr, mc]
        ds = ins_s[lr, mc]
        B = self._buf_cap
        # prune rows the new plan just invalidated (their slot now maps
        # to a different expert)
        self._live = [r for r in self._live
                      if self._cur[r[0], r[2]] == r[3]]
        done = 0
        while done < n:
            room = B - len(self._live)
            if room <= 0:
                tf = time.perf_counter()
                off = self._fold_live(off)
                t0 += time.perf_counter() - tf   # booked under commit_s
                room = B
            take = min(room, n - done)
            sl = slice(done, done + take)
            clr, cee, cds = lr[sl], ee[sl], ds[sl]
            # allocate buffer rows for this chunk from the free set
            occ = np.zeros(B, bool)
            for v in self._live:
                occ[v[1]] = True
            alloc = np.nonzero(~occ)[0][:take].astype(np.int32)
            for i in range(take):
                self._live.append((int(clr[i]), int(alloc[i]),
                                   int(cds[i]), int(cee[i])))
            Q = 1 << (take - 1).bit_length()   # pow2 row bucket
            # pad rows carry pos = B and drop on scatter; the gathers
            # write straight into one preallocated packed host buffer
            # (no stack/concat copies on the critical path)
            pos = np.full(Q, B, np.int32)
            pos[:take] = alloc
            rowsbuf = tuple(np.empty((Q,) + self.host[k].shape[2:],
                                     self.dtype)
                            for k in ("gate", "up", "down"))
            for k, h in enumerate((self.host["gate"], self.host["up"],
                                   self.host["down"])):
                for i in range(take):
                    rowsbuf[k][i] = h[clr[i], cee[i]]
                rowsbuf[k][take:] = 0
            truth = (_row_checksums_np(rowsbuf[0], rowsbuf[1], rowsbuf[2])
                     if self._verify else None)
            if self.injector is not None:
                self.injector.corrupt({"gate": rowsbuf[0],
                                       "up": rowsbuf[1],
                                       "down": rowsbuf[2]}, take)
            meta = np.concatenate([self._cur.astype(np.int32),
                                   self._inj_of()], axis=1)
            tc0 = time.perf_counter()
            rows_dev = jax.device_put(rowsbuf)
            if self._verify:
                rows_dev = self._verify_rowsbuf(rows_dev, rowsbuf, truth,
                                                take, clr, cee)
            if self.watchdog is not None:
                jax.block_until_ready(rows_dev)
                nbytes = sum(a.nbytes for a in rowsbuf)
                self._fault_sleep(nbytes)
                self._observe(nbytes, time.perf_counter() - tc0)
            buf_g, buf_u, buf_d = self._inject_buffers()
            buf_g, buf_u, buf_d, cur_d, inj_of_d = self._stage_inj_jit(
                buf_g, buf_u, buf_d, pos, rows_dev, meta)
            self._inject_bufs = (buf_g, buf_u, buf_d)
            done += take
            self._bump("h2d_bytes", Q * self.expert_bytes)
        inj = {"gate": buf_g, "up": buf_u, "down": buf_d,
               "inj_of": inj_of_d, "cur": cur_d}
        self._idle_inj = inj
        self._bump("h2d_rows", n)
        self._bump("stage_s", time.perf_counter() - t0)
        return dict(off, inject=inj)

    def _verify_rowsbuf(self, rows_dev, rowsbuf, truth, take, clr, cee):
        """Checksum the device copy of a pipelined rows chunk against the
        host-store truth; re-gather and re-ship any corrupted rows."""
        got = np.asarray(_rowsbuf_checksum(rows_dev))
        bad = np.nonzero(got[:take] != truth[:take])[0]
        if len(bad) == 0:
            return rows_dev
        self._bump("corrupt_caught", len(bad))
        for k, h in enumerate((self.host["gate"], self.host["up"],
                               self.host["down"])):
            rowsbuf[k][bad] = h[clr[bad], cee[bad]]
        self._bump("restaged_rows", len(bad))
        return jax.device_put(rowsbuf)

    def _inj_of(self):
        """(L, E) expert→buffer-row map over the live unfolded rows."""
        inj_of = np.full((self.n_layers, self.E), -1, np.int32)
        for l, r, _, e in self._live:
            inj_of[l, e] = r
        return inj_of

    @functools.partial(annotate_function, name="dali:store.fold")
    def _fold_live(self, off):
        """Scatter every live unfolded buffer row into the (donated)
        pool and clear the ledger — the pipelined commit point.  Rows
        are already on device, so nothing crosses the link; the decode
        keeps reading them through ``inj_of`` until the NEXT stage
        rebuilds it, so the fold is invisible to parity."""
        if not self._live:
            return off
        t0 = time.perf_counter()
        L, S = self.n_layers, self.n_slots
        F = 1 << (len(self._live) - 1).bit_length()
        fidx = np.full((3, F), [[L], [0], [S]], np.int32)
        for i, (l, r, dst, _) in enumerate(self._live):
            fidx[:, i] = (l, r, dst)
        buf_g, buf_u, buf_d = self._inject_buffers()
        pool_g, pool_u, pool_d = self._fold_inj_jit(
            off["gate"], off["up"], off["down"],
            buf_g, buf_u, buf_d, fidx)
        self._live = []
        # the pool now holds the mirror state; refresh the cur table the
        # non-inject generation selector reads
        off = dict(off, gate=pool_g, up=pool_u, down=pool_d,
                   cur=jax.device_put(self._cur.copy()))
        self._bump("commit_s", time.perf_counter() - t0)
        return off

    def plan(self, target):
        """Lower a (L, E) bool target against the HOST slot-table mirror
        (NumPy twin; the in-graph ``lower_slot_plan`` is parity-tested
        against it).  Does NOT mutate the mirror — ``step_update`` does,
        once the plan is actually issued.  While the ladder is DEGRADED
        the move budget is halved (``_move_cap``) so a slow link ships
        fewer rows per step."""
        return lower_slot_plan_np(self._cur, target, self._effective_moves())

    @functools.partial(annotate_function, name="dali:store.stage")
    def stage(self, target) -> bool:
        """Plan one step's pool update toward ``target`` (L, E) bool (the
        policy's cache ∪ prefetch for the next step) and issue the
        host→device copy of the staged rows — the planned inserts plus
        the rows the spare generation still lags by, deduped (the new
        plan wins on a (layer, slot) collision), bucketed to the next
        power of two (→ O(log) scatter compilations).

        This is the half the overlap mode hides behind the in-flight
        decode step: pure host work + the H2D transfer, no device-queue
        entry.  Returns False when the pool is already at target.  The
        staged rows are folded into the pool by the next ``commit``
        (guaranteed to run before the next ``stage``)."""
        if self._staged is not None:
            # a second stage would advance the host mirror past what ever
            # reaches the device — a silent permanent mirror/pool split
            raise RuntimeError("stage() called twice without commit()")
        # suspended (LITTLE rung) or retries exhausted: skip the plan —
        # nothing has mutated yet, so skipping is always safe
        if self._suspended or not self._guard_transient("stage"):
            return False
        t0 = time.perf_counter()
        new_cur, ins_e, ins_s, valid = self.plan(target)
        lay_v, mv = np.nonzero(valid)
        n = len(lay_v)
        if n == 0:
            self._bump("stage_s", time.perf_counter() - t0)
            return False                     # pool already at target
        rows = np.stack([lay_v, ins_s[lay_v, mv], ins_e[lay_v, mv]],
                        axis=1).astype(np.int32)
        # rows the spare lags by, minus (layer, slot) pairs this plan
        # overwrites anyway
        if len(self._spare_lag):
            key_new = set(map(tuple, rows[:, :2].tolist()))
            keep = [r for r in self._spare_lag
                    if (int(r[0]), int(r[1])) not in key_new]
            combined = np.concatenate(
                [np.asarray(keep, np.int32).reshape(-1, 3), rows])
        else:
            combined = rows
        m = len(combined)
        R = _next_pow2(m)
        lay = np.zeros(R, np.int32)
        slot = np.full(R, self.n_slots, np.int32)
        exp = np.zeros(R, np.int32)
        ok = np.zeros(R, bool)
        lay[:m], slot[:m], exp[:m] = combined.T
        ok[:m] = True
        # staged rows gathered in one shot (pad rows gather garbage from
        # (0, 0) and are dropped by the scatter)
        sg = self.host["gate"][lay, exp]
        su = self.host["up"][lay, exp]
        sd = self.host["down"][lay, exp]
        truth = (_row_checksums_np(sg, su, sd)
                 if self._verify else None)
        if self.injector is not None:
            self.injector.corrupt({"gate": sg, "up": su, "down": sd}, m)
        nbytes = sg.nbytes + su.nbytes + sd.nbytes
        tt0 = time.perf_counter()
        self._staged = jax.device_put((sg, su, sd, lay, slot, exp, ok))
        if self._verify:
            got = np.asarray(_staged_checksum(*self._staged[:3]))
            bad = np.nonzero(got[:m] != truth[:m])[0]
            if len(bad):
                self._bump("corrupt_caught", len(bad))
                # re-gather the flagged rows from the host store and
                # re-ship the buffers — the host store is the truth
                sg[bad] = self.host["gate"][lay[bad], exp[bad]]
                su[bad] = self.host["up"][lay[bad], exp[bad]]
                sd[bad] = self.host["down"][lay[bad], exp[bad]]
                self._staged = jax.device_put(
                    (sg, su, sd, lay, slot, exp, ok))
                self._bump("restaged_rows", len(bad))
        if self.watchdog is not None:
            jax.block_until_ready(self._staged)
            self._fault_sleep(nbytes)
            self._observe(nbytes, time.perf_counter() - tt0)
        self._staged_rows = rows
        self._cur = new_cur
        self._bump("h2d_rows", n)
        # actual bus traffic: the full staged buffer crosses the link —
        # new rows, spare-lag re-applies AND the pow2 padding rows
        self._bump("h2d_bytes", R * self.expert_bytes)
        self._bump("stage_s", time.perf_counter() - t0)
        return True

    @functools.partial(annotate_function, name="dali:store.commit")
    def commit(self, off, blocking: bool = False):
        """Fold the staged rows into the spare pool generation (donated,
        in-place scatter — O(rows), no pool copy) and return it as the
        next ``state["offload"]``; the generation passed in becomes the
        new spare.  No-op when nothing is staged.

        MUST be dispatched while the device queue is idle (the serving
        loops call it right after the per-step token sync): donation
        makes the dispatch wait for any in-flight execution, which would
        serialize exactly the work overlap wants to hide.  The donated
        spare's last reader was the decode step one full sync ago, so
        the in-place write cannot race."""
        if self._staged is None:
            return off
        t0 = time.perf_counter()
        staged_nbytes = sum(int(a.nbytes) for a in self._staged[:3])
        spare = self._spare
        pool_g, pool_u, pool_d, cur = self._apply_jit(
            spare["gate"], spare["up"], spare["down"], spare["cur"],
            *self._staged)
        # the generation the caller was decoding against becomes the new
        # spare; it lags by exactly the plan just applied
        self._spare = {"gate": off["gate"], "up": off["up"],
                       "down": off["down"], "cur": off["cur"]}
        self._spare_lag = self._staged_rows
        self._staged = None
        self._staged_rows = None
        new_off = dict(off, gate=pool_g, up=pool_u, down=pool_d, cur=cur)
        if blocking:
            jax.block_until_ready(new_off)
            if (self.watchdog is not None
                    and time.perf_counter() - t0
                    > self.watchdog.deadline(staged_nbytes)):
                self.watchdog.deadline_misses += 1
        self._bump("commit_s", time.perf_counter() - t0)
        return new_off

    def step_update(self, off, target, blocking: bool = False):
        """stage + commit in one call — the blocking mode's critical-path
        update (and the convenience entry tests use).  The overlap mode
        splits the halves instead: ``stage`` behind the in-flight decode,
        ``commit`` at the next idle point."""
        if not self.stage(target):
            return off
        return self.commit(off, blocking=blocking)

    # -- serving-loop orchestration ----------------------------------------
    # ONE copy of the ordering-critical per-step protocol (commit must
    # precede the decode dispatch, stage must follow it, the target must
    # be read after the token sync) — both servers, the streaming
    # benchmark and the example drive these three hooks.

    @functools.partial(annotate_function, name="dali:store.pre_step")
    def pre_step(self, off, mode: str, target):
        """Before the decode dispatch: "blocking" → stage + commit +
        wait (the whole copy on the critical path); "overlap" → commit
        the previously staged rows (the device queue is idle at the step
        boundary, so the donated in-place scatter dispatches without
        stalling); "pipelined" → fold the previous step's inject into
        the pool, then stage THIS step's plan as fresh inject buffers
        riding ``off["inject"]`` — the decode about to dispatch reads
        the plan through the per-layer seam, t+1 fresh.

        Also the robustness heartbeat: the injector clock, health probe
        and degradation ladder advance here, once per step, in every
        mode (``_health_tick``)."""
        self._health_tick()
        if mode == "blocking":
            if target is None:
                return off
            return self.step_update(off, target, blocking=True)
        if mode == "pipelined":
            return self._pipeline_pre_step(off, target)
        return self.commit(off)

    def post_dispatch(self, mode: str, target):
        """Right after the decode dispatch: in "overlap" mode, stage the
        next plan — the H2D copy hides behind the in-flight step's
        compute.  ("pipelined" stages in ``pre_step`` instead: its copy
        still overlaps, with the dispatched step's own early layers.)"""
        if mode == "overlap" and target is not None:
            self.stage(target)

    @staticmethod
    @functools.partial(annotate_function, name="dali:store.next_target")
    def next_target(state, tel):
        """The next step's pool target — this step's cache ∪ prefetch
        (tiny D2H; call after the step's token sync so it never blocks)."""
        return (np.asarray(state["dali"]["resident"])
                | np.asarray(tel["prefetched"]))


# declare the host<->device seams this store exposes to serving graphs:
# the graph-contract auditor (repro/analysis) rejects any callback
# equation in a lowered serving graph that does not match one of these
for _name, _fn, _kind in (
        ("fetch_weights", ExpertStore.fetch_weights_cb, "pure"),
        ("host_ffn", ExpertStore.host_ffn_cb, "pure"),
        ("little_miss", ExpertStore.little_miss_cb, "io"),
        ("prefill_fetch", ExpertStore.prefill_fetch_cb, "pure"),
        ("prefill_host", ExpertStore.prefill_host_cb, "pure")):
    register_callback_seam(_name, _fn, kind=_kind)
del _name, _fn, _kind


def host_expert_params(params, cfg: ModelConfig):
    """Params whose routed expert gate/up/down stacks are host numpy
    arrays, with their device buffers FREED — the caller's own device
    copies are deleted, so only use the returned tree afterwards.

    Pass the result to ``ServeSpec.resolve`` for a physical offload mode
    at full width: the store then takes the host arrays without copying,
    and the device never holds the full-resident stacks beside the slot
    pool (at Mixtral-8x7B widths the two would not fit one chip)."""
    prefix_moe, scan_moe, _ = moe_layer_layout(cfg)

    def to_host(mlp):
        out = dict(mlp)
        for k in ("gate", "up", "down"):
            a = mlp[k]
            if isinstance(a, jax.Array):
                out[k] = np.asarray(a)
                a.delete()
        return out

    out = dict(params)
    out["prefix"] = tuple(
        dict(b, mlp=to_host(b["mlp"])) if i in prefix_moe else b
        for i, b in enumerate(params["prefix"]))
    out["scan"] = tuple(
        dict(b, mlp=to_host(b["mlp"])) if p in scan_moe else b
        for p, b in enumerate(params["scan"]))
    return out


def strip_expert_params(params, cfg: ModelConfig):
    """Params with the routed experts' gate/up/down stacks REMOVED —
    decode through the slot pool never reads them, so a physical-offload
    server only keeps router/shared/attention weights on device (the
    memory saving the paper's layout exists for).  Returns a new pytree;
    the original is untouched."""
    prefix_moe, scan_moe, _ = moe_layer_layout(cfg)

    def strip_mlp(mlp):
        return {k: v for k, v in mlp.items()
                if k not in ("gate", "up", "down")}

    out = dict(params)
    out["prefix"] = tuple(
        dict(b, mlp=strip_mlp(b["mlp"])) if i in prefix_moe else b
        for i, b in enumerate(params["prefix"]))
    out["scan"] = tuple(
        dict(b, mlp=strip_mlp(b["mlp"])) if p in scan_moe else b
        for p, b in enumerate(params["scan"]))
    return out
