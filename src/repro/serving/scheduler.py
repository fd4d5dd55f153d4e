"""Serving schedulers: slot-level continuous batching (default) and the
wave-based compat preset.

``ContinuousBatchServer`` keeps a slot table of ``batch_size`` independent
sequences.  Every step it (1) admits queued requests into free slots —
each admission is a B=1 right-padded prefill whose KV rows are inserted
into the batch cache at the slot index (prefill-on-admit), (2) runs ONE
batched decode step in which every slot sits at its own sequence position
(per-slot positions, see models/attention.py), and (3) retires slots whose
request hit EOS / its token budget / the cache horizon, freeing them for
the next admission.  DALI scheduling telemetry (T_cpu/T_gpu estimates,
cache hits, link seconds, paper §4) is aggregated per decode step under
the changing batch composition — the time-varying token mix is exactly
what workload-aware offloading is about (DESIGN.md §3).

``BatchServer`` is the historical wave scheduler: requests are grouped
into fixed waves of equal (left-padded) prompt length, prefilled once and
decoded in lockstep until the whole wave drains.  It pads every request to
the longest prompt in its wave and keeps slots of finished requests idle,
so mixed-length traffic leaves throughput on the floor — kept as a stable
baseline for tests, examples and the serving benchmark.

Both servers take ``policy=`` — a registered offload-policy name
("dali" | "static" | "all_gpu" | "lru" | "score" | "statistical" |
"random" | "none") or an ``OffloadPolicy`` instance (core/policy.py);
names are validated at construction.  Legacy ``dali_cfg``-only
construction keeps meaning "dali".

Both servers also take ``offload=`` — "modeled" (default: every expert
weight stays on device, the policy feeds telemetry only), "blocking",
"overlap" or "pipelined" (physical offload: routed expert weights live
in a host :class:`repro.serving.expert_store.ExpertStore` and decode
reads a device slot pool; the policy's cache ∪ prefetch decisions are
lowered to slot plans and streamed host→device between steps —
"blocking" keeps the copies on the critical path, "overlap" issues them
right after the decode dispatch so they hide behind the step's compute
at the price of one extra step of decision lag, and "pipelined" ships
each step's plan as per-layer inject buffers the decode folds in-graph,
keeping the copy off the critical path AND the decisions t+1-fresh,
DESIGN.md §8–§9).  Prefill streams through the SAME slot pool: each
admission / wave sweep assembles its dense per-layer expert stacks from
resident pool rows plus ``prefill_rows``-sized waves of staged misses,
bit-identical to full-resident prefill (DESIGN.md §11) — so a
physically-offloaded server never materializes the on-device expert
stacks (``strip_expert_params``) for either phase.

Construction routes through :mod:`repro.serving.spec`:
``ServeSpec(...).resolve(params).server()`` is the canonical path; the
legacy kwarg constructors below keep working behind a once-per-process
``DeprecationWarning`` and resolve through the same spec internally.

Telemetry is sync-free in both servers: the jitted DALI schedule folds
per-step sums into a device-side accumulator and the aggregator drains it
once per flush interval (``TelemetryAggregator.observe``/``flush``), so
the decode loop never blocks on a telemetry device→host transfer.

Both servers respect ``Request.not_before`` (virtual arrival time) so the
serving benchmark can drive them with the same Poisson arrival process,
and both report per-request latency and TTFT.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import DaliConfig, TelemetryAggregator
from repro.models.config import ModelConfig
from repro.models.model import init_caches
from repro.serving.spec import (ResolvedServe, ServeSpec,
                                build_store, warn_legacy)
from repro.serving.steps import ADMIT_KEYS, make_admit_step, retire_slot


def make_store(offload: str, params, cfg, policy, fallback: str = "fetch",
               faults=None, cost_model=None):
    """Legacy shim over :func:`repro.serving.spec.build_store` (the
    store-sizing logic moved there so ``ServeSpec.resolve()`` owns the
    one copy); kept for direct callers, deprecated."""
    warn_legacy("make_store")
    return build_store(offload, params, cfg, policy, fallback=fallback,
                       faults=faults, cost_model=cost_model)


class PromptTooLongError(ValueError):
    """A submitted prompt does not fit the server's KV budget.

    Raised by ``submit()`` (both servers) instead of a bare ``assert`` so
    admission control survives ``python -O`` — a prompt of ``max_len``
    tokens would leave no cache row for the first generated token."""

    def __init__(self, n_tokens: int, max_len: int):
        self.n_tokens = int(n_tokens)
        self.max_len = int(max_len)
        super().__init__(
            f"prompt of {n_tokens} tokens exceeds max_len={max_len} "
            f"(prompts must be < max_len so at least one generated "
            f"token fits the cache)")


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 32
    submitted_at: float = 0.0
    not_before: float = 0.0             # virtual arrival time (0 = now)
    output: List[int] = field(default_factory=list)
    # set to [] to record, for every token in ``output``, the (V,) logits
    # row it was sampled from (host copies — for correctness checks,
    # e.g. chip_smoke.py); None records nothing and costs nothing
    logits: Optional[list] = None
    first_token_at: float = 0.0
    done_at: float = 0.0

    @property
    def ttft(self) -> float:
        return self.first_token_at - self.submitted_at

    @property
    def latency(self) -> float:
        return self.done_at - self.submitted_at


@dataclass
class ServeMetrics:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    waves: int = 0                      # wave server: waves; cont.: unused
    steps: int = 0                      # decode steps
    occupancy_sum: int = 0              # live slots summed over steps
    requests: int = 0                   # finished requests
    # physical-offload counters folded from ExpertStore.drain() — the
    # drain-safe path: the store's pure_callback fallbacks bump under a
    # lock and each delta lands in exactly one fold, so per-request
    # rates derived here cannot double- or under-count
    offload_tel: dict = field(default_factory=dict)
    # per-link watchdog counter snapshots keyed by link name ("host>0",
    # "0>3", ...) — monotonic totals from LinkWatchdog.report() /
    # WatchdogBank.report(), so the LATEST snapshot per link wins
    links: dict = field(default_factory=dict)
    dali: TelemetryAggregator = field(default_factory=TelemetryAggregator)

    def fold_offload(self, deltas: Optional[dict]):
        if not deltas:
            return
        for k, v in deltas.items():
            self.offload_tel[k] = self.offload_tel.get(k, 0) + v

    def fold_links(self, links: Optional[dict]):
        """Merge per-link watchdog reports (ExpertStore.health()['links']
        or an EP WatchdogBank.report()).  Reports are cumulative counter
        snapshots, not deltas, so merging replaces per link."""
        if not links:
            return
        for name, rep in links.items():
            self.links[name] = dict(rep)

    def fallback_rate(self) -> float:
        """Miss-fallback (token, k) rows per finished request — the
        per-request visibility of degradation the reports surface."""
        if not self.requests:
            return 0.0
        return self.offload_tel.get("fallback_rows", 0) / self.requests

    # -- legacy accessors (pre-refactor field names) -----------------------
    @property
    def dali_moe_time_est(self) -> float:
        return self.dali.moe_time_est

    @property
    def dali_link_time_est(self) -> float:
        return self.dali.link_time_est

    @property
    def dali_hits(self) -> int:
        return self.dali.hits

    @property
    def dali_lookups(self) -> int:
        return self.dali.lookups

    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    def summary(self) -> str:
        pf = self.prefill_tokens / self.prefill_s if self.prefill_s else 0
        dc = self.decode_tokens / self.decode_s if self.decode_s else 0
        s = (f"steps={self.steps} prefill={pf:.1f} tok/s "
             f"decode={dc:.1f} tok/s occ={self.mean_occupancy():.2f}")
        if self.dali.lookups:
            s += " | " + self.dali.summary()
        if self.offload_tel:
            ot = self.offload_tel
            s += (f" | fb_rows/req={self.fallback_rate():.2f}"
                  f" fetches={ot.get('fallback_fetches', 0)}")
            extras = [(k, ot[k]) for k in ("retries", "stage_aborts",
                                           "corrupt_caught",
                                           "restaged_rows", "little_steps")
                      if ot.get(k)]
            if extras:
                s += " " + " ".join(f"{k}={v}" for k, v in extras)
        hot = [(n, r) for n, r in sorted(self.links.items())
               if r.get("refit_rejections") or r.get("degrade_events")
               or r.get("deadline_misses")]
        if hot:
            s += " | links " + " ".join(
                f"{n}[miss={r.get('deadline_misses', 0)}"
                f" refit={r.get('refits', 0)}"
                f"/rej={r.get('refit_rejections', 0)}"
                f" degr={r.get('degrade_events', 0)}]" for n, r in hot)
        return s


def _pop_arrived(queue: deque, now: float) -> Optional[Request]:
    """FIFO pop of the head request iff its arrival time has passed
    (queues are submitted in arrival order)."""
    if queue and queue[0].not_before <= now:
        return queue.popleft()
    return None


def _record_logits(reqs, rows, logits):
    """Append row ``rows[j]`` of a step's (B, 1, V) ``logits`` to
    ``reqs[j].logits`` for the requests that asked for them (one host
    copy per step, only when some request records)."""
    if not any(r.logits is not None for r in reqs):
        return
    host = np.asarray(logits[:, -1])
    for r, i in zip(reqs, rows):
        if r.logits is not None:
            r.logits.append(host[i])


def _bucket_len(n: int, min_bucket: int, cap: int) -> int:
    """Power-of-two padding bucket for prompt lengths: bounds the number of
    distinct prefill compilations to O(log max_len) instead of one per
    prompt length."""
    b = min_bucket
    while b < n:
        b *= 2
    return max(n, min(b, cap))


# --------------------------------------------------------------------------
# continuous batching
# --------------------------------------------------------------------------

class ContinuousBatchServer:
    """Slot-level continuous batching with prefill-on-admit.

    Request outputs INCLUDE the token sampled by the prefill (it is the
    request's first token — TTFT refers to it) in BOTH servers, so the
    serving benchmark compares identical definitions; ``max_new_tokens``
    bounds the total generated tokens."""

    def __init__(self, params, cfg: Optional[ModelConfig] = None,
                 batch_size: int = 8, max_len: int = 256, eos_id: int = 1,
                 dali_cfg: Optional[DaliConfig] = None, res_vecs=None,
                 min_bucket: int = 16, policy=None,
                 offload: str = "modeled", faults=None, cost_model=None,
                 resolved: Optional[ResolvedServe] = None):
        if resolved is None:
            # legacy kwarg surface: route through the same spec resolution
            # (validation, store sizing, param stripping) the canonical
            # ServeSpec.resolve(params).server() path uses
            if cfg is None:
                raise TypeError("ContinuousBatchServer needs cfg (legacy "
                                "kwargs) or resolved= "
                                "(ServeSpec.resolve(params).server())")
            warn_legacy("ContinuousBatchServer(params, cfg, ...)")
            resolved = ServeSpec.from_legacy(
                cfg, server="continuous", policy=policy, dali_cfg=dali_cfg,
                batch_size=batch_size, max_len=max_len, eos_id=eos_id,
                min_bucket=min_bucket, offload=offload, faults=faults,
                cost_model=cost_model).resolve(params)
        spec = resolved.spec
        from repro.models.config import layer_pattern
        if any(mixer == "mamba" for mixer, _ in layer_pattern(spec.cfg)):
            # attention masks hide right-pad slots (pos = -1); a recurrent
            # SSM state has no such mask, so pad tokens would corrupt it
            raise ValueError(
                "continuous batching requires attention caches; serve "
                "SSM/hybrid archs with the 'wave' preset")
        self._resolved = resolved
        self.params = resolved.params   # expert stacks stripped (physical)
        self.cfg = spec.cfg
        self.batch = spec.batch_size
        self.max_len = spec.max_len
        self.eos = spec.eos_id
        self.dali_cfg = spec.dali_cfg
        self.policy = resolved.policy
        self.offload = spec.offload.mode
        self.store = resolved.store
        self.res_vecs = res_vecs
        self.min_bucket = spec.min_bucket
        self.queue: deque[Request] = deque()
        self.metrics = ServeMetrics()
        # admission prefill streams through the slot pool (physical modes)
        self._prefill = jax.jit(resolved.admit_prefill())
        # resilient decode: one callable that swaps between the healthy/
        # degraded/little jitted variants as the store's ladder reacts
        self._decode = resolved.resilient_decode()
        self._admit = jax.jit(make_admit_step(spec.cfg))
        # rolling (sliding-window) caches keep the LAST S_c positions of a
        # prefill chunk; right-pad beyond the window would evict real prompt
        # tokens, so such configs prefill at exact length (one compilation
        # per distinct prompt length instead of per bucket)
        a = spec.cfg.attn
        self._exact_prefill = bool(
            a is not None and a.sliding_window
            and a.sliding_window < spec.max_len)
        # immutable zero template reused by every admission prefill
        self._fresh_caches = init_caches(spec.cfg, 1, spec.max_len)

    def submit(self, req: Request):
        if not req.submitted_at:
            req.submitted_at = req.not_before or time.perf_counter()
        if len(req.prompt) >= self.max_len:
            raise PromptTooLongError(len(req.prompt), self.max_len)
        self.queue.append(req)

    def _admit_request(self, state, req: Request, slot: int):
        t0 = time.perf_counter()
        L = len(req.prompt)
        Sb = L if self._exact_prefill else \
            _bucket_len(L, self.min_bucket, self.max_len)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :L] = req.prompt                     # RIGHT-pad (see steps)
        if self.store is not None:
            # overlap mode may hold a staged-uncommitted plan from the
            # last decode; commit it so the admission sweep reads a
            # coherent pool (prefill_barrier, DESIGN.md §11)
            state["offload"] = self.store.prefill_barrier(state["offload"])
            first_tok, fresh, logits = self._prefill(
                self.params, jnp.asarray(toks), self._fresh_caches,
                jnp.asarray(L, jnp.int32), state["offload"])
        else:
            first_tok, fresh, logits = self._prefill(
                self.params, jnp.asarray(toks), self._fresh_caches,
                jnp.asarray(L, jnp.int32))
        state = dict(state, **self._admit(
            {k: state[k] for k in ADMIT_KEYS}, fresh, first_tok,
            jnp.asarray(slot, jnp.int32), jnp.asarray(L, jnp.int32)))
        jax.block_until_ready(state["tokens"])
        t1 = time.perf_counter()
        self.metrics.prefill_s += t1 - t0
        self.metrics.prefill_tokens += L
        req.output.append(int(np.asarray(first_tok)[0, 0]))
        _record_logits([req], [0], logits[:, None])
        req.first_token_at = t1
        return state

    def _should_retire(self, req: Request) -> bool:
        return (req.output[-1] == self.eos
                or len(req.output) >= req.max_new_tokens
                or len(req.prompt) + len(req.output) >= self.max_len)

    def run(self) -> List[Request]:
        B = self.batch
        finished: List[Request] = []
        state = self._resolved.init_state(per_slot=True)
        slot_req: List[Optional[Request]] = [None] * B
        # physical offload: the previous step's cache ∪ prefetch decision,
        # pending lowering to a slot plan (double-buffer lag of one step)
        pool_target = None

        while self.queue or any(slot_req):
            now = time.perf_counter()
            # -- admission: fill freed slots from the queue ----------------
            for slot in range(B):
                if slot_req[slot] is not None:
                    continue
                req = _pop_arrived(self.queue, now)
                if req is None:
                    break
                state = self._admit_request(state, req, slot)
                if self._should_retire(req):         # EOS on first token
                    req.done_at = req.first_token_at
                    finished.append(req)
                    state = retire_slot(state, slot)
                else:
                    slot_req[slot] = req

            busy = [i for i in range(B) if slot_req[i] is not None]
            if not busy:
                if not self.queue:
                    break
                time.sleep(max(0.0,
                               self.queue[0].not_before - time.perf_counter()))
                continue

            # -- one decode step over the whole slot table -----------------
            # (physical offload: the store's pre_step/post_dispatch/
            # next_target hooks schedule the pool streaming around the
            # dispatch — see expert_store.py, DESIGN.md §8)
            t0 = time.perf_counter()
            if self.store is not None:
                state["offload"] = self.store.pre_step(
                    state["offload"], self.offload, pool_target)
                self._decode.react()     # follow the degradation ladder
            state, logits, tel = self._decode(self.params, state,
                                              self.res_vecs)
            if self.store is not None:
                self.store.post_dispatch(self.offload, pool_target)
            toks = np.asarray(state["tokens"])[:, 0]
            t1 = time.perf_counter()
            _record_logits([slot_req[i] for i in busy], busy, logits)
            if self.store is not None:
                pool_target = self.store.next_target(state, tel)

            # single per-slot "emitted this step" count: every live slot
            # contributes exactly one token (no re-derivation, no double
            # counting of a request's final token)
            emitted = len(busy)
            for i in busy:
                r = slot_req[i]
                r.output.append(int(toks[i]))
                if self._should_retire(r):
                    r.done_at = t1
                    finished.append(r)
                    slot_req[i] = None
                    state = retire_slot(state, i)
            self.metrics.decode_tokens += emitted
            self.metrics.decode_s += t1 - t0
            self.metrics.steps += 1
            self.metrics.occupancy_sum += emitted
            if self.store is not None:
                self.metrics.fold_offload(self.store.drain())
            # sync-free: telemetry accumulates on device, drained on the
            # aggregator's flush interval (and below, at retirement)
            self.metrics.dali.observe(state.get("dali"), n_active=emitted)
        self.metrics.dali.end_epoch()
        if self.store is not None:
            self.metrics.fold_offload(self.store.drain())
            self.metrics.fold_links(self.store.health().get("links"))
        self.metrics.requests += len(finished)
        return finished


# --------------------------------------------------------------------------
# wave-based compat preset
# --------------------------------------------------------------------------

class BatchServer:
    """Wave scheduler (compat preset): equal-padded waves decoded in
    lockstep.  See module docstring; prefer ContinuousBatchServer."""

    def __init__(self, params, cfg: Optional[ModelConfig] = None,
                 batch_size: int = 8, max_len: int = 256, eos_id: int = 1,
                 dali_cfg: Optional[DaliConfig] = None, res_vecs=None,
                 min_bucket: int = 16, policy=None,
                 offload: str = "modeled", faults=None, cost_model=None,
                 resolved: Optional[ResolvedServe] = None):
        if resolved is None:
            if cfg is None:
                raise TypeError("BatchServer needs cfg (legacy kwargs) or "
                                "resolved= "
                                "(ServeSpec.resolve(params).server())")
            warn_legacy("BatchServer(params, cfg, ...)")
            resolved = ServeSpec.from_legacy(
                cfg, server="wave", policy=policy, dali_cfg=dali_cfg,
                batch_size=batch_size, max_len=max_len, eos_id=eos_id,
                min_bucket=min_bucket, offload=offload, faults=faults,
                cost_model=cost_model).resolve(params)
        spec = resolved.spec
        self._resolved = resolved
        self.params = resolved.params   # expert stacks stripped (physical)
        self.cfg = spec.cfg
        self.batch = spec.batch_size
        self.max_len = spec.max_len
        self.eos = spec.eos_id
        self.dali_cfg = spec.dali_cfg
        self.policy = resolved.policy
        self.offload = spec.offload.mode
        self.store = resolved.store
        self.res_vecs = res_vecs
        self.min_bucket = spec.min_bucket
        self.queue: deque[Request] = deque()
        self.metrics = ServeMetrics()
        # wave prefill streams through the slot pool (physical modes)
        self._prefill = jax.jit(resolved.prefill_step())
        self._decode = resolved.resilient_decode()

    def submit(self, req: Request):
        if not req.submitted_at:
            req.submitted_at = req.not_before or time.perf_counter()
        if len(req.prompt) >= self.max_len:
            raise PromptTooLongError(len(req.prompt), self.max_len)
        self.queue.append(req)

    def run(self) -> List[Request]:
        finished: List[Request] = []
        while self.queue:
            now = time.perf_counter()
            wave = []
            while len(wave) < self.batch:
                req = _pop_arrived(self.queue, now)
                if req is None:
                    break
                wave.append(req)
            if not wave:        # next request hasn't "arrived" yet
                time.sleep(max(0.0,
                               self.queue[0].not_before - time.perf_counter()))
                continue
            finished.extend(self._run_wave(wave))
        return finished

    # -- internals ---------------------------------------------------------
    def _run_wave(self, wave: List[Request]) -> List[Request]:
        B = self.batch
        S_raw = max(len(r.prompt) for r in wave)
        budget = max(r.max_new_tokens for r in wave)
        # bucketed wave length bounds prefill compilations across waves,
        # but never at the cost of decode budget: the bucket is capped so
        # S + budget still fits the KV horizon whenever S_raw would
        S = _bucket_len(S_raw, self.min_bucket,
                        max(S_raw, self.max_len - budget - 1))
        prompts = np.zeros((B, S), np.int32)
        for i, r in enumerate(wave):
            prompts[i, S - len(r.prompt):] = r.prompt   # left-pad

        # per-wave state re-init also re-seeds the slot pool (the fresh
        # policy state draws a fresh random resident set)
        state = self._resolved.init_state(batch=B)
        t0 = time.perf_counter()
        if self.store is not None:
            state["offload"] = self.store.prefill_barrier(state["offload"])
            tok, caches, logits = self._prefill(
                self.params, jnp.asarray(prompts), state["caches"], None,
                state["offload"])
        else:
            tok, caches, logits = self._prefill(
                self.params, jnp.asarray(prompts), state["caches"])
        tok.block_until_ready()
        t_pf = time.perf_counter()
        self.metrics.prefill_s += t_pf - t0
        self.metrics.prefill_tokens += B * S
        state = dict(state, tokens=tok, caches=caches,
                     pos=jnp.asarray(S, jnp.int32))

        # the prefill samples each request's FIRST token (same definition
        # as the continuous server, so the serving benchmark compares like
        # with like: outputs include it, TTFT points at it)
        toks0 = np.asarray(tok)[:, 0]
        _record_logits(wave, range(len(wave)), logits[:, None])
        live = np.array([i < len(wave) for i in range(B)])
        for i, r in enumerate(wave):
            if live[i]:
                r.output.append(int(toks0[i]))
                r.first_token_at = t_pf
                if toks0[i] == self.eos or len(r.output) >= r.max_new_tokens:
                    live[i] = False
                    r.done_at = t_pf
        t0 = time.perf_counter()
        pool_target = None
        for _ in range(min(budget, self.max_len - S - 1)):
            if not live.any():        # whole wave done at/after prefill
                break
            # single per-slot "emitted this step" count: each slot live at
            # the top of the step emits exactly one token (the fix for the
            # old live.sum() + re-derived-final-token double count)
            emitted = int(live.sum())
            if self.store is not None:
                state["offload"] = self.store.pre_step(
                    state["offload"], self.offload, pool_target)
                self._decode.react()     # follow the degradation ladder
            state, logits, tel = self._decode(self.params, state,
                                              self.res_vecs)
            if self.store is not None:
                self.store.post_dispatch(self.offload, pool_target)
            toks = np.asarray(state["tokens"])[:, 0]
            t_step = time.perf_counter()
            if self.store is not None:
                pool_target = self.store.next_target(state, tel)
            _record_logits([r for i, r in enumerate(wave) if live[i]],
                           np.nonzero(live[:len(wave)])[0], logits)
            for i, r in enumerate(wave):
                if live[i]:
                    r.output.append(int(toks[i]))
                    if toks[i] == self.eos or len(r.output) >= r.max_new_tokens:
                        live[i] = False
                        r.done_at = t_step
            self.metrics.decode_tokens += emitted
            self.metrics.steps += 1
            self.metrics.occupancy_sum += emitted
            if self.store is not None:
                self.metrics.fold_offload(self.store.drain())
            self.metrics.dali.observe(state.get("dali"), n_active=emitted)
            if not live.any():
                break
        self.metrics.decode_s += time.perf_counter() - t0
        # each wave re-inits its serve (and DALI) state: close the epoch so
        # the next wave's accumulator drains from zero again
        self.metrics.dali.end_epoch()
        if self.store is not None:
            self.metrics.fold_offload(self.store.drain())
            self.metrics.fold_links(self.store.health().get("links"))
        self.metrics.waves += 1
        self.metrics.requests += len(wave)
        for r in wave:
            if not r.done_at:
                r.done_at = time.perf_counter()
        return wave


SERVER_PRESETS = {
    "continuous": ContinuousBatchServer,
    "wave": BatchServer,
}


def make_server(preset: str, params, cfg: ModelConfig, **kw):
    """Factory over SERVER_PRESETS ('continuous' | 'wave')."""
    try:
        cls = SERVER_PRESETS[preset]
    except KeyError:
        raise ValueError(f"unknown server preset {preset!r}; "
                         f"choose from {sorted(SERVER_PRESETS)}") from None
    return cls(params, cfg, **kw)
