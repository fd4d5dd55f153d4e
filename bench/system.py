"""The system under test as the benchmark drives it: the program's
``ModelConfig`` from a configuration file and its parameter tree from the
benchmark's seeded weights (both by the file's family,
``bench/families/``), the served path (``ServeSpec`` -> resolve ->
continuous server), and wrappers around the server's calls that give the
measured window its boundaries, timings and spans.  Nothing here edits
the program: the wrappers replace attributes of one server instance.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax

from bench import weights as W
from bench.families import config_key, family


def model_config(m: dict):
    """The program's ModelConfig for a configuration file ``m``."""
    return family(m).model_config(m)


def program_params(m: dict, seed: int):
    """Seeded parameters in the program's layout, made by the family's
    builder; checked against the tree the program's own ``init_model``
    would build."""
    from repro.models.model import init_model
    cfg = model_config(m)
    build = functools.partial(family(m).params, config_key(m),
                              *W.seed_halves(seed))
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(build)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise ValueError("the benchmark's parameter tree does not match the "
                         "program's")
    return build()


def build_server(m: dict, t: dict, params):
    """``ServeSpec(...).resolve(params).server()`` for configuration ``m``
    and traffic ``t``.  A physical offload mode first moves the expert
    stacks to host memory (their device copies are freed)."""
    from repro.serving.spec import OffloadSpec, ServeSpec
    from repro.serving.steps import default_dali_config
    dep = m["deployment"]
    cfg = model_config(m)
    if dep["offload"] != "modeled":
        from repro.serving.expert_store import host_expert_params
        params = host_expert_params(params, cfg)
    spec = ServeSpec(cfg=cfg, server="continuous", policy=dep["policy"],
                     dali_cfg=default_dali_config(
                         cfg, cache_ratio=dep["cache_ratio"]),
                     batch_size=t["slots"], max_len=t["max_len"],
                     eos_id=m["eos_token_id"], min_bucket=t["min_bucket"],
                     offload=OffloadSpec(mode=dep["offload"]))
    return spec.resolve(params).server()


def request(r):
    """The program's Request for a generated request."""
    from repro.serving.scheduler import Request
    return Request(rid=r.index, prompt=r.prompt,
                   max_new_tokens=r.max_new_tokens)


def span(name: str, on: bool):
    """A profiler host span when tracing, else nothing."""
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


class _Decode:
    """The server's decode callable, wrapped: boundary check before
    (unless the store's ``pre_step`` already made it), token sync and
    timing after.  With a store, the sync waits until the store's
    ``post_dispatch`` has run (``finish``), so host work the server
    overlaps with the step stays overlapped.  ``react`` passes through."""

    def __init__(self, inner, window, trace: bool, store: bool):
        self._inner, self._window = inner, window
        self._trace, self._store = trace, store
        self._pending = None

    def react(self):
        return self._inner.react()

    def __call__(self, params, state, res_vecs=None):
        if not self._store:
            self._window.boundary()
        t0 = time.perf_counter()
        with span("bench:decode", self._trace):
            out = self._inner(params, state, res_vecs)
        self._pending = (t0, out)
        if not self._store:
            self.finish()
        return out

    def finish(self):
        """The step's tokens are on the host: book the step."""
        t0, out = self._pending
        self._pending = None
        with span("bench:decode.sync", self._trace):
            jax.block_until_ready(out[0]["tokens"])
        self._window.after_step(t0, time.perf_counter(), out[0], out[2])


def install(srv, window, trace: bool):
    """Wrap one server's calls so ``window`` sees every admission and
    decode step, with host spans around them when ``trace``."""
    admit_request = srv._admit_request

    def _admit_request(state, req, slot):
        window.boundary()
        t0 = time.perf_counter()
        with span("bench:admit", trace):
            state = admit_request(state, req, slot)
        window.after_admission(req, slot, t0, time.perf_counter())
        return state

    srv._admit_request = _admit_request
    store = srv.store
    decode = srv._decode = _Decode(srv._decode, window, trace,
                                   store=store is not None)
    if trace:
        for name, label in (("_prefill", "bench:prefill"),
                            ("_admit", "bench:cache_insert")):
            setattr(srv, name, _spanned(label, getattr(srv, name)))
    if store is not None:
        pre_step, post_dispatch = store.pre_step, store.post_dispatch

        def _pre_step(off, mode, target):
            window.boundary()
            with span("bench:store.pre_step", trace):
                return pre_step(off, mode, target)

        def _post_dispatch(mode, target):
            with span("bench:store.post_dispatch", trace):
                post_dispatch(mode, target)
            decode.finish()

        store.pre_step, store.post_dispatch = _pre_step, _post_dispatch
        if trace:
            store.next_target = _spanned("bench:store.next_target",
                                         store.next_target)


def _spanned(name, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped
