"""The on-chip benchmark.  One run of one cell:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic mix ``bench/traffic/<mix>.json``, its
limits ``bench/cells/<cell>.json`` and each metric's reader
``bench/metrics/<metric>.py``.  It exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.

A run: seeded weights made on the chip -> the program's served path
(``ServeSpec(...).resolve(params).server()``, ``submit`` + ``run``) ->
set-up work (every program the window uses is built and run once) ->
the measured window of ``--seconds``, closed at the first admission or
decode-step boundary after it (work in flight runs to its end, and an
open loop also waits for every request due in the window to get its
first token) -> metrics -> the program's state is freed -> the plain
reference checks a seeded sample of the served tokens.

The last stdout line is one JSON object; the numbers compared are
printed beside their limits as the last stderr lines and last in that
object.  With ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are reported instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE = os.path.join(HERE, ".cache")
JAX_CACHE = os.path.join(CACHE, "jax")


class WindowClosed(Exception):
    """Raised at the first boundary after the window's end; it unwinds
    the server's ``run()``."""


class NoChip(SystemExit):
    pass


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

class Rec:
    """What the harness saw of one request."""

    def __init__(self, g, due_abs=None, warm=False):
        self.gen = g                   # generator.Req
        self.req = None                # the program's Request
        self.due = due_abs             # perf_counter due time (open loop)
        self.warm = warm
        self.admit = None              # (start, end)
        self.times = []                # host time each token was seen
        self.seen = 0


class Window:
    """Admission and decode-step boundaries, token times, counters.

    Set-up ends, and the window opens, after ``warm.admissions``
    admissions and ``warm.steps`` decode steps.  Closed loop: one request
    always waits in the queue, so a freed slot is refilled at once.  Open
    loop: the schedule is submitted when the window opens, due times
    counted from then."""

    def __init__(self, srv, t, seconds, seed, vocab, trace_dir=None,
                 record_logits=False, close_after=None):
        from bench import generator
        self.srv, self.t, self.seconds = srv, t, float(seconds)
        self.record_logits = record_logits
        # tests: close after this many admissions and steps instead of
        # after ``seconds``, so a run on a loaded CPU is repeatable
        self.close_after = close_after
        self.loop = t["loop"]
        self.warm_adm = t["warm"]["admissions"]
        self.warm_steps = t["warm"]["steps"]
        self.max_ext = float(t.get("max_extension_s", 60))
        self.trace_dir = trace_dir
        self.recs = {}
        self.slot_req = {}
        self.admissions = self.steps = 0
        self.open_at = self.close_at = self.deadline = None
        self.pending = None            # host time of the last step's tokens
        self.adm_log = []              # (start, end, prompt tokens)
        self.step_log = []             # [start, end, live, tel or None]
        self.counters = {}
        self.mem_max = 0               # device bytes in use, window boundaries
        self._span = None
        if self.loop == "open":
            self.schedule = generator.open_schedule(
                t, seed, self.seconds + t["extra_arrivals_s"], vocab)
            first = generator.warm_requests(t, seed, vocab)
            if self.warm_adm != len(first):
                raise ValueError(f"open loop warm.admissions must be "
                                 f"{len(first)} (one per prefill bucket)")
            for g in first:
                self._submit(g, warm=True)
        elif self.loop == "closed":
            self.stream = generator.closed_stream(t, seed, vocab)
            self._top_up()
        else:
            raise ValueError(f"loop must be open or closed, got "
                             f"{self.loop!r}")

    # -- submission --------------------------------------------------------
    def _submit(self, g, due_abs=None, warm=False):
        from bench import system
        rec = Rec(g, due_abs, warm)
        rec.req = system.request(g)
        if self.record_logits:
            rec.req.logits = []
        if due_abs is not None:
            rec.req.not_before = due_abs
        self.recs[g.index] = rec
        self.srv.submit(rec.req)

    def _top_up(self):
        while len(self.srv.queue) < 1:
            self._submit(next(self.stream))

    # -- hooks the system wrappers call --------------------------------------
    def boundary(self):
        self._flush()
        if self.open_at is None:
            return
        self.mem_max = max(self.mem_max, bytes_in_use())
        if "open" not in self.counters:
            # the server books the step or admission that opened the
            # window after it returns: read its counters from here on
            self.counters["open"] = self.snapshot()
        now = time.perf_counter()
        if self.close_after is not None:
            if self.admissions + self.steps - self._at_open < \
                    self.close_after:
                return
        elif now < self.deadline or (
                self.loop == "open" and now < self.deadline + self.max_ext
                and not self._due_served()):
            return
        self._close(now)
        raise WindowClosed

    def after_admission(self, req, slot, t0, t1):
        rec = self.recs[req.rid]
        rec.admit = (t0, t1)
        rec.times = [t1] * len(req.output)
        rec.seen = len(req.output)
        self.slot_req[slot] = rec
        self.admissions += 1
        if self.open_at is not None:
            self.adm_log.append((t0, t1, len(req.prompt)))
        if self.loop == "closed":
            self._top_up()
        if (self.open_at is None and self.warm_steps == 0
                and self.admissions >= self.warm_adm):
            self._open(t1)

    def after_step(self, t0, t1, state, tel):
        self.pending = t1
        self.steps += 1
        if self.open_at is not None:
            keep = None
            if self.trace_dir is not None and "on_gpu" in tel:
                keep = (tel["on_gpu"], tel["on_cpu"])
            self.step_log.append([t0, t1, 0, keep])
        elif (self.warm_steps and self.steps >= self.warm_steps
              and self.admissions >= self.warm_adm):
            self._open(t1)

    # -- internals -----------------------------------------------------------
    def _flush(self):
        """Attribute the last decode step's tokens to its requests."""
        if self.pending is None:
            return
        live = 0
        for rec in self.slot_req.values():
            n = len(rec.req.output)
            if n > rec.seen:
                rec.times += [self.pending] * (n - rec.seen)
                rec.seen = n
                live += 1
        if self.step_log and self.step_log[-1][1] == self.pending:
            self.step_log[-1][2] = live
        self.pending = None

    def _due_served(self) -> bool:
        return all(r.admit is not None for r in self.recs.values()
                   if r.due is not None and r.due < self.deadline)

    def snapshot(self) -> dict:
        m = self.srv.metrics
        out = {k: getattr(m, k) for k in (
            "prefill_tokens", "decode_tokens", "prefill_s", "decode_s",
            "steps")}
        if self.srv.store is not None:
            out.update({f"store.{k}": v
                        for k, v in self.srv.store.stats().items()})
        return out

    def _open(self, now):
        import jax
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("bench:window")
            self._span.__enter__()
            now = time.perf_counter()
        self.open_at = now
        self.deadline = now + self.seconds
        self._at_open = self.admissions + self.steps
        say(f"[{now - T_START:8.1f}s] window open")
        if self.loop == "open":
            for g in self.schedule:
                self._submit(g, due_abs=now + g.due)

    def _close(self, now):
        self.close_at = now
        self.mem_max = max(self.mem_max, bytes_in_use())
        self.counters["close"] = self.snapshot()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


def bytes_in_use() -> int:
    """Device bytes in use now, on the fullest chip."""
    import jax
    return max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.local_devices())


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

class Ctx:
    """What a metric reader reads: the window, its requests and counters,
    the reduced trace (traced runs), the configuration and the peaks."""

    def __init__(self, win: Window, m, t, peak, trace, setup_s):
        self.m, self.t, self.peak, self.trace = m, t, peak, trace
        self.open_at, self.close_at = win.open_at, win.close_at
        self.deadline = win.deadline
        self.window_s = win.close_at - win.open_at
        self.setup_s = setup_s
        self.recs = [r for r in win.recs.values() if not r.warm]
        self.adm_log = win.adm_log
        self.step_log = win.step_log
        self.counters = win.counters

    def delta(self, key):
        """A counter's change over the window (None: no such counter)."""
        a, b = self.counters["open"].get(key), self.counters["close"].get(key)
        return None if a is None else b - a

    def in_window(self, t) -> bool:
        return self.open_at < t <= self.close_at

    def due_in_window(self):
        return [r for r in self.recs
                if r.due is not None and self.open_at <= r.due < self.deadline]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_names(bench: dict, workload: str, trace: bool):
    """The cell's metrics: end-to-end ones with ``--trace 0``, per-layer
    ones with ``--trace 1``; a metric with a ``workloads`` list only in
    those cells."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(x["name"], x["unit"]) for x in group
            if workload in x.get("workloads", [workload])]


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def device_info(chips: int):
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        say(f"no TPU: JAX found {info}; the benchmark runs only on the chip")
        raise NoChip(2)
    if len(devs) < chips:
        say(f"the cell needs {chips} chips, JAX found {len(devs)}")
        raise NoChip(2)
    return info


def keep_logs_inside():
    """The TPU runtime's logs go inside the checkout (before JAX starts)."""
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE, "tpu_logs"))


def use_cache(path: str = JAX_CACHE):
    """JAX's persistent compilation cache at a fixed path in the checkout
    (whatever the environment says), every program kept."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(m, t, limits, seed, seconds, trace=False, names=(),
             peak=None, control=False, fault=None, close_after=None):
    """Serve one window of one cell and check it.  Returns the result
    dict (without ``device``).  Tests only: ``fault`` is called with the
    server before the window, to break the timed path; ``close_after``
    closes the window after that many admissions and decode steps."""
    import jax
    import numpy as np
    from bench import check, system
    from bench import trace as trace_mod

    def stage(what):
        say(f"[{time.perf_counter() - T_START:8.1f}s] {what}")

    params = system.program_params(m, seed)
    jax.block_until_ready(params)
    stage("weights made on the device")
    srv = system.build_server(m, t, params)
    del params
    stage("server built")
    if fault is not None:
        fault(srv)
    tdir = os.path.join(CACHE, "trace") if trace else None
    win = Window(srv, t, seconds, seed, m["vocab_size"], tdir,
                 limits.get("record_logits", False), close_after)
    system.install(srv, win, trace)
    closed = False
    try:
        srv.run()
    except WindowClosed:
        closed = True
    win._flush()
    span = (win.close_at - win.open_at) if closed else None
    stage(f"window closed={closed} ({span} s): {win.steps} steps and "
          f"{win.admissions} admissions in all")
    red = None
    if trace:
        jax.profiler.stop_trace()
        if closed:
            red = trace_mod.reduce_file(trace_mod.find_xplane(tdir))
    # the process's high-water mark: set-up's (one-call weight build)
    # where that is higher than serving's, so reported apart
    setup_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.local_devices())
    for rec in win.step_log:                 # routing of traced steps
        if rec[3] is not None:
            rec[3] = np.asarray(rec[3][0]) | np.asarray(rec[3][1])
    served = [(r.gen.prompt, list(r.req.output), r.req.logits) for r in
              sorted(win.recs.values(), key=lambda r: r.gen.index)
              if not r.warm and r.req.output]
    result = {"attempted": 0, "failed": 0, "metrics": {},
              "memory_peak_bytes": int(win.mem_max),
              "process_peak_bytes": int(setup_peak)}
    if closed:
        ctx = Ctx(win, m, t, peak, red, win.open_at - T_START)
        due = ctx.due_in_window()
        if t["loop"] == "open":
            result["attempted"] = len(due)
            result["failed"] = sum(1 for r in due if not r.times)
        else:
            result["attempted"] = sum(
                1 for r in ctx.recs if any(ctx.in_window(x) for x in r.times))
        for name, unit in names:
            v = load_reader(name)(ctx)
            if v is not None:
                result["metrics"][name] = {"value": float(v), "unit": unit}
        if red is not None:
            result["busy_s"], result["window_s"] = red.busy_s, red.window_s
            result["breakdown"] = {
                "device_ops": [[k, v] for k, v in red.op_seconds()[:10]],
                "idle_gaps": [[k, v] for k, v in red.idle_gaps(10)]}
        result["lateness"] = lateness(ctx)
    # free the program's state before the reference runs on the chip: the
    # host store's arrays too (the compiled callbacks keep the store
    # object itself alive)
    if srv.store is not None:
        srv.store.host.clear()
    del srv, win
    gc.collect()
    res = check.compare(m, seed, served, limits, control=control)
    stage("reference check done")
    ok, result["compared"] = check.verdict(res, limits)
    result["check"] = res
    result["correct"] = bool(closed and ok)
    return result


def lateness(ctx: Ctx) -> dict:
    """How late the open-loop generator's requests were offered: the
    server pops a request at its first boundary after the due time, so
    queue wait (due -> admission start) bounds it.  Time to first token
    goes on the same earlier line: read, not judged (its p90 spread too
    widely over a window for a bound; PERF.md)."""
    from bench import readers
    w = readers.queue_wait_ms(ctx)
    if not w:
        return {}
    t = readers.ttft_ms(ctx)
    return {"queue_wait_p50_ms": readers.pct(w, 50),
            "queue_wait_p90_ms": readers.pct(w, 90),
            "queue_wait_max_ms": max(w), "ttft_p50_ms": readers.pct(t, 50),
            "ttft_p90_ms": readers.pct(t, 90), "n": len(w)}


def find_cell(bench: dict, workload: str):
    for c in bench["workloads"]:
        if c["name"] == workload:
            cfg = next(x for x in bench["configs"] if x["name"] == c["config"])
            return c, cfg
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str, root: str = ROOT):
    from bench import check, generator
    cell, cfg = find_cell(bench, workload)
    with open(os.path.join(root, cfg["file"])) as fh:
        m = json.load(fh)
    m["name"] = cfg["name"]
    t = generator.load_traffic(cell["traffic"])
    return cell, m, t, check.load_limits(workload)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell, m, t, limits = load_cell(bench, args.workload)
    keep_logs_inside()
    try:
        info = device_info(cell["chips"])
    except NoChip as e:
        return e.code
    from bench.peaks import peaks_for
    try:
        peak = peaks_for(info["kind"])
    except KeyError as e:
        say(str(e))
        return 2
    use_cache()
    names = metric_names(bench, args.workload, bool(args.trace))
    res = run_cell(m, t, limits, args.seed, args.seconds,
                   trace=bool(args.trace), names=names, peak=peak)
    say(f"open loop, lateness and first tokens: "
        f"{json.dumps(res.pop('lateness', {}))}")
    say(f"check: {json.dumps(res.pop('check'))}")
    say(f"device memory: {res['memory_peak_bytes']} B in use at most at a "
        f"window boundary, {res.pop('process_peak_bytes')} B the process's "
        f"high-water mark (set-up included)")
    device = dict(info, memory_peak_bytes=res.pop("memory_peak_bytes"))
    if args.trace:
        device["busy_s"] = res.pop("busy_s", 0.0)
        device["window_s"] = res.pop("window_s", 0.0)
    compared = res.pop("compared")
    for k, v in compared.items():
        say(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": device}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["compared"] = compared
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
