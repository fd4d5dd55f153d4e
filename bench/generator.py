"""The one traffic generator.  A traffic mix is a data file,
``bench/traffic/<name>.json``, that this module reads:

    loop        "open" (arrivals on a schedule, whatever the server does)
                | "closed" (every slot kept busy: a finished request is
                replaced at once)
    slots, max_len, min_bucket      the server's batch geometry
    rate_per_s  open loop: mean arrival rate (Poisson)
    prompt, output   {"dist": "fixed", "tokens": n}
                | {"dist": "lognormal", "median": m, "sigma": s,
                   "min": lo, "max": hi}
    warm        {"admissions": n, "steps": k}: set-up work before the
                window opens (closed loop: the first requests of the
                sequence; open loop: one request per prefill bucket)

Every seed gets the same multiset of sizes, drawn at stratified
quantiles of their distributions, in an order the seed shuffles, and the
same arrival times (the exponential gaps' stratified quantiles in one
fixed order); prompt token ids come from the seed.  So two seeds offer
the same work at the same moments, and the seed does not change how
much work a window holds or when it bunches up.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIRST_ID = 3        # ids 0, 1, 2 (unk, bos, eos) never appear in a prompt
BLOCK = 64          # closed loop: sizes are stratified over blocks of this


@dataclass
class Req:
    """One generated request.  ``due`` is seconds after the window opens
    (open loop) or None (closed loop: due when a slot frees)."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float | None = None


def load_traffic(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as fh:
        return json.load(fh)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n sizes at the stratified quantiles (i + 0.5) / n of ``spec``."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["tokens"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def _prompt(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(FIRST_ID, vocab, n).astype(np.int32)


def open_schedule(t: dict, seed: int, horizon_s: float,
                  vocab: int) -> List[Req]:
    """Poisson arrivals at ``rate_per_s`` over ``horizon_s`` seconds: the
    number of requests is the rate times the horizon, the gaps are the
    exponential distribution's stratified quantiles in one order for
    every seed (the tail of time to first token follows how arrivals
    bunch, so a seed that reordered them would change the work)."""
    n = max(1, int(round(t["rate_per_s"] * horizon_s)))
    rng = _rng(seed, 0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / t["rate_per_s"]
    gaps = gaps[_rng(0, 3).permutation(n)]
    due = np.cumsum(gaps) - gaps[0]           # the first request at 0
    p = _quantiles(t["prompt"], n)[rng.permutation(n)]
    o = _quantiles(t["output"], n)[rng.permutation(n)]
    return [Req(i, _prompt(rng, int(p[i]), vocab), int(o[i]), float(due[i]))
            for i in range(n)]


def closed_stream(t: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless requests for a closed loop, sizes stratified per block."""
    rng = _rng(seed, 1)
    i = 0
    while True:
        p = _quantiles(t["prompt"], BLOCK)[rng.permutation(BLOCK)]
        o = _quantiles(t["output"], BLOCK)[rng.permutation(BLOCK)]
        for j in range(BLOCK):
            yield Req(i, _prompt(rng, int(p[j]), vocab), int(o[j]))
            i += 1


def bucket_len(n: int, min_bucket: int, cap: int) -> int:
    """The server's prefill padding bucket for an n-token prompt (a
    power-of-two multiple of ``min_bucket``, capped at ``cap``)."""
    b = min_bucket
    while b < n:
        b *= 2
    return max(n, min(b, cap))


def warm_requests(t: dict, seed: int, vocab: int) -> List[Req]:
    """Open loop set-up: one 2-token request per prefill bucket the mix
    can use, longest first, so every program the window runs is built."""
    lo, hi = t["prompt"].get("min", t["prompt"].get("tokens")), \
        t["prompt"].get("max", t["prompt"].get("tokens"))
    sizes, n = [], lo
    while True:
        b = bucket_len(n, t["min_bucket"], t["max_len"])
        if b not in sizes:
            sizes.append(b)
        if n >= hi:
            break
        n = min(hi, b + 1)
    rng = _rng(seed, 2)
    return [Req(-1 - k, _prompt(rng, min(b, hi), vocab), 2)
            for k, b in enumerate(sorted(sizes, reverse=True))]
