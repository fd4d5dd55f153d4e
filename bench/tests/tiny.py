"""Tiny stand-ins for the cells, for CPU tests: the same traffic files
cut to a few slots and short sequences, and the tiny configurations in
``data/``."""
import json
import os

from bench import generator

DATA = os.path.join(os.path.dirname(__file__), "data")
# tiny limits from tiny readings (CPU, 8 seeds a cell, windows closed by
# count): sound runs read widest_gap 0-1.32 (a bf16 near tie of the
# router sends a token to another expert), logit_err 0.0199-0.0435 and
# flip_share 0-0.031; the float8 control reads widest_gap 0-1.52 (no
# wider than sound runs on most seeds), logit_err 0.219-0.293 and
# flip_share 0.072-0.172, so it fails logit_err and flip_share; an
# altered token reads a widest gap of several units.  Each cell compares
# what its limits file compares: host-decode records logits, chat does
# not.
LIMITS = {"mixtral-host-decode": {"widest_gap": 2.0, "logit_err": 0.08},
          "mixtral-hbm-chat": {"widest_gap": 2.0, "flip_share": 0.05}}


def config(mode):
    m = json.load(open(os.path.join(DATA, f"tiny-{mode}.json")))
    m["name"] = f"tiny-{mode}"
    return m


def traffic(name):
    t = dict(generator.load_traffic(name))
    t.update(slots=min(t["slots"], 4), max_len=min(t["max_len"], 160),
             min_bucket=min(t["min_bucket"], 16))
    if t["prompt"]["dist"] == "fixed":
        t["prompt"] = {"dist": "fixed",
                       "tokens": min(t["prompt"]["tokens"], 64)}
        t["output"] = {"dist": "fixed",
                       "tokens": min(t["output"]["tokens"], 40)}
    else:
        # 100 requests all due within a microsecond of the window's
        # opening: admissions follow the queue alone, not the clock
        t.update(rate_per_s=1e9, extra_arrivals_s=1e-7)
        t["prompt"] = dict(t["prompt"], median=24, min=8, max=96)
        t["output"] = dict(t["output"], median=8, min=2, max=40)
        t["warm"] = dict(t["warm"], admissions=len(
            generator.warm_requests(t, 0, 100)))
    return t


def limits(cell):
    return {"compare": dict(LIMITS[cell]),
            "record_logits": "logit_err" in LIMITS[cell],
            "sample_tokens": 64, "max_requests": 16}


# admissions and decode steps a tiny window holds (count, not seconds)
CLOSE_AFTER = 60

CELLS = {"mixtral-host-decode": ("host", "decode-closed"),
         "mixtral-hbm-chat": ("hbm", "lmsys-chat-poisson")}
