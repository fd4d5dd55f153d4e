"""Readings that set a cell's limits: the program's numbers and the
float8 control's, over many seeds in one process (set-up is long, so one
process serves every seed; each seed gets new weights, a new server, a
short window at the cell's own load, and the comparison).  Not part of a
benchmark run.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

One JSON line per seed on stdout: the program's readings (``widest_gap``,
``flip_share``, ``logit_err``) and the control's (``control_widest_gap``,
``control_flip_share``, ``control_logit_err``) at the same positions.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]

from bench import run  # noqa: E402


def host_rss() -> int:
    """This process's resident set now (one host store must not pile up
    on the next seed's)."""
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell, m, t, limits = run.load_cell(bench, args.workload)
    run.keep_logs_inside()
    try:
        run.device_info(cell["chips"])
    except run.NoChip as e:
        return e.code
    run.use_cache()
    limits = dict(limits, record_logits=True)
    for seed in args.seeds:
        r = run.run_cell(m, t, limits, seed, args.seconds, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], **r["check"],
                          "host_rss_bytes": host_rss()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
