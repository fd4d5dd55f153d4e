"""Benchmark harness: one module per paper table/figure, plus the
CLI-style system benchmarks (moe_dispatch, ep_exchange, serving,
policy_ablation — run at their --smoke preset, each writing its
machine-readable reports/bench/*.json).  The suite list lives in
``benchmarks.common.SUITE_SPECS`` — new benchmarks register there, not
here.  Legacy suites print ``name,us_per_call,derived`` CSV rows
(us_per_call = simulated/measured step time where meaningful, 0.0 for
pure-ratio metrics).

  PYTHONPATH=src python -m benchmarks.run [--only speed,policy_ablation,...]
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import Csv, SUITE_SPECS, load_suite


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names "
                         f"(registered: {','.join(SUITE_SPECS)})")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    picks = args.only.split(",") if args.only else list(SUITE_SPECS)
    unknown = [p for p in picks if p not in SUITE_SPECS]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; "
                         f"registered: {sorted(SUITE_SPECS)}")
    csv = Csv()
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in picks:
        print(f"# === {name} ===", flush=True)
        t1 = time.time()
        load_suite(name)(csv)
        print(f"# {name} done in {time.time()-t1:.0f}s", flush=True)
    print(f"# all suites done in {time.time()-t0:.0f}s "
          f"({len(csv.rows)} rows)")


if __name__ == "__main__":
    main()
