"""Benchmark driver: one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV rows, or a JSON array with
``--json``. ``--only substr`` restricts to matching module names (CI runs
``--only queries --json`` as one smoke invocation).
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks.common import emit
from repro.launch import compile_cache

MODULES = (
    "benchmarks.fig1_bandwidth_capacity",
    "benchmarks.fig3_performance_provisioning",
    "benchmarks.fig4_power_provisioning",
    "benchmarks.fig5_capacity_provisioning",
    "benchmarks.fig6_energy",
    "benchmarks.crossover",
    "benchmarks.advisor_tpu",
    "benchmarks.queries_bench",
    "benchmarks.tier_bench",
    "benchmarks.energy_bench",
    "benchmarks.store_bench",
    "benchmarks.resilience_bench",
    "benchmarks.roofline_table",
)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON array instead of CSV rows")
    ap.add_argument("--only", default="",
                    help="run only modules whose name contains this")
    args = ap.parse_args(argv)
    compile_cache.enable()

    modules = [m for m in MODULES if args.only in m]
    records = []
    if not args.json:
        print("name,us_per_call,derived")
    failed = []
    for modname in modules:
        try:
            mod = __import__(modname, fromlist=["rows"])
            rows = mod.rows()
        except Exception:
            failed.append(modname)
            traceback.print_exc(file=sys.stderr)
            rows = [(modname, 0.0, "ERROR")]
        if args.json:
            records += [{"name": n, "us_per_call": us, "derived": d}
                        for n, us, d in rows]
        else:
            emit(rows)
    if args.json:
        print(json.dumps(records, indent=1))
    if failed:
        raise SystemExit(f"benchmark failures: {failed}")


if __name__ == "__main__":
    main()
