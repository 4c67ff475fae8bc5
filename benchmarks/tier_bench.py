"""Tier placement benchmarks: hit-rate, blended GB/s, SLA attainment.

Runs the same seeded multi-tenant trace through the three placement
policies (STATIC memory-style pinning, CACHE LRU, MEMCACHE frequency-aware
admission) at three skew levels, with the fast tier capped at 25% of the
table — the regime where the paper's question ("is the bandwidth-rich,
capacity-poor tier worth it?") has a non-trivial answer. The fast tier
runs at a fixed 8 GB/s; the capacity tier is derated by the Table 1
bandwidth ratio. Deadlines ride a VirtualClock on the modeled tiered
latency, so the numbers are CPU-speed-independent and reproducible.

Appends one record per run to BENCH_tier.json at the repo root — a
trajectory future PRs diff to catch placement/accounting regressions.
Set REPRO_TIER_BENCH_QUICK=1 for a smaller table/trace (test smoke).
"""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax

from benchmarks.common import append_trajectory, obs_digest
from repro.core.advisor import advise_tier_split
from repro.db import Table
from repro.query import physical
from repro.tier import (Policy, TraceSpec, make_trace, paper_tiers,
                        replay_trace, zipf_hit_curve)

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_tier.json"

SKEWS = (0.6, 1.1, 1.5)
FAST_FRACTION = 0.25
SLA_SLACK = 2.0           # deadline = slack x the all-fast service time;
#                           capacity-only service is 2.5x (Table 1 ratio),
#                           so meeting it requires a warm fast tier


def _sizes() -> tuple[int, int, int, int]:
    """(columns, rows, chunk_rows, n_queries); quick mode for CI/tests."""
    if os.environ.get("REPRO_TIER_BENCH_QUICK"):
        return 8, 4096, 256, 40
    return 16, 32768, 1024, 150


def _run_policy(table, trace, tiers, policy, chunk_rows, sla_s):
    """replay_trace warms the placement on the first third (deadline-free)
    and measures steady-state attainment on the rest, rejections counted
    as misses — the same methodology as examples/tiered_store.py."""
    t0 = time.perf_counter()
    pe, eng, att = replay_trace(table, trace, tiers, policy, sla_s=sla_s,
                                chunk_rows=chunk_rows)
    wall_us = (time.perf_counter() - t0) / len(trace) * 1e6
    s = eng.summary()
    return {
        "hit_rate": round(pe.hit_rate, 4),
        "blended_gbps": round(s["tier"]["blended_gbps"], 4),
        "sla_attainment": round(att, 4),
        "served": s["served"],
        "rejected": s["rejected"],
        "energy_j": s["tier"]["energy_j"],
    }, wall_us, eng


def rows():
    n_cols, n_rows, chunk_rows, n_queries = _sizes()
    table = Table.synthetic("tier", n_rows,
                            {f"c{i:02d}": 8 for i in range(n_cols)}, seed=0)
    tiers = paper_tiers(table.nbytes * FAST_FRACTION, fast_gbps=8.0)

    out = []
    record: dict = {"policies": {}}
    for skew in SKEWS:
        trace = make_trace(table, TraceSpec(n_queries=n_queries, skew=skew,
                                            seed=7))
        bytes_typ = sum(
            physical.referenced_bytes(tq.query.plan(), tq.query.aggregates,
                                      table.columns)
            for tq in trace) / len(trace)
        sla_s = SLA_SLACK * bytes_typ / tiers.fast.bandwidth
        for policy in Policy:
            r, wall_us, eng = _run_policy(table, trace, tiers, policy,
                                          chunk_rows, sla_s)
            out.append((f"tier/{policy.value}/skew={skew:g}", wall_us,
                        f"hit={r['hit_rate']:.2f},"
                        f"{r['blended_gbps']:.2f}GBps,"
                        f"att={r['sla_attainment']:.2f}"))
            record["policies"].setdefault(policy.value, {})[str(skew)] = r
            if policy is Policy.MEMCACHE and skew == 1.1:
                # the headline run (check_regress gates on it) carries
                # the trace-diff baseline digest
                record["obs"] = obs_digest(eng)
        adv = advise_tier_split(
            table.nbytes, bytes_typ, sla_s,
            hit_curve=zipf_hit_curve(n_cols, skew),
            fast_gbps=tiers.fast.gbps, capacity_gbps=tiers.capacity.gbps)
        best = adv["best"]
        record.setdefault("advise", {})[str(skew)] = {
            "sla_ms": sla_s * 1e3,
            "best_fast_fraction": best and best["fast_fraction"],
            "roofline_gbps": adv["roofline_gbps"],
        }
        out.append((f"tier/advise_split/skew={skew:g}", 0.0,
                    f"fast_frac={best and best['fast_fraction']}"))

    record.update({
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "columns": n_cols, "rows": n_rows, "chunk_rows": chunk_rows,
        "n_queries": n_queries, "fast_fraction": FAST_FRACTION,
        "fast_gbps": round(tiers.fast.gbps, 4),
        "capacity_gbps": round(tiers.capacity.gbps, 4),
    })
    append_trajectory(BENCH_PATH, record)
    return out
