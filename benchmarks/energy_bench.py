"""Energy & cost benchmarks: metered watts, power-capped replay, $/query.

Two experiments, both appended to BENCH_energy.json at the repo root:

1. *Power-capped replay*: the tier bench's seeded zipfian trace replayed
   through the MEMCACHE policy with the full energy model (per-byte tier
   energy + compute-chip watts over modeled busy time), uncapped to
   establish the demand power, then under a PowerCap at 70% of that
   demand. Recorded: SLA attainment with and without the cap, the max
   window-average watts over the whole replay (the contract: <= budget),
   throttle/rejection counts, and the per-tenant joules bill.

2. *Decision surface*: the paper's 16 TiB / 20%-accessed workload swept
   over SLA x skew x power budget (Fig. 4's 50 kW / 250 kW / 1 MW
   operating points), winners priced from the CostSheet at the
   datasheet's Eq. 4 tier rates.

Set REPRO_ENERGY_BENCH_QUICK=1 for a smaller table/trace (CI smoke).
"""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax

from benchmarks.common import append_trajectory, obs_digest
from repro.core.advisor import advise_cost
from repro.core.systems import DIE_STACKED, TiB
from repro.db import Table
from repro.energy import PowerCap, chip_compute_watts, decision_surface
from repro.tier import (Policy, TraceSpec, make_trace, paper_tiers,
                        replay_trace)

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_energy.json"

SKEW = 1.1
FAST_FRACTION = 0.25
SLA_SLACK = 2.0
CAP_FRACTION = 0.7        # budget = 70% of the uncapped demand power
PAPER_DB = 16 * TiB
PAPER_ACCESSED = 0.20


def _sizes() -> tuple[int, int, int, int]:
    """(columns, rows, chunk_rows, n_queries); quick mode for CI/tests."""
    if os.environ.get("REPRO_ENERGY_BENCH_QUICK"):
        return 8, 4096, 256, 40
    return 16, 32768, 1024, 150


def _capped_replay() -> tuple[list, dict]:
    n_cols, n_rows, chunk_rows, n_queries = _sizes()
    table = Table.synthetic("energy", n_rows,
                            {f"c{i:02d}": 8 for i in range(n_cols)}, seed=0)
    tiers = paper_tiers(table.nbytes * FAST_FRACTION, fast_gbps=8.0)
    trace = make_trace(table, TraceSpec(n_queries=n_queries, skew=SKEW,
                                        seed=7))
    compute_w = chip_compute_watts(DIE_STACKED)
    sla_s = SLA_SLACK * (table.nbytes / n_cols * 2) / tiers.fast.bandwidth

    t0 = time.perf_counter()
    pe, eng, att = replay_trace(table, trace, tiers, Policy.MEMCACHE,
                                sla_s=sla_s, chunk_rows=chunk_rows,
                                compute_w=compute_w)
    uncapped_us = (time.perf_counter() - t0) / len(trace) * 1e6
    energy = eng.summary()["energy"]
    demand_w = energy["total_j"] / eng.seconds_total
    budget_w = CAP_FRACTION * demand_w
    window_s = 20 * sla_s

    cap = PowerCap(budget_w=budget_w, window_s=window_s)
    t0 = time.perf_counter()
    _, ceng, catt = replay_trace(table, trace, tiers, Policy.MEMCACHE,
                                 sla_s=sla_s, chunk_rows=chunk_rows,
                                 compute_w=compute_w, power_cap=cap)
    capped_us = (time.perf_counter() - t0) / len(trace) * 1e6
    rep = cap.report(now=ceng.clock())
    assert rep["max_window_w"] <= budget_w * (1 + 1e-9), \
        f"power cap violated: {rep['max_window_w']} > {budget_w}"

    record = {
        "sla_ms": sla_s * 1e3,
        "compute_w_per_chip": compute_w,
        "demand_w": demand_w,
        "budget_w": budget_w,
        "window_s": window_s,
        "uncapped": {"attainment": att,
                     "energy_j": energy["total_j"],
                     "j_per_query": energy["j_per_query"],
                     "hit_rate": pe.hit_rate},
        "capped": {"attainment": catt,
                   "max_window_w": rep["max_window_w"],
                   "budget_utilization": rep["budget_utilization"],
                   "throttled_queries": rep["throttled_queries"],
                   "throttle_s_total": rep["throttle_s_total"],
                   "rejected": ceng.summary()["rejected"]},
        "by_tenant": {str(k): v for k, v in
                      sorted(ceng.summary()["energy"]["by_tenant"].items())},
        # the capped replay is the gated headline; its digest is the
        # trace-diff explainer's baseline
        "obs": obs_digest(ceng),
    }
    rows = [
        ("energy/replay/uncapped", uncapped_us,
         f"att={att:.2f},{demand_w:.1f}W,"
         f"{energy['j_per_query']:.2e}J/q"),
        ("energy/replay/capped70", capped_us,
         f"att={catt:.2f},peak={rep['max_window_w']:.1f}W"
         f"<=budget={budget_w:.1f}W,"
         f"throttled={rep['throttled_queries']}"),
    ]
    return rows, record


def _surface() -> tuple[list, dict]:
    quick = bool(os.environ.get("REPRO_ENERGY_BENCH_QUICK"))
    slas = (0.010, 0.060, 1.0) if quick else (0.005, 0.010, 0.060, 0.250,
                                              1.0)
    t0 = time.perf_counter()
    surf = decision_surface(PAPER_DB, PAPER_ACCESSED * PAPER_DB,
                            slas=slas, skews=(None, SKEW))
    us = (time.perf_counter() - t0) / max(len(surf["cells"]), 1) * 1e6
    rows = []
    for cell in surf["cells"]:
        if cell["skew"] is None and cell["power_budget_w"] == 1e6:
            rows.append((
                f"energy/surface/sla={cell['sla_s']:g}s/1MW", us,
                f"winner={cell['winner']}"))
    cheapest = advise_cost(PAPER_DB, PAPER_ACCESSED * PAPER_DB, 0.010, 1e6,
                           skew=SKEW)
    rows.append(("energy/advise_cost/10ms/1MW/zipf1.1", 0.0,
                 f"winner={cheapest['winner']},"
                 f"${(cheapest['usd_per_query'] or 0):.4f}/q"))
    record = {
        "fast_gbps": None,              # the datasheet's Eq. 4 rates
        "winners": {f"sla={c['sla_s']:g};skew={c['skew']};"
                    f"budget={c['power_budget_w']:g}": c["winner"]
                    for c in surf["cells"]},
        "advise_cost_10ms_1mw": {"winner": cheapest["winner"],
                                 "usd_per_query":
                                     cheapest["usd_per_query"]},
    }
    return rows, record


def rows():
    replay_rows, replay_rec = _capped_replay()
    surface_rows, surface_rec = _surface()
    record = {
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "replay": replay_rec,
        "surface": surface_rec,
        # every bench record carries its digest at the top level — the
        # one place check_regress.py's explainer looks
        "obs": replay_rec.pop("obs"),
    }
    append_trajectory(BENCH_PATH, record)
    return replay_rows + surface_rows
