"""Query-engine benchmarks: sharded scan GB/s + SLA attainment vs load.

Shards a synthetic table across every available device (a CPU run that
wants 8 virtual devices sets
XLA_FLAGS=--xla_force_host_platform_device_count=8 on its own command
line), times the sharded scan+aggregate path, compares
attained throughput against the analytical model's roofline
(QueryEngine.model_check), then sweeps offered load: batches of deadline-
carrying queries at 0.5x/1x/2x the engine's measured capacity, recording
attainment and rejections. Appends to BENCH_queries.json at the repo root —
a trajectory future PRs diff to catch sharding/dispatch regressions.

Interpret-mode numbers on CPU: the GB/s is not TPU-representative, but the
sharded-vs-oracle parity and the attainment-vs-load shape are.
"""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import append_trajectory, obs_digest, timed
from repro.db import Table
from repro.db.columnar import BitPackedColumn
from repro.launch.mesh import make_mesh
from repro.obs import metrics as obs_metrics
from repro.query import GroupBy, Pred, Query, QueryEngine, ShardedTable
from repro.query import relational

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_queries.json"


def _attainment_vs_load(st, measured_gbps: float, loads=(0.5, 1.0, 2.0),
                        n_queries: int = 12) -> dict:
    """Submit batches whose deadlines assume `load` x the engine's measured
    capacity: load <= 1 should mostly meet, load > 1 must shed/miss."""
    q = Query(Pred("a", "lt", 64), aggregates=("b",))
    out = {}
    for load in loads:
        eng = QueryEngine(st, est_gbps=measured_gbps)
        service = eng.bytes_scanned(q) / (measured_gbps * 1e9)
        t0 = eng.clock()
        for i in range(n_queries):
            # back-to-back arrivals; deadline i assumes the engine drains
            # (i+1) queries at load x capacity
            eng.submit(q, deadline=t0 + (i + 1) * service / load)
        eng.run()
        s = eng.summary()
        out[load] = {"sla_attainment": s["sla_attainment"],
                     "served": s["served"], "rejected": s["rejected"],
                     "latency_p99_s": s["latency_p99_s"]}
    return out


def _grouped_cardinality_sweep(cards=(8, 256, 32768)) -> dict:
    """Grouped-aggregation throughput vs key cardinality on one device:
    low cardinalities run the dense accumulator-plane kernel, anything
    past DENSE_MAX_GROUPS the host sort/hash fallback — the strategy
    cliff the decision surface's grouped axis prices. (The 16-bit
    BitWeaving payload caps codes at 32767, so the high-cardinality
    point is 32768 groups rather than a full 64k.)"""
    rng = np.random.default_rng(7)
    n = 1 << 18
    res = {}
    for card in cards:
        t = Table(f"card{card}")
        t.add(BitPackedColumn.from_values("k", rng.integers(0, card, n),
                                          16))
        t.add(BitPackedColumn.from_values("v", rng.integers(0, 120, n),
                                          8))
        q = GroupBy("k", ("v",))
        relational.execute_grouped(q, t, mode="xla_ref")   # warm jit
        r, us = timed(lambda: relational.execute_grouped(
            q, t, mode="xla_ref"), repeat=3)
        res[card] = {
            "strategy": ("dense" if card <= relational.DENSE_MAX_GROUPS
                         else "fallback"),
            "groups": len(r["groups"]),
            "rows_per_s": round(n / (us / 1e6), 1),
            "groups_per_s": round(len(r["groups"]) / (us / 1e6), 1),
        }
    return res


def _rle_vs_fallback() -> tuple[dict, object]:
    """Count-only GroupBy over a *sorted* low-cardinality key, encoded:
    the fused RLE run-accumulation path (one batched launch, no scatter)
    against the host sort/hash fallback on the same bytes — the
    pre-grouped-data win the RLE strategy exists for. The fallback is
    forced by shrinking the dense cutoff, the documented strategy knob."""
    from repro.kernels.group_aggregate import ops as gops
    from repro.store import EncodedTable
    from repro.store.exec import execute_grouped_encoded
    default = obs_metrics.default_registry()
    rng = np.random.default_rng(11)
    n = 1 << 18
    t = Table("rle")
    t.add(BitPackedColumn.from_values(
        "k", np.sort(rng.integers(0, 16, n)), 8))
    t.add(BitPackedColumn.from_values("v", rng.integers(0, 120, n), 8))
    store = EncodedTable.from_table(t, chunk_rows=4096)
    assert any(c.encoding.value == "rle"
               for c in store.columns["k"].chunks), \
        "sorted low-cardinality key did not RLE-encode"
    q = GroupBy("k")                              # count-only: RLE-fused
    execute_grouped_encoded(q, store, mode="xla_ref")      # warm
    before = dict(default.launch_counts())
    want, rle_us = timed(lambda: execute_grouped_encoded(
        q, store, mode="xla_ref"), repeat=3)
    # timed() makes 1 warm + 3 timed calls after the snapshot
    launches = {k: (v - before.get(k, 0)) / 4
                for k, v in default.launch_counts().items()
                if v != before.get(k, 0)}
    saved = relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS
    try:
        relational.DENSE_MAX_GROUPS = gops.DENSE_MAX_GROUPS = 0
        execute_grouped_encoded(q, store, mode="xla_ref")  # warm numpy
        got, fb_us = timed(lambda: execute_grouped_encoded(
            q, store, mode="xla_ref"), repeat=3)
    finally:
        relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS = saved
    assert got == want, "RLE-fused and fallback disagree"
    return ({"rle_pregrouped_us": round(rle_us, 1),
             "hash_fallback_us": round(fb_us, 1),
             "speedup": round(fb_us / max(rle_us, 1e-9), 3),
             "rle_launches_per_query": launches.get(
                 "group_aggregate_rle", 0.0),
             "fallback_launches_during_rle": launches.get(
                 "group_aggregate_fallback", 0.0),
             "groups": len(want["groups"])}, want)


def rows():
    out = []
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    table = Table.synthetic("bench", 1 << 21, {"a": 8, "b": 8, "c": 16},
                            seed=0)
    st = ShardedTable.shard(table, mesh)
    q = Query(Pred("a", "lt", 64), aggregates=("b",))

    # compile the execution into st's jit cache with a throwaway engine so
    # eng's cumulative totals (model_check/provision below) measure hot
    # scans, not trace+compile
    warm = QueryEngine(st)
    warm.submit(q)
    warm.run()

    eng = QueryEngine(st)

    def once():
        eng.submit(q)
        return eng.run()[-1]

    res, us = timed(once, repeat=3)
    gbps = res.bytes_scanned / (us / 1e6) / 1e9
    out.append((f"queries/sharded_scan_agg_{n_dev}shards", us,
                f"{gbps:.3f}GBps,sel={res.selectivity:.3f}"))

    mc = eng.model_check()
    out.append(("queries/model_vs_measured", 0.0,
                f"{mc['attained_fraction']:.2e}of_{mc['system']}"))
    adv = eng.provision(sla_s=0.100)
    out.append(("queries/provision_100ms_sla", 0.0,
                f"{adv.design.compute_chips}chips_measured_calibrated"))

    sla = _attainment_vs_load(st, max(gbps, 1e-6))
    for load, s in sla.items():
        out.append((f"queries/sla_attainment/load={load:g}", 0.0,
                    f"{s['sla_attainment']:.2f}att,{s['rejected']}rej"))

    # --- grouped aggregation & hash join ---------------------------------
    gq = GroupBy("a", ("b",), where=Pred("c", "lt", 16000))
    warm_g = QueryEngine(st, mode="xla_ref")
    warm_g.submit(gq)
    warm_g.run()
    eng_g = QueryEngine(st, mode="xla_ref")

    def once_grouped():
        eng_g.submit(gq)
        return eng_g.run()[-1]

    res_g, us_g = timed(once_grouped, repeat=3)
    g_rows_per_s = table.num_rows / (us_g / 1e6)
    out.append((f"queries/grouped_sharded_{n_dev}shards", us_g,
                f"{len(res_g.aggregates['groups'])}groups,"
                f"{g_rows_per_s / 1e6:.1f}Mrows/s"))

    cards = _grouped_cardinality_sweep()
    for card, c in cards.items():
        out.append((f"queries/grouped_card={card}", 0.0,
                    f"{c['rows_per_s'] / 1e6:.1f}Mrows/s,"
                    f"{c['groups_per_s']:.0f}groups/s,{c['strategy']}"))

    rle, _ = _rle_vs_fallback()
    out.append(("queries/grouped_rle_vs_fallback", rle["rle_pregrouped_us"],
                f"{rle['speedup']}x_vs_fallback,"
                f"{rle['rle_launches_per_query']:g}launch/q"))

    append_trajectory(BENCH_PATH, {
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "n_shards": n_dev,
        "rows": table.num_rows,
        "rows_per_shard": st.rows_per_shard,
        "scan_agg_gbps": round(gbps, 4),
        "model_gbps": round(mc["model_gbps"], 1),
        "attained_fraction": mc["attained_fraction"],
        "provision_100ms_chips": adv.design.compute_chips,
        "sla_vs_load": {str(k): v for k, v in sla.items()},
        "grouped": {
            "sharded_us_per_query": round(us_g, 1),
            "sharded_rows_per_s": round(g_rows_per_s, 1),
            "sharded_groups": len(res_g.aggregates["groups"]),
            "cardinality": {str(k): v for k, v in cards.items()},
            **rle,
        },
        # flat engine: the digest carries snapshot scalars + launch
        # counts (no tier ledger), still diffable by the explainer
        "obs": obs_digest(eng),
    })
    return out
