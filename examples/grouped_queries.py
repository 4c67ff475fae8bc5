"""Grouped queries walkthrough: GROUP BY and joins over compressed data.

The paper's "big data workloads" are not single-column scans — they are
grouped aggregation and joins. This walkthrough runs both through the
`kernels/group_aggregate` family and shows where the compressed store
changes the execution strategy, not just the byte count:

- an RLE run over a sorted low-cardinality group key is *pre-grouped*:
  a run of length n contributes n to one group's count in registers —
  no scatter, ONE batched kernel launch for the whole table (the launch
  counters prove it);
- a FOR frame bounds the key range, so a dense int32 accumulator plane
  replaces the hash table while the domain stays under
  `DENSE_MAX_GROUPS`;
- past the cutoff, chunks take the host sort/hash fallback — the
  strategy cliff the decision surface's grouped-mix axis prices.

Every path lands in one exact host-partial algebra, so results are
bit-identical to a numpy oracle whichever strategy ran.

Run: PYTHONPATH=src:. python examples/grouped_queries.py
"""
import time

import numpy as np

from repro.db.columnar import BitPackedColumn, Table
from repro.energy.tco import decision_surface
from repro.obs import metrics as obs_metrics
from repro.query import GroupBy, HashJoin, Pred, QueryEngine, relational
from repro.store import EncodedTable
from repro.store.exec import execute_grouped_encoded

N_ROWS, CHUNK_ROWS = 1 << 17, 4096


def main():
    rng = np.random.default_rng(0)
    t = Table("facts")
    t.add(BitPackedColumn.from_values(          # sorted low-card -> RLE
        "region", np.sort(rng.integers(0, 12, N_ROWS)), 8))
    t.add(BitPackedColumn.from_values(          # clustered -> FOR
        "day", 40 + rng.integers(0, 8, N_ROWS), 8))
    t.add(BitPackedColumn.from_values(          # uniform value column
        "sales", rng.integers(0, 120, N_ROWS), 8))
    store = EncodedTable.from_table(t, chunk_rows=CHUNK_ROWS)

    # --- GROUP BY through the engine, bit-exact vs the numpy oracle ----
    q = GroupBy("region", ("sales",), where=Pred("day", "lt", 45))
    eng = QueryEngine(store)
    eng.submit(q)
    (res,) = eng.run()
    assert res.aggregates == relational.execute_grouped_oracle(q, t)
    print(f"GROUP BY region: {len(res.aggregates['groups'])} groups over "
          f"{res.count} selected rows (physical {res.bytes_scanned} B of "
          f"{res.logical_bytes} B logical)")
    top = max(res.aggregates["groups"].items(),
              key=lambda kv: kv[1]["sums"]["sales"])
    print(f"  busiest region {top[0]}: count={top[1]['count']} "
          f"sum(sales)={top[1]['sums']['sales']}\n")

    # --- the RLE pre-grouped path: one launch, no scatter --------------
    hist = GroupBy("region")                    # count-only histogram
    execute_grouped_encoded(hist, store, mode="xla_ref")       # warm
    default = obs_metrics.default_registry()
    before = dict(default.launch_counts())
    t0 = time.perf_counter()
    got = execute_grouped_encoded(hist, store, mode="xla_ref")
    rle_s = time.perf_counter() - t0
    launches = {k: v - before.get(k, 0)
                for k, v in default.launch_counts().items()
                if v != before.get(k, 0)}
    print(f"count-only histogram on the RLE key: launches={launches} "
          f"({store.n_chunks} chunks) in {rle_s * 1e3:.1f} ms")

    # force the sort/hash fallback on the same bytes (the strategy knob)
    from repro.kernels.group_aggregate import ops as gops
    saved = relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS
    try:
        relational.DENSE_MAX_GROUPS = gops.DENSE_MAX_GROUPS = 0
        t0 = time.perf_counter()
        fb = execute_grouped_encoded(hist, store, mode="xla_ref")
        fb_s = time.perf_counter() - t0
    finally:
        relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS = saved
    assert fb == got
    print(f"same query, forced sort/hash fallback: {fb_s * 1e3:.1f} ms "
          f"-> pre-grouped RLE is {fb_s / rle_s:.1f}x faster\n")

    # --- hash join: build side broadcast, probe keys clipped -----------
    dim = Table("dim_region")
    dim.add(BitPackedColumn.from_values(
        "region", np.array([0, 3, 7, 11]), 8))
    j = HashJoin(dim, "region", "region", aggs=("sales",))
    jres = execute_grouped_encoded(j, store)
    assert jres == relational.execute_grouped_oracle(j, t)
    print(f"join vs 4-row dim table: groups={sorted(jres['groups'])} "
          f"({jres['count']} rows matched)")

    # --- the grouped-mix axis of the decision surface ------------------
    surf = decision_surface(
        16 * (1 << 40), 1 << 30, grouped_mixes=(0.0, 0.5),
        grouped_bytes_per_query=3 * (1 << 30))
    for mix in (0.0, 0.5):
        cells = [c for c in surf["cells"] if c["grouped_mix"] == mix
                 and c["winner"] is not None]
        wins = {}
        for c in cells:
            wins[c["winner"]] = wins.get(c["winner"], 0) + 1
        print(f"decision surface @ grouped_mix={mix}: winners {wins}")


if __name__ == "__main__":
    main()
