"""Compressed-store benchmarks: ratio, effective bandwidth, hit-rate
delta, and the compression axis of the decision surface.

Four experiments over one compressible table (a realistic column mix:
sorted low-cardinality -> RLE, clustered 8/16-bit -> FOR, uniform ->
plain), all appended to BENCH_store.json at the repo root:

1. *Ratio*: per-column encoding choices and the table's logical/physical
   ratio (the selector's never-worse-than-plain guarantee in numbers).
2. *Scan-over-compressed bandwidth*: a zipf(1.1) multi-tenant trace
   replayed through the tiered QueryEngine over the plain and the
   encoded table — physical (compressed) vs logical (effective) GB/s and
   the trace's physical/logical byte fraction (the acceptance bar:
   <= 0.5x on this mix).
3. *Tier hit-rate delta*: the same trace, same absolute fast-tier
   capacity — compressed chunks are smaller, so the fast tier holds
   1/ratio more of the table and the hit rate strictly rises.
4. *Decision surface*: the 16 TiB paper workload at compression ratios
   (1.0, measured) plus `compression_crossover_ratio` at the 10 ms SLA —
   at what ratio does a software-compressed traditional system beat the
   die-stacked baseline?
5. *Batched launches*: kernel launches per query over the encoded table —
   the batched executor issues one launch per (column group, encoding),
   not one per chunk, so launches/query stays below the chunk count.
6. *Overlap*: the encoded trace replayed with the async prefetch
   pipeline across a fast-capacity sweep — modeled service is
   max(scan, stream) per stage instead of the sum, so blended GB/s
   climbs toward the fast tier's rate as the hit rate rises, with the
   pipeline's own traffic visible on the prefetch ledger.

Both replay timings are taken warm (one untimed pass first): the store
measures the scan path, not XLA compile amortization.

Set REPRO_STORE_BENCH_QUICK=1 for a smaller table/trace (CI smoke).
"""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import append_trajectory, obs_digest
from repro.db.columnar import BitPackedColumn, Table
from repro.energy.tco import (cheapest_architecture,
                              compression_crossover_ratio)
from repro.core.systems import TiB
from repro.obs import metrics as obs_metrics
from repro.query import physical
from repro.store import EncodedTable
from repro.store.exec import execute_encoded
from repro.tier import (Policy, TraceSpec, make_trace, paper_tiers,
                        replay_trace)

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_store.json"

SKEW = 1.1
FAST_FRACTION = 0.25
SLA_SLACK = 2.0
PAPER_DB = 16 * TiB
PAPER_ACCESSED = 0.20


def _sizes() -> tuple[int, int, int, int]:
    """(columns, rows, chunk_rows, n_queries); quick mode for CI/tests."""
    if os.environ.get("REPRO_STORE_BENCH_QUICK"):
        return 8, 4096, 512, 40
    return 16, 32768, 2048, 150


def compressible_table(n_cols: int, n_rows: int, seed: int = 0) -> Table:
    """The column mix compression was built for — mostly sorted
    low-cardinality (RLE) and clustered 8/16-bit (FOR at 4 bits), with
    one uniform full-payload column per eight that stays plain — a
    zipfian dashboard workload's shape (timestamps cluster, statuses
    repeat, only payload hashes resist)."""
    rng = np.random.default_rng(seed)
    t = Table("store")
    for i in range(n_cols):
        name = f"c{i:02d}"
        kind = i % 8
        if kind in (0, 4):       # sorted, 8 distinct values -> RLE
            t.add(BitPackedColumn.from_values(
                name, np.sort(rng.integers(0, 8, n_rows)), 8))
        elif kind in (1, 5, 7):  # 16-bit clustered, span 7 -> FOR, 4 bits
            t.add(BitPackedColumn.from_values(
                name, 9000 + rng.integers(0, 8, n_rows), 16))
        elif kind in (2, 6):     # 8-bit clustered, span 7 -> FOR, 4 bits
            t.add(BitPackedColumn.from_values(
                name, 40 + rng.integers(0, 8, n_rows), 8))
        else:                    # uniform full-payload -> plain
            t.add(BitPackedColumn.from_values(
                name, rng.integers(0, 128, n_rows), 8))
    return t


def _overlap_sweep(encoded, trace, tiers, chunk_rows):
    """Replay the encoded trace sync vs pipelined across a fast-capacity
    sweep. Returns the overlap record: per fast-fraction point, modeled
    service with and without prefetch, the blended GB/s trajectory
    toward the fast rate, and the prefetch ledger."""
    points = []
    for frac in (0.125, 0.25, 0.5):
        tw = paper_tiers(max(1, int(encoded.logical_nbytes * frac)),
                         fast_gbps=tiers.fast.gbps)
        pe_s, eng_s, _ = replay_trace(encoded, trace, tw, Policy.CACHE,
                                      chunk_rows=chunk_rows)
        # a double buffer needs ~one chunk of staging depth, not a cache's
        # worth: the reservation evicts residents, so an oversized buffer
        # trades hit rate for overlap and can lose on net
        buf = max(1, int(tw.fast.capacity / 16))
        pe_o, eng_o, _ = replay_trace(encoded, trace, tw, Policy.CACHE,
                                      chunk_rows=chunk_rows,
                                      prefetch_bytes=buf)
        ps = pe_o.stats()
        points.append({
            "fast_fraction": frac,
            "hit_rate": round(pe_o.hit_rate, 4),
            "sync_s": eng_s.seconds_total,
            "pipelined_s": eng_o.seconds_total,
            "sync_gbps": round(eng_s.summary()["measured_gbps"], 4),
            "pipelined_gbps": round(eng_o.summary()["measured_gbps"], 4),
            "staged_chunks": eng_o.prefetch.stats()["staged_chunks"],
            "prefetch_reserved_bytes": ps["prefetch_reserved_bytes"],
            "fast_capacity_bytes": int(tw.fast.capacity),
            "prefetch_streamed_bytes": ps["prefetch_streamed_bytes"],
            "prefetch_wasted_bytes": ps["prefetch_wasted_bytes"],
            "prefetch_j": pe_o.meter.prefetch_j,
        })
    return {"fast_gbps": tiers.fast.gbps,
            "capacity_gbps": tiers.capacity.gbps,
            "points": points}


def rows():
    n_cols, n_rows, chunk_rows, n_queries = _sizes()
    table = compressible_table(n_cols, n_rows, seed=0)
    t0 = time.perf_counter()
    encoded = EncodedTable.from_table(table, chunk_rows=chunk_rows)
    encode_us = (time.perf_counter() - t0) * 1e6
    ratio = encoded.ratio
    enc_counts: dict[str, int] = {}
    for col in encoded.columns.values():
        for k, v in col.encodings().items():
            enc_counts[k] = enc_counts.get(k, 0) + v

    # fixed *absolute* fast capacity: 25% of the PLAIN table for both runs
    tiers = paper_tiers(table.nbytes * FAST_FRACTION, fast_gbps=8.0)
    trace = make_trace(table, TraceSpec(n_queries=n_queries, skew=SKEW,
                                        seed=7))
    sla_s = SLA_SLACK * (table.nbytes / n_cols * 2) / tiers.fast.bandwidth

    # warm both execution paths first: one untimed pass compiles every
    # query shape, so the timed replays measure scans, not XLA compiles
    slices = physical.table_slices(table)
    for tq in trace:
        physical.finalize_aggs(physical.execute(
            tq.query.plan(), tq.query.aggregates, slices, mode="xla_ref"))
        execute_encoded(tq.query.plan(), tq.query.aggregates, encoded,
                        mode="xla_ref")

    t0 = time.perf_counter()
    pe_p, eng_p, att_p = replay_trace(table, trace, tiers, Policy.CACHE,
                                      sla_s=sla_s, chunk_rows=chunk_rows)
    plain_us = (time.perf_counter() - t0) / len(trace) * 1e6
    default = obs_metrics.default_registry()
    default.reset_launches()
    t0 = time.perf_counter()
    pe_e, eng_e, att_e = replay_trace(encoded, trace, tiers, Policy.CACHE,
                                      sla_s=sla_s, chunk_rows=chunk_rows)
    enc_us = (time.perf_counter() - t0) / len(trace) * 1e6
    launches = {
        "per_query": round(default.total_launches() / len(trace), 2),
        "n_chunks": encoded.n_chunks,
        "by_family": default.launch_counts(),
    }
    se, sp = eng_e.summary(), eng_p.summary()

    overlap = _overlap_sweep(encoded, trace, tiers, chunk_rows)

    surf_ratio1 = cheapest_architecture(
        PAPER_DB, PAPER_ACCESSED * PAPER_DB, 0.010, 1e6,
        compression_ratio=1.0)
    surf_measured = cheapest_architecture(
        PAPER_DB, PAPER_ACCESSED * PAPER_DB, 0.010, 1e6,
        compression_ratio=max(ratio, 1.0))
    crossover = compression_crossover_ratio(
        PAPER_DB, PAPER_ACCESSED * PAPER_DB, 0.010, 1e6)

    record = {
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "columns": n_cols, "rows": n_rows, "chunk_rows": chunk_rows,
        "n_queries": n_queries, "skew": SKEW,
        "ratio": round(ratio, 4),
        "encodings": enc_counts,
        "physical_bytes": encoded.nbytes,
        "logical_bytes": encoded.logical_nbytes,
        "trace": {
            "physical_bytes": se["bytes_scanned"],
            "logical_bytes": se["logical_bytes"],
            "physical_fraction": round(se["bytes_scanned"]
                                       / se["logical_bytes"], 4),
            "physical_gbps": round(se["measured_gbps"], 4),
            "effective_gbps": round(se["effective_gbps"], 4),
            "plain_gbps": round(sp["measured_gbps"], 4),
        },
        "tier": {
            "fast_fraction_of_plain": FAST_FRACTION,
            "plain_hit_rate": round(pe_p.hit_rate, 4),
            "encoded_hit_rate": round(pe_e.hit_rate, 4),
            "plain_attainment": att_p,
            "encoded_attainment": att_e,
        },
        "surface": {
            "verdict_ratio1_10ms": surf_ratio1["winner"],
            "verdict_measured_10ms": surf_measured["winner"],
            "crossover_ratio_10ms": crossover,
        },
        "launches": launches,
        "plain_us_per_query": round(plain_us, 1),
        "encoded_us_per_query": round(enc_us, 1),
        "overlap": overlap,
        # the encoded replay produces the gated physical_gbps headline;
        # its digest is the trace-diff explainer's baseline
        "obs": obs_digest(eng_e),
    }
    append_trajectory(BENCH_PATH, record)
    last = overlap["points"][-1]
    return [
        ("store/encode", encode_us,
         f"ratio={ratio:.2f}x,"
         + ",".join(f"{k}={v}" for k, v in sorted(enc_counts.items()))),
        ("store/trace/plain", plain_us,
         f"hit={pe_p.hit_rate:.2f},{sp['measured_gbps']:.2f}GBps,"
         f"att={att_p:.2f}"),
        ("store/trace/encoded", enc_us,
         f"hit={pe_e.hit_rate:.2f},"
         f"phys={se['measured_gbps']:.2f}GBps,"
         f"eff={se['effective_gbps']:.2f}GBps,att={att_e:.2f},"
         f"launches/q={launches['per_query']}"
         f"(chunks={launches['n_chunks']})"),
        ("store/overlap", 0.0,
         ",".join(f"f={p['fast_fraction']}:"
                  f"{p['sync_gbps']}->{p['pipelined_gbps']}GBps"
                  for p in overlap["points"])
         + f",staged={last['staged_chunks']}"),
        ("store/surface/10ms", 0.0,
         f"ratio1={surf_ratio1['winner']},"
         f"measured={surf_measured['winner']},"
         f"crossover={crossover and round(crossover, 2)}"),
    ]
