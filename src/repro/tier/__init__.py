"""Tiered-memory placement: the paper's die-stacked-vs-DDR question made
executable inside the query path.

- `tiers`: TierSpecs derived from core.systems Table-1 datasheets (fast
  HBM-like tier, DDR capacity tier, measured-rate calibration) and the
  fast-tier TieredBudget.
- `placement`: chunk-granular placement of a table's packed columns across
  the two tiers under STATIC / CACHE / MEMCACHE policies (Bakhshalipour et
  al.'s memory / cache / memcache designs), with host-side numpy state.
- `trace`: seeded zipfian multi-tenant query streams that exercise the
  hot/cold structure placement exists to exploit.

QueryEngine(table, tiered=PlacementEngine...) wires it into execution:
answers stay bit-exact, latency is charged per chunk at each tier's rate,
and admission feasibility uses the blended rate.
"""
from repro.tier.placement import Access, PlacementEngine, Policy
from repro.tier.prefetch import PrefetchPipeline, PrefetchPlan
from repro.tier.tiers import (TieredBudget, TierPair, TierSpec,
                              paper_tiers, table1_bandwidth_ratio,
                              tier_from_system)
from repro.tier.trace import (TracedQuery, TraceSpec, make_trace,
                              replay_trace, zipf_hit_curve, zipf_weights)

__all__ = [
    "Access", "PlacementEngine", "Policy",
    "PrefetchPipeline", "PrefetchPlan",
    "TierSpec", "TierPair", "TieredBudget", "paper_tiers",
    "tier_from_system", "table1_bandwidth_ratio",
    "TraceSpec", "TracedQuery", "make_trace", "replay_trace",
    "zipf_weights", "zipf_hit_curve",
]
