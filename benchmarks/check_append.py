"""Parameterized BENCH_*.json append checks for CI smoke steps.

Every benchmark module appends one record per run to its trajectory file;
the CI smoke steps used to each carry a copy-pasted inline Python block
asserting the append happened and the record is sane. This script is that
check, once, parameterized by bench name:

    python benchmarks/check_append.py tier energy store

Each check asserts (a) the trajectory exists and is a non-empty list and
(b) the latest record carries the bench's invariants — the same
assertions the inline blocks made, plus the new store contract.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _load(name: str) -> tuple[list, dict]:
    path = ROOT / f"BENCH_{name}.json"
    assert path.exists(), f"{path.name} missing: the {name} bench did " \
        f"not append a record"
    hist = json.loads(path.read_text())
    assert isinstance(hist, list) and hist, \
        f"{path.name} holds no records"
    rec = hist[-1]
    # the obs digest is additive — old rows without one still load — but
    # when present it must carry the diffable schema the explainer reads
    obs = rec.get("obs")
    if obs is not None:
        assert isinstance(obs.get("v"), int), obs
        assert isinstance(obs.get("snapshot"), dict), obs
        assert isinstance(obs.get("categories"), dict), obs
        assert isinstance(obs.get("queries"), int), obs
    return hist, rec


def check_queries() -> str:
    hist, rec = _load("queries")
    assert rec["scan_agg_gbps"] > 0 and rec["n_shards"] >= 1, rec
    assert rec["sla_vs_load"], rec
    g = rec["grouped"]
    assert g["sharded_rows_per_s"] > 0 and g["sharded_groups"] > 0, g
    cards = g["cardinality"]
    assert len(cards) >= 3, cards
    strategies = {c["strategy"] for c in cards.values()}
    assert strategies == {"dense", "fallback"}, \
        f"cardinality sweep should cross the dense cutoff: {cards}"
    assert g["rle_pregrouped_us"] < g["hash_fallback_us"], \
        (f"fused RLE run-accumulation did not beat the hash fallback on a "
         f"sorted low-cardinality key: {g['rle_pregrouped_us']} vs "
         f"{g['hash_fallback_us']} us")
    assert g["rle_launches_per_query"] == 1, \
        f"count-only RLE rollup should be ONE batched launch: {g}"
    assert g["fallback_launches_during_rle"] == 0, \
        f"RLE path fell back to the host sort/hash: {g}"
    return (f"{len(hist)} record(s), last: {rec['n_shards']} shards, "
            f"{rec['scan_agg_gbps']} GB/s, grouped rle "
            f"{g['speedup']}x vs fallback")


def check_tier() -> str:
    hist, rec = _load("tier")
    assert set(rec["policies"]) == {"static", "cache", "memcache"}, rec
    return f"{len(hist)} record(s), last: " + str(
        {p: v[str(1.1)] for p, v in rec["policies"].items()})


def check_energy() -> str:
    hist, rec = _load("energy")
    capped = rec["replay"]["capped"]
    assert capped["budget_utilization"] <= 1 + 1e-9, capped
    assert any(rec["surface"]["winners"].values()), rec["surface"]
    return f"{len(hist)} record(s), capped replay: {capped}"


def check_store() -> str:
    hist, rec = _load("store")
    assert rec["ratio"] > 1.0, rec
    tr = rec["trace"]
    assert tr["physical_bytes"] <= 0.5 * tr["logical_bytes"], \
        f"compressed trace streams more than half the logical bytes: {tr}"
    tier = rec["tier"]
    assert tier["encoded_hit_rate"] > tier["plain_hit_rate"], \
        f"compression did not improve the fast-tier hit rate: {tier}"
    surf = rec["surface"]
    assert surf["verdict_ratio1_10ms"] == "die-stacked", surf
    assert surf["crossover_ratio_10ms"] is not None, surf
    la = rec["launches"]
    assert la["per_query"] < la["n_chunks"], \
        (f"batched execution should launch fewer kernels per query than "
         f"the table has chunks: {la}")
    assert rec["encoded_us_per_query"] <= rec["plain_us_per_query"], \
        (f"warm encoded replay slower than plain: "
         f"{rec['encoded_us_per_query']} vs {rec['plain_us_per_query']} us")
    ov = rec["overlap"]
    pipelined = [p["pipelined_gbps"] for p in ov["points"]]
    assert pipelined == sorted(pipelined), \
        f"blended GB/s should rise with the fast fraction: {ov['points']}"
    for p in ov["points"]:
        assert p["pipelined_s"] <= p["sync_s"] * (1 + 1e-9), \
            f"prefetch overlap made the replay slower: {p}"
        assert p["prefetch_reserved_bytes"] <= p["fast_capacity_bytes"], \
            f"staging buffer exceeds the fast tier: {p}"
        assert p["staged_chunks"] > 0, f"pipeline never staged a chunk: {p}"
    return (f"{len(hist)} record(s), ratio={rec['ratio']}, "
            f"hit {tier['plain_hit_rate']}->{tier['encoded_hit_rate']}, "
            f"launches/q={la['per_query']}(chunks={la['n_chunks']}), "
            f"overlap {ov['points'][0]['sync_gbps']}->"
            f"{ov['points'][-1]['pipelined_gbps']} GB/s, "
            f"crossover@10ms={surf['crossover_ratio_10ms']}")


def check_resilience() -> str:
    hist, rec = _load("resilience")
    sweep = rec["sweep"]
    recovered = [k for k in next(iter(sweep.values())) if k != "norecover"]
    assert recovered, rec
    for rate, per in sweep.items():
        base = per["norecover"]["attainment"]
        if float(rate) == 0.0:
            # a fault-free chaos run is the plain tiered path: recovery
            # machinery idle, attainment identical
            assert all(per[p]["attainment"] == base for p in recovered), per
            continue
        for p in recovered:
            assert per[p]["attainment"] > base, \
                (f"recovery policy {p!r} did not beat the no-recovery "
                 f"baseline at fault rate {rate}: {per}")
            assert per[p]["degraded"] == 0, per
            assert per[p]["mttr_ms"] is not None, per
            assert per[p]["recovery_bytes"] > 0, per
    worst = max((r for r in sweep if float(r) > 0), key=float)
    per = sweep[worst]
    return (f"{len(hist)} record(s), rate={worst}: "
            + ", ".join(f"{p}={per[p]['attainment']}"
                        for p in ["norecover"] + recovered))


CHECKS = {
    "queries": check_queries,
    "tier": check_tier,
    "energy": check_energy,
    "store": check_store,
    "resilience": check_resilience,
}


def main(argv=None) -> None:
    names = (argv if argv is not None else sys.argv[1:]) or []
    unknown = [n for n in names if n not in CHECKS]
    if not names or unknown:
        raise SystemExit(f"usage: check_append.py <bench>... ; benches: "
                         f"{sorted(CHECKS)}"
                         + (f" (unknown: {unknown})" if unknown else ""))
    for n in names:
        print(f"BENCH_{n}.json: {CHECKS[n]()}")


if __name__ == "__main__":
    main()
