"""Tiered-memory placement engine (repro.tier) + query-path integration.

The load-bearing guarantees:
- placement NEVER changes query answers — all three policies are bit-exact
  vs the flat-memory engine on the same trace (only latency accounting
  moves);
- adaptive policies (CACHE, MEMCACHE) strictly beat STATIC pinning's
  hit-rate on a zipfian(1.1) trace with the fast tier at 25% of the table;
- the fast-tier budget is a hard invariant;
- advise_tier_split is consistent with the Eq. 4 roofline;
- benchmarks/run.py --only tier appends a record to BENCH_tier.json.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core.advisor import advise_tier_split
from repro.core.systems import DIE_STACKED, TRADITIONAL
from repro.db import Table
from repro.query import Pred, Query, QueryEngine
from repro.serve.sla import VirtualClock, blended_bps
from repro.tier import (PlacementEngine, Policy, TieredBudget, TraceSpec,
                        make_trace, paper_tiers, table1_bandwidth_ratio,
                        tier_from_system, zipf_hit_curve, zipf_weights)

N_COLS, N_ROWS = 16, 4096
FAST_FRACTION = 0.25
CHUNK_ROWS = 256


@pytest.fixture(scope="module")
def table():
    return Table.synthetic("tier", N_ROWS,
                           {f"c{i:02d}": 8 for i in range(N_COLS)}, seed=1)


@pytest.fixture(scope="module")
def tiers(table):
    return paper_tiers(table.nbytes * FAST_FRACTION, fast_gbps=10.0)


@pytest.fixture(scope="module")
def trace(table):
    return make_trace(table, TraceSpec(n_queries=120, skew=1.1, seed=3))


def run_trace(table, trace, policy, tiers):
    pe = PlacementEngine.for_table(table, tiers, policy,
                                   chunk_rows=CHUNK_ROWS)
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock())
    results = []
    for tq in trace:
        eng.submit(tq.query)
        results += eng.run()
        assert pe.budget.used <= pe.budget.fast_capacity + 1e-9
    return pe, eng, results


# --------------------------------------------------------------------------
# tiers: datasheet derivation + budget
# --------------------------------------------------------------------------
class TestTiers:
    def test_table1_bandwidth_ratio(self):
        # 256 GB/s HBM stack vs 4 x 25.6 GB/s DDR channels
        assert table1_bandwidth_ratio() == pytest.approx(2.5)

    def test_tier_from_system_die_stacked(self):
        t = tier_from_system(DIE_STACKED)
        assert t.bandwidth == DIE_STACKED.chip_bandwidth
        assert t.capacity == DIE_STACKED.chip_capacity
        assert t.energy_per_byte == pytest.approx(10.0 / (256 * 1e9))

    def test_paper_tiers_derates_capacity_by_ratio(self):
        p = paper_tiers(1 << 20, fast_gbps=10.0)
        assert p.fast.gbps == pytest.approx(10.0)
        assert p.capacity.gbps == pytest.approx(4.0)
        assert p.fast.capacity == 1 << 20

    def test_paper_tiers_datasheet_rates_without_measurement(self):
        p = paper_tiers(1 << 20)
        assert p.fast.bandwidth == DIE_STACKED.chip_bandwidth
        assert p.capacity.bandwidth == TRADITIONAL.chip_bandwidth

    def test_blended_is_harmonic(self):
        p = paper_tiers(1 << 20, fast_gbps=10.0)
        assert p.blended(1.0) == pytest.approx(10e9)
        assert p.blended(0.0) == pytest.approx(4e9)
        assert p.blended(0.5) == pytest.approx(1 / (.5 / 10e9 + .5 / 4e9))
        assert p.blended(0.5, chips=4) == pytest.approx(4 * p.blended(0.5))

    def test_service_time_adds_per_tier(self):
        p = paper_tiers(1 << 20, fast_gbps=10.0)
        assert p.service_s(10e9, 4e9) == pytest.approx(2.0)
        assert p.service_s(10e9, 4e9, chips=2) == pytest.approx(1.0)

    def test_as_system_is_eq4_bandwidth_bound(self):
        t = tier_from_system(DIE_STACKED)
        s = t.as_system()
        assert s.chip_peak_perf == pytest.approx(t.bandwidth)

    def test_budget_enforced(self):
        b = TieredBudget(100)
        b.alloc(60)
        assert not b.fits(50)
        with pytest.raises(ValueError, match="overflow"):
            b.alloc(50)
        b.free(30)
        b.alloc(50)
        assert b.remaining == pytest.approx(20)

    def test_budget_rejects_nonpositive_capacity(self):
        with pytest.raises(ValueError, match="positive"):
            TieredBudget(0)
        with pytest.raises(ValueError, match="positive"):
            paper_tiers(0)

    def test_blended_bps_guards_rates(self):
        with pytest.raises(ValueError, match="positive"):
            blended_bps(0.0, 4e9, 0.5)


# --------------------------------------------------------------------------
# trace: seeded zipfian streams
# --------------------------------------------------------------------------
class TestTrace:
    def test_zipf_weights_normalized_decreasing(self):
        w = zipf_weights(16, 1.1)
        assert w.sum() == pytest.approx(1.0)
        assert (np.diff(w) < 0).all()

    def test_zipf_hit_curve_endpoints_and_monotone(self):
        hit = zipf_hit_curve(16, 1.1)
        assert hit(0.0) == 0.0 and hit(1.0) == 1.0
        xs = np.linspace(0, 1, 21)
        ys = [hit(x) for x in xs]
        assert (np.diff(ys) >= -1e-12).all()
        assert hit(0.25) > 0.25        # the head is hotter than uniform

    def test_trace_is_deterministic(self, table):
        spec = TraceSpec(n_queries=30, skew=1.1, seed=9)
        assert make_trace(table, spec) == make_trace(table, spec)

    def test_trace_on_two_column_table(self):
        """Regression: the documented minimum of 2 columns must not crash
        the rank draw (no compound predicates are possible there)."""
        t = Table.synthetic("two", 256, {"a": 8, "b": 8}, seed=0)
        trace = make_trace(t, TraceSpec(n_queries=20, seed=0))
        assert len(trace) == 20
        assert all(len(tq.query.aggregates) == 1 for tq in trace)

    def test_trace_queries_bind_to_table(self, table, trace):
        for tq in trace:
            assert tq.query.aggregates[0] in table.columns
            assert 0 <= tq.tenant < 4


# --------------------------------------------------------------------------
# placement: the acceptance guarantees
# --------------------------------------------------------------------------
class TestPlacement:
    def test_chunk_universe_covers_table(self, table, tiers):
        pe = PlacementEngine.for_table(table, tiers, Policy.STATIC,
                                       chunk_rows=CHUNK_ROWS)
        assert pe.total_bytes == table.nbytes
        per_col = {}
        for (c, _), i in pe.index.items():
            per_col[c] = per_col.get(c, 0) + int(pe.nbytes[i])
        assert per_col == {n: col.nbytes
                           for n, col in table.columns.items()}

    def test_static_is_pinned_once(self, table, trace, tiers):
        pe, _, _ = run_trace(table, trace[:20], Policy.STATIC, tiers)
        before = pe.in_fast.copy()
        pe.on_access({cid: int(pe.nbytes[i])
                      for cid, i in list(pe.index.items())[:8]})
        np.testing.assert_array_equal(before, pe.in_fast)

    def test_all_policies_bit_exact_vs_flat(self, table, trace, tiers):
        """Placement never changes answers, only latency."""
        flat = QueryEngine(table, mode="xla_ref")
        flat_aggs = []
        for tq in trace[:30]:
            flat.submit(tq.query)
            flat_aggs.append(flat.run()[0].aggregates)
        for policy in Policy:
            _, _, results = run_trace(table, trace[:30], policy, tiers)
            assert [r.aggregates for r in results] == flat_aggs, policy

    def test_adaptive_beats_static_hit_rate(self, table, trace, tiers):
        """zipf(1.1) trace, fast tier at 25%: CACHE and MEMCACHE strictly
        exceed STATIC's byte-weighted hit-rate."""
        hit = {p: run_trace(table, trace, p, tiers)[0].hit_rate
               for p in Policy}
        assert hit[Policy.CACHE] > hit[Policy.STATIC]
        assert hit[Policy.MEMCACHE] > hit[Policy.STATIC]

    def test_hot_columns_hint_orders_static_pinning(self, table, tiers):
        pe = PlacementEngine.for_table(table, tiers, Policy.STATIC,
                                       chunk_rows=CHUNK_ROWS,
                                       hot_columns=("c07", "c03"))
        pinned = {c for (c, _), i in pe.index.items() if pe.in_fast[i]}
        assert {"c07", "c03"} <= pinned

    def test_unknown_chunk_raises(self, table, tiers):
        pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                       chunk_rows=CHUNK_ROWS)
        with pytest.raises(ValueError, match="unknown chunk"):
            pe.on_access({("nope", 0): 4})

    def test_energy_ledger_bit_compatible_with_old_scalar(self, table,
                                                          trace, tiers):
        """Satellite regression: the meter replaced the energy_j_total
        scalar, but stats()["energy_j"] must stay bit-compatible — the
        old per-access accumulation reproduced exactly by the sum of the
        ledger's per-charge memory lines."""
        pe, _, _ = run_trace(table, trace[:30], Policy.CACHE, tiers)
        ledger = pe.meter.charges
        assert len(ledger) == 30            # one charge per query
        old_style = 0.0                     # the pre-meter accumulation
        for c in ledger:
            old_style += tiers.energy_j(c.fast_bytes, c.capacity_bytes)
        assert pe.stats()["energy_j"] == old_style          # bitwise
        assert pe.energy_j_total == sum(c.memory_j for c in ledger)
        m = pe.meter.summary()                # the canonical breakdown
        assert m["fast_j"] + m["capacity_j"] == \
            pytest.approx(pe.stats()["energy_j"])
        assert m["compute_j"] == 0.0          # no compute_w: memory only
        assert m["total_j"] == pytest.approx(pe.stats()["energy_j"])

    def test_sharded_chunk_accounting(self, table, tiers):
        """ShardedTable reports device-resident (padding-included) chunk
        bytes and runs the tiered path end-to-end."""
        from repro.launch.mesh import make_mesh
        from repro.query import ShardedTable
        st = ShardedTable.shard(table, make_mesh((1,), ("data",)))
        q = Query(Pred("c00", "lt", 64), aggregates=("c01",))
        chunks = st.chunk_bytes(q.plan(), q.aggregates, CHUNK_ROWS)
        assert sum(chunks.values()) == sum(
            int(st.slices[c].words.size) * 4 for c in ("c00", "c01"))
        pe = PlacementEngine.for_table(st, tiers, Policy.CACHE,
                                       chunk_rows=CHUNK_ROWS)
        eng = QueryEngine(st, mode="xla_ref", tiered=pe,
                          clock=VirtualClock())
        eng.submit(q)
        res = eng.run()[0]
        assert res.tier["fast_bytes"] + res.tier["capacity_bytes"] \
            == sum(chunks.values())


# --------------------------------------------------------------------------
# engine integration: tiered latency model + blended admission
# --------------------------------------------------------------------------
class TestTieredEngine:
    def test_latency_is_modeled_service(self, table, tiers):
        pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                       chunk_rows=CHUNK_ROWS)
        clk = VirtualClock()
        eng = QueryEngine(table, mode="xla_ref", tiered=pe, clock=clk)
        q = Query(Pred("c00", "lt", 64), aggregates=("c01",))
        eng.submit(q)
        res = eng.run()[0]
        # cold cache: every byte at the capacity tier's rate
        want = res.bytes_scanned / tiers.capacity.bandwidth
        assert res.tier["service_s"] == pytest.approx(want)
        assert res.latency_s == pytest.approx(want)
        assert clk() == pytest.approx(want)
        assert eng.summary()["tier"]["policy"] == "cache"

    def test_admission_uses_blended_rate(self, table, tiers):
        pe = PlacementEngine.for_table(table, tiers, Policy.STATIC,
                                       chunk_rows=CHUNK_ROWS)
        clk = VirtualClock()
        eng = QueryEngine(table, mode="xla_ref", tiered=pe, clock=clk)
        assert eng.measured_bps == pytest.approx(
            tiers.blended(pe.resident_fast_fraction))
        q = Query(Pred("c00", "lt", 64), aggregates=("c01",))
        est = eng.bytes_scanned(q) / eng.measured_bps
        assert eng.submit(q, deadline=clk() + est * 0.5) is None  # rejected
        assert eng.submit(q, deadline=clk() + est * 2.0) is not None

    def test_sharded_admission_and_charge_share_one_byte_basis(self):
        """Regression: with shard-alignment padding (mixed code widths
        force lcm-aligned rows), the admission estimate, bytes_scanned,
        and the modeled service charge must all use the same padded
        device-resident bytes — a logical-bytes estimate would admit
        queries the padded charge then deterministically misses."""
        from repro.launch.mesh import make_mesh
        from repro.query import ShardedTable, physical
        t = Table.synthetic("pad", 100, {"a": 16, "b": 2}, seed=0)
        st = ShardedTable.shard(t, make_mesh((1,), ("data",)))
        tiers = paper_tiers(st.nbytes // 4, fast_gbps=10.0)
        pe = PlacementEngine.for_table(st, tiers, Policy.STATIC,
                                       chunk_rows=16)
        clk = VirtualClock()
        eng = QueryEngine(st, mode="xla_ref", tiered=pe, clock=clk)
        q = Query(Pred("a", "lt", 64), aggregates=("b",))
        padded = sum(st.chunk_bytes(q.plan(), q.aggregates, 16).values())
        logical = physical.referenced_bytes(q.plan(), q.aggregates,
                                            t.columns)
        assert padded > logical          # the padding is real in this case
        est = padded / eng.measured_bps
        assert eng.submit(q, deadline=clk() + est * 1.05) is not None
        res = eng.run()[0]
        assert res.bytes_scanned == padded
        assert res.tier["fast_bytes"] + res.tier["capacity_bytes"] == padded
        assert res.met                   # admitted estimate was honest

    def test_tiered_requires_advanceable_clock(self, table, tiers):
        """Modeled service on a wall clock would price admission and
        deadlines on incommensurate time axes — rejected at construction."""
        pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                       chunk_rows=CHUNK_ROWS)
        with pytest.raises(ValueError, match="VirtualClock"):
            QueryEngine(table, tiered=pe)

    def test_chunk_accesses_requires_tiered(self, table):
        eng = QueryEngine(table)
        with pytest.raises(ValueError, match="tiered"):
            eng.chunk_accesses(Query(Pred("c00", "lt", 4),
                                     aggregates=("c00",)))


# --------------------------------------------------------------------------
# advisor: fast-tier fraction search vs the Eq. 4 roofline
# --------------------------------------------------------------------------
class TestAdviseTierSplit:
    def adv(self, sla_s=0.010, fast_gbps=10.0, capacity_gbps=4.0):
        return advise_tier_split(
            1 << 30, 1 << 24, sla_s, hit_curve=zipf_hit_curve(16, 1.1),
            fast_gbps=fast_gbps, capacity_gbps=capacity_gbps)

    def test_consistent_with_eq4_roofline(self):
        adv = self.adv()
        # roofline from the DIE_STACKED datasheet, Eq. 4: min(compute 6*32,
        # bandwidth 256) = 192 GB/s — independent of the measured rates
        assert adv["roofline_gbps"] == pytest.approx(192.0)
        assert adv["fast_within_roofline"]
        assert all(r["within_roofline"] for r in adv["rows"])
        assert all(r["blended_gbps"] <= adv["roofline_gbps"] * (1 + 1e-9)
                   for r in adv["rows"])
        full = adv["rows"][-1]
        assert full["fast_fraction"] == 1.0
        assert full["blended_gbps"] == pytest.approx(10.0)

    def test_roofline_flags_mismeasured_fast_rate(self):
        """A fast rate above what Eq. 4 says the die-stacked chip can
        sustain (e.g. broken byte accounting) fails the cross-check."""
        adv = self.adv(fast_gbps=500.0)
        assert not adv["fast_within_roofline"]
        assert not adv["rows"][-1]["within_roofline"]

    def test_blended_monotone_in_fraction(self):
        gbps = [r["blended_gbps"] for r in self.adv()["rows"]]
        assert (np.diff(gbps) >= -1e-12).all()

    def test_best_is_minimal_feasible_fraction(self):
        adv = self.adv(sla_s=(1 << 24) / 4e9 * 10)   # generously feasible
        assert adv["best"] == adv["rows"][0]
        # bytes/query at the full fast rate takes (1<<24)/10e9 s; no
        # fraction can beat that
        assert self.adv(sla_s=(1 << 24) / 10e9 * 0.5)["best"] is None

    def test_measured_hit_points_interpolate(self):
        adv = advise_tier_split(
            1 << 30, 1 << 24, 0.010, hit_curve={0.25: 0.6, 0.5: 0.8},
            fast_gbps=10.0, capacity_gbps=4.0)
        r = next(r for r in adv["rows"]
                 if r["fast_fraction"] == pytest.approx(0.25))
        assert r["hit_rate"] == pytest.approx(0.6)

    def test_measured_endpoint_is_not_shadowed(self):
        """Regression: a measured point at full residency must win over
        the synthetic hit(1.0)=1.0 anchor, and the curve must clamp (not
        assume perfection) beyond the last measured point."""
        adv = advise_tier_split(
            1 << 30, 1 << 24, 0.010, hit_curve={1.0: 0.5},
            fast_gbps=10.0, capacity_gbps=4.0)
        assert adv["rows"][-1]["hit_rate"] == pytest.approx(0.5)
        half = next(r for r in adv["rows"]
                    if r["fast_fraction"] == pytest.approx(0.5))
        assert half["hit_rate"] == pytest.approx(0.25)
        with pytest.raises(ValueError, match="hit_curve"):
            advise_tier_split(1, 1, 0.1, hit_curve={1.5: 0.5},
                              fast_gbps=1.0, capacity_gbps=1.0)

    def test_guards_degenerate_inputs(self):
        with pytest.raises(ValueError, match="positive"):
            self.adv(fast_gbps=0.0)
        with pytest.raises(ValueError, match="sla_s"):
            self.adv(sla_s=0.0)
        with pytest.raises(ValueError, match="positive"):
            advise_tier_split(0, 1, 0.1, hit_curve=lambda f: f,
                              fast_gbps=1.0, capacity_gbps=1.0)


# --------------------------------------------------------------------------
# bench wiring: run.py --only tier appends to BENCH_tier.json
# --------------------------------------------------------------------------
def test_tier_bench_appends_record(tmp_path, monkeypatch, capsys):
    import benchmarks.run as bench_run
    import benchmarks.tier_bench as tier_bench
    monkeypatch.setenv("REPRO_TIER_BENCH_QUICK", "1")
    monkeypatch.setattr(tier_bench, "BENCH_PATH", tmp_path / "B.json")
    bench_run.main(["--only", "tier", "--json"])
    records = json.loads(capsys.readouterr().out)
    assert any(r["name"].startswith("tier/") for r in records)
    hist = json.loads((tmp_path / "B.json").read_text())
    assert len(hist) == 1
    rec = hist[0]
    assert set(rec["policies"]) == {"static", "cache", "memcache"}
    for pol in rec["policies"].values():
        for skew_row in pol.values():
            assert 0.0 <= skew_row["hit_rate"] <= 1.0
            assert math.isfinite(skew_row["blended_gbps"])


class TestPrefetch:
    """PrefetchPipeline: overlap = max per stage (not sum), bounded
    staging budget, in-flight chunks never double-projected, stall ->
    synchronous degradation. Hand-computed against paper_tiers at
    fast=10 GB/s, capacity=4 GB/s (the 2.5x Table-1 ratio)."""

    B = 1000                           # bytes per chunk
    FAST = 10e9
    CAP = 4e9

    def _pe(self, policy=Policy.STATIC, fast_capacity=2000, pin=(0,)):
        from repro.tier import PlacementEngine
        ids = [("c", 0), ("c", 1), ("c", 2)]
        return PlacementEngine(ids, [self.B] * 3,
                               paper_tiers(fast_capacity, fast_gbps=10.0),
                               policy, chunk_rows=256,
                               pin_order=list(pin))

    def test_service_is_max_per_stage_not_sum(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()                # only chunk 0 pinned fast
        pf = PrefetchPipeline(pe, self.B)
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        plan = pf.plan(chunks)
        # hit c0 scans 1e-7; first miss c1 reads sync 2.5e-7 (fill);
        # c2 streams under c1's scan: service = 1e-7 + max(2.5e-7,
        # 2.5e-7) + 1e-7 = 4.5e-7, vs sync 1e-7 + 5e-7 = 6e-7
        assert plan.sync_service_s == pytest.approx(6.0e-7)
        assert plan.service_s == pytest.approx(4.5e-7)
        assert plan.used and plan.staged_bytes == self.B
        assert plan.staged_cids == (("c", 2),)
        pf.close()

    def test_pipelined_never_worse_and_identical_placement(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        pf = PrefetchPipeline(pe, self.B)
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        plan = pf.plan(chunks)
        assert plan.service_s <= plan.sync_service_s
        before = pe.in_fast.copy()
        acc = pe.on_access(chunks, qid=1, tenant=0)       # unchanged path
        assert (pe.in_fast == before).all()               # STATIC anyway
        # the nominal charge is untouched by the pipeline
        assert acc.fast_bytes == self.B
        assert acc.capacity_bytes == 2 * self.B
        line = pf.finish(plan, qid=1, tenant=0)
        assert line.kind == "prefetch"
        assert line.fast_bytes == self.B and line.capacity_bytes == 0
        assert pe.meter.prefetch_j == line.total_j
        # prefetch traffic never pollutes the demand (hit-rate) totals
        assert pe.fast_bytes_total == self.B
        assert pe.capacity_bytes_total == 2 * self.B
        pf.close()

    def test_inflight_projects_as_fast_exactly_once(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        pf = PrefetchPipeline(pe, self.B)
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        plan = pf.plan(chunks)
        assert pe.project(chunks).fast_bytes == self.B
        pf.begin(plan, chunks)
        # c2 is streaming: admission now projects it fast, not a second
        # capacity read
        assert pe.project(chunks).fast_bytes == 2 * self.B
        pf.finish(plan)
        assert pe.project(chunks).fast_bytes == self.B
        pf.close()

    def test_chunk_larger_than_buffer_never_staged(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        pf = PrefetchPipeline(pe, self.B // 2)
        plan = pf.plan({("c", 0): self.B, ("c", 1): self.B,
                        ("c", 2): self.B})
        assert not plan.used
        assert plan.service_s == pytest.approx(plan.sync_service_s)
        pf.close()

    def test_memcache_first_touch_not_staged(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe(policy=Policy.MEMCACHE, pin=())
        pf = PrefetchPipeline(pe, self.B)
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        assert not pf.plan(chunks).used    # no frequency evidence yet
        pe.on_access(chunks)               # first touch builds evidence
        pe.demoted = False
        plan = pf.plan(chunks)
        assert plan.used                   # admission bar now cleared
        pf.close()

    def test_demoted_fast_tier_stages_nothing(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        pf = PrefetchPipeline(pe, self.B)
        pe.demoted = True
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        plan = pf.plan(chunks)
        assert not plan.used
        # everything reads from the durable capacity tier
        assert plan.sync_service_s == pytest.approx(3 * self.B / self.CAP)
        assert plan.service_s == pytest.approx(plan.sync_service_s)
        pf.close()

    def test_stall_degrades_to_sync_and_reports_waste(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        pf = PrefetchPipeline(pe, self.B)
        chunks = {("c", 0): self.B, ("c", 1): self.B, ("c", 2): self.B}
        plan = pf.plan(chunks, stalled=lambda cid: cid == ("c", 2))
        # the stalled stream re-reads synchronously: overlap gone
        assert plan.service_s == pytest.approx(plan.sync_service_s)
        assert plan.stalled_bytes == self.B
        assert plan.staged_bytes == 0      # nothing usefully streamed
        line = pf.finish(plan, qid=9)
        assert line is None                # stalled waste is the caller's
        assert pf.stats()["stalled_chunks"] == 1
        pf.close()

    def test_reservation_bounded_and_restored(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe()
        with pytest.raises(ValueError, match="exceeds fast tier"):
            PrefetchPipeline(pe, 10_000)
        pf = PrefetchPipeline(pe, self.B)
        assert pe.prefetch_reserved_bytes == self.B
        assert pe.stats()["prefetch_reserved_bytes"] == self.B
        pf.close()
        assert pe.prefetch_reserved_bytes == 0

    def test_reservation_evicts_lru_when_tier_full(self):
        from repro.tier import PrefetchPipeline
        pe = self._pe(policy=Policy.CACHE, fast_capacity=2000, pin=())
        chunks = {("c", 0): self.B, ("c", 1): self.B}
        pe.on_access(chunks)               # CACHE promotes both; tier full
        assert pe.in_fast.sum() == 2
        pf = PrefetchPipeline(pe, self.B)  # must evict the LRU resident
        assert pe.in_fast.sum() == 1
        assert int(pe.budget.remaining) == 0
        pf.close()

    def test_engine_requires_matching_placement(self):
        from repro.query import QueryEngine
        from repro.serve.sla import VirtualClock
        from repro.tier import PrefetchPipeline
        pe, other = self._pe(), self._pe()
        pf = PrefetchPipeline(other, self.B)
        with pytest.raises(ValueError, match="different PlacementEngine"):
            QueryEngine(Table.synthetic("t", 256, {"a": 8, "b": 8},
                                        seed=0),
                        tiered=pe, clock=VirtualClock(), prefetch=pf)
        pf.close()
