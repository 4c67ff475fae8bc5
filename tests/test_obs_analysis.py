"""Observability analysis: critical paths, SLO burn rates, trace diffs.

Pins down PR 10's contracts:

- `critical_path`/`verify` reconcile exactly on every execution path —
  plain tiered, encoded, sharded, grouped, prefetch overlap, chaos —
  and flag tampered span trees instead of mis-attributing them;
- same-seed chaos replays emit **byte-identical** SLO alert streams;
  burn-rate rules fire on sustained error burns and resolve when the
  short window goes quiet, at computed (never accumulated) timestamps;
- `RingSeries` ring-buffer semantics, `latency_percentile` and
  `Histogram` edge cases (empty / single / all-equal);
- the Chrome trace export matches its golden waterfall, serializes with
  sorted keys, and keeps X events ts-monotone per (pid, tid) lane;
- `diff_digests` names the dominant regressing span category, and
  `check_regress.py` prints it when the gate trips;
- `whatif_fast_fraction` stays consistent with the advise_tier_split
  decision surface.
"""
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core.advisor import whatif_fast_fraction
from repro.db import Table
from repro.launch.mesh import make_mesh
from repro.obs import (ConservationError, RingSeries, SLOMonitor, Tracer,
                       attribute, chrome_trace_json, critical_path,
                       diff_digests, diff_traces, digest, verify)
from repro.obs.critical_path import CATEGORIES
from repro.obs.export import waterfall_query
from repro.obs.metrics import Histogram
from repro.obs.slo import BurnRateRule, default_rules
from repro.query import Query, QueryEngine, ShardedTable
from repro.query.plan import GroupBy, Pred
from repro.resilience import (ChaosHarness, ChunkGuard, FaultSpec,
                              RetryPolicy)
from repro.serve.sla import VirtualClock, latency_percentile
from repro.store import EncodedTable
from repro.tier import (PlacementEngine, Policy, TraceSpec, make_trace,
                        paper_tiers, replay_trace, zipf_hit_curve)
from repro.tier.prefetch import PrefetchPipeline

N_ROWS, CHUNK_ROWS = 4096, 512
GOLDEN = Path(__file__).parent / "golden"


def make_table(seed=1, n_cols=8):
    return Table.synthetic("obs", N_ROWS,
                           {f"c{i:02d}": 8 for i in range(n_cols)},
                           seed=seed)


def tiered_engine(table, *, policy=Policy.CACHE, fast_frac=0.5, **kw):
    from repro.energy.meter import EnergyMeter
    tiers = paper_tiers(table.nbytes * fast_frac, fast_gbps=10.0)
    pe = PlacementEngine.for_table(table, tiers, policy,
                                   chunk_rows=CHUNK_ROWS,
                                   meter=EnergyMeter(tiers))
    tracer = Tracer()
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock(), tracer=tracer, **kw)
    return eng, pe, tracer


def run_queries(eng, n=4):
    for _ in range(n):
        q = Query(Pred("c00", "ge", 10), aggregates=("c01",))
        assert eng.submit(q, deadline=eng.clock() + 100.0) is not None
        eng.run()


def monitored_chaos_run(n_queries=40):
    """Seeded fault replay with monitor + tracer; fresh state per call."""
    from repro.query import physical
    table = Table.synthetic("events", 8192,
                            {f"c{i:02d}": 8 for i in range(8)}, seed=0)
    enc = EncodedTable.from_table(table, chunk_rows=CHUNK_ROWS)
    tiers = paper_tiers(table.nbytes * 0.25, fast_gbps=0.016)
    qtrace = make_trace(table, TraceSpec(n_queries=n_queries, skew=1.2,
                                         seed=11))
    clean_s = (enc.nbytes
               / sum(len(c.chunks) for c in enc.columns.values())
               / tiers.fast.bandwidth)
    chaos = ChaosHarness(
        FaultSpec(seed=42, stall_rate=0.1, corrupt_rate=0.05),
        guard=ChunkGuard(enc),
        retry=RetryPolicy(timeout_s=2.0 * clean_s,
                          backoff_s=0.5 * clean_s, max_retries=2))
    chaos.inject_corruption()
    bytes_typ = sum(
        physical.referenced_bytes(tq.query.plan(), tq.query.aggregates,
                                  enc.columns)
        for tq in qtrace) / len(qtrace)
    sla_s = 2.5 * bytes_typ / tiers.fast.bandwidth
    tracer = Tracer()
    monitor = SLOMonitor(target=0.9, cadence_s=sla_s / 2)
    pe, eng, att = replay_trace(
        enc, qtrace, tiers, Policy.CACHE, sla_s=sla_s,
        chunk_rows=CHUNK_ROWS, chaos=chaos,
        prefetch_bytes=table.nbytes // 16, tracer=tracer, monitor=monitor)
    monitor.tick(eng.clock() + monitor.max_window_s)
    return monitor, tracer, pe, eng, att


# --------------------------------------------------------------------------
# critical-path reconciliation across execution paths
# --------------------------------------------------------------------------

def _assert_paths_close(attr, tracer):
    """Every path tiles [submitted_at, t_end] and splits bytes exactly."""
    assert attr.ok, attr.problems
    for cp, qt in zip(attr.paths, tracer.queries):
        assert cp.ok, cp.problems
        assert set(cp.seconds_by_category()) <= set(CATEGORIES)
        path_s = sum(seg.dur_s for seg in cp.segments)
        assert path_s == pytest.approx(qt.t_end - qt.submitted_at,
                                       rel=1e-9, abs=1e-12)
        got = dict(cp.on_path_bytes)
        for key, n in cp.off_path_bytes.items():
            got[key] = got.get(key, 0) + n
        assert got == qt.bytes_by_ledger()    # exact int equality


def test_critical_path_plain():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng)
    attr = verify(tracer, pe.meter)
    _assert_paths_close(attr, tracer)
    assert attr.queries == 4 and attr.missed == 0
    for cp in attr.paths:
        assert any(seg.category == "queue" for seg in cp.segments)
    # no pipeline, no chaos, no cap: only queue + tier reads on the path
    assert set(attr.seconds) <= {"queue", "fast_read", "capacity_read"}


def test_critical_path_encoded():
    enc = EncodedTable.from_table(make_table(), chunk_rows=CHUNK_ROWS)
    eng, pe, tracer = tiered_engine(enc)
    run_queries(eng)
    _assert_paths_close(verify(tracer, pe.meter), tracer)


def test_critical_path_sharded():
    st = ShardedTable.shard(make_table(), make_mesh((1,), ("data",)))
    eng, pe, tracer = tiered_engine(st)
    run_queries(eng)
    _assert_paths_close(verify(tracer, pe.meter), tracer)


def test_critical_path_grouped_shape():
    enc = EncodedTable.from_table(make_table(), chunk_rows=CHUNK_ROWS)
    eng, pe, tracer = tiered_engine(enc)
    q = GroupBy(keys=("c00",), aggs=("c01",), where=Pred("c02", "ge", 4))
    assert eng.submit(q, deadline=eng.clock() + 100.0) is not None
    eng.run()
    attr = verify(tracer, pe.meter)
    _assert_paths_close(attr, tracer)
    # the engine stamped the query shape for per-shape diffs
    assert tracer.queries[0].shape == "grouped"
    assert all(shape == "grouped" for shape, _ in attr.shape_seconds)


def test_critical_path_prefetch():
    table = make_table()
    from repro.energy.meter import EnergyMeter
    tiers = paper_tiers(table.nbytes * 0.25, fast_gbps=10.0)
    pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                   chunk_rows=CHUNK_ROWS,
                                   meter=EnergyMeter(tiers))
    pf = PrefetchPipeline(pe, table.nbytes // 8)
    tracer = Tracer()
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock(), prefetch=pf, tracer=tracer)
    run_queries(eng, n=6)
    attr = verify(tracer, pe.meter)
    _assert_paths_close(attr, tracer)
    # overlap means the path is the max branch per window, never the sum:
    # path time <= the sum of all scan+stream span durations
    for cp, qt in zip(attr.paths, tracer.queries):
        span_sum = sum(sp.dur_s for sp in qt.spans
                       if sp.kind in ("read", "prefetch_read"))
        assert sum(s.dur_s for s in cp.segments) <= span_sum + 1e-12


def test_critical_path_chaos():
    monitor, tracer, pe, eng, att = monitored_chaos_run()
    attr = verify(tracer, pe.meter)
    _assert_paths_close(attr, tracer)
    assert attr.seconds.get("recovery", 0.0) > 0.0
    # under the tight SLA hopeless queries are *rejected at admission*
    # (burning SLO budget — the monitor saw errors) rather than served
    # late, so served queries can all meet while attainment drops
    assert len(eng.queue.rejected) > 0 and att < 1.0
    assert monitor.tenants and any(
        led.errors for led in monitor.tenants.values())
    fr = attr.fractions()
    assert abs(sum(fr.values()) - 1.0) < 1e-9
    assert "SLA-missed" in attr.render()


def test_critical_path_flags_tampered_trace():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng, n=2)
    verify(tracer, pe.meter)
    qt = tracer.queries[0]
    qt.spans[:] = [sp for sp in qt.spans if sp.kind != "admission"]
    cp = critical_path(qt)
    assert not cp.ok
    assert any("admission" in p for p in cp.problems)
    with pytest.raises(ConservationError, match="admission"):
        verify(tracer, pe.meter)


def test_critical_path_unserved_query():
    qt = SimpleNamespace(qid=7, tenant=0, shape="scan", met=None,
                         degraded=False, submitted_at=1.0, t_start=None,
                         t_end=None, spans=[], reads=[])
    cp = critical_path(qt)
    assert not cp.ok and cp.total_s == 0.0
    assert any("never served" in p for p in cp.problems)


# --------------------------------------------------------------------------
# SLO burn-rate monitoring
# --------------------------------------------------------------------------

def test_slo_alerts_byte_identical_across_replays():
    m1 = monitored_chaos_run()[0]
    m2 = monitored_chaos_run()[0]
    assert m1.alerts_json() == m2.alerts_json()
    assert len(m1.alerts) > 0, "chaos run burned no budget — dead test"
    # computed timestamps: every alert sits exactly on a cadence tick
    for a in m1.alerts:
        k = round(a.t / m1.cadence_s)
        assert a.t == k * m1.cadence_s


def test_slo_fire_and_resolve():
    mon = SLOMonitor(target=0.9, cadence_s=1.0)
    mon.tick(0.0)
    bad = SimpleNamespace(met=False)
    good = SimpleNamespace(met=True)
    mon.observe(bad)
    alerts = mon.tick(1.0)
    # 100% errors / 10% budget = burn 10 >= both thresholds: both fire
    assert [a.kind for a in alerts] == ["fire", "fire"]
    assert {a.rule for a in alerts} == {"fast_burn", "slow_burn"}
    assert alerts[0].t == 1.0
    assert alerts[0].burn_long == pytest.approx(10.0)
    for _ in range(40):
        mon.observe(good)
    alerts = mon.tick(3.0)
    # the short windows go quiet -> both rules resolve
    assert [a.kind for a in alerts] == ["resolve", "resolve"]
    assert mon.summary()["firing"] == []
    budget = mon.error_budget(0)
    assert budget["events"] == 41 and budget["errors"] == 1


def test_slo_rejection_burns_budget():
    mon = SLOMonitor(target=0.9, cadence_s=1.0)
    mon.observe_rejected(tenant=3)
    b = mon.error_budget(3)
    assert b["events"] == 1 and b["errors"] == 1
    assert b["remaining_fraction"] < 0       # over budget
    assert mon.error_budget(99)["remaining_fraction"] == 1.0


def test_slo_engine_rejection_and_gauges():
    table = make_table()
    mon = SLOMonitor(target=0.9, cadence_s=1e-5)
    eng, pe, tracer = tiered_engine(table, monitor=mon)
    run_queries(eng, n=4)
    # an infeasible deadline is rejected at admission and lands in the
    # tenant ledger automatically
    q = Query(Pred("c00", "ge", 10), aggregates=("c01",))
    assert eng.submit(q, deadline=eng.clock()) is None
    led = mon.tenants[0]
    assert led.events == 5 and led.errors == 1
    # engine gauges sampled on the modeled clock
    assert len(mon.series["hit_rate"]) > 0
    assert mon.series["blended_gbps"].last > 0
    assert "watts" not in mon.series         # no power cap wired
    assert eng.summary()["slo"]["ticks"] == mon._next_tick


def test_slo_monitor_requires_tiered():
    with pytest.raises(ValueError, match="tiered"):
        QueryEngine(make_table(), mode="xla_ref", monitor=SLOMonitor())


def test_slo_validation():
    with pytest.raises(ValueError, match="target"):
        SLOMonitor(target=1.0)
    with pytest.raises(ValueError, match="cadence"):
        SLOMonitor(cadence_s=0.0)
    with pytest.raises(ValueError, match="short window"):
        BurnRateRule("bad", long_s=1.0, short_s=2.0, threshold=1.0)
    with pytest.raises(ValueError, match="positive"):
        BurnRateRule("bad", long_s=1.0, short_s=0.5, threshold=0.0)
    fast, slow = default_rules(0.01)
    assert fast.long_s == 0.16 and slow.threshold == 1.5


# --------------------------------------------------------------------------
# ring series + percentile/histogram edge cases
# --------------------------------------------------------------------------

def test_ring_series_basics():
    s = RingSeries("x", capacity=3)
    assert s.last is None and s.last_t is None
    assert s.at_or_before(10.0) is None
    for i in range(4):
        s.push(float(i), float(i * 10))
    assert len(s) == 3                       # oldest sample evicted
    assert s.at_or_before(0.5) is None       # t=0 aged out of the ring
    assert s.at_or_before(2.5) == 20.0
    assert s.last == 30.0 and s.last_t == 3.0
    assert s.window(1.0, 3.0) == [(2.0, 20.0), (3.0, 30.0)]
    assert s.window_mean(1.0, 3.0) == 25.0
    assert s.window_mean(90.0, 99.0) == 0.0  # empty window convention
    with pytest.raises(ValueError, match="before"):
        s.push(2.0, 0.0)
    with pytest.raises(ValueError, match="capacity"):
        RingSeries("x", capacity=0)


def test_latency_percentile_edges():
    assert latency_percentile([], 99) == 0.0
    for q in (0, 50, 99, 100):
        assert latency_percentile([0.7], q) == 0.7
    assert latency_percentile([3.3] * 5, 99) == 3.3   # exactly, no interp
    assert latency_percentile([1.0, 2.0, 3.0], 50) == 2.0


def test_histogram_edges():
    h = Histogram("lat")
    assert h.mean == 0.0
    assert h.as_dict() == {"count": 0, "sum": 0.0, "mean": 0.0,
                           "min": None, "max": None}
    h.observe(2.5)
    assert h.as_dict() == {"count": 1, "sum": 2.5, "mean": 2.5,
                           "min": 2.5, "max": 2.5}
    h2 = Histogram("eq")
    for _ in range(4):
        h2.observe(1.25)
    assert h2.mean == 1.25 and h2.vmin == h2.vmax == 1.25
    with pytest.raises(ValueError, match="finite"):
        h.observe(float("nan"))


# --------------------------------------------------------------------------
# export: golden waterfall + Perfetto schema invariants
# --------------------------------------------------------------------------

def test_waterfall_matches_golden():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng, n=2)
    got = waterfall_query(tracer.queries[0], width=40) + "\n"
    golden = GOLDEN / "waterfall_plain.txt"
    if os.environ.get("REPRO_UPDATE_GOLDEN"):
        golden.write_text(got)
    assert got == golden.read_text(), \
        "waterfall drifted from tests/golden/waterfall_plain.txt " \
        "(set REPRO_UPDATE_GOLDEN=1 to regenerate on purpose)"


def test_chrome_trace_schema():
    _, tracer, pe, eng, _ = monitored_chaos_run(n_queries=20)
    j = chrome_trace_json(tracer)
    doc = json.loads(j)
    # sorted keys + fixed separators: the canonical serialization
    assert j == json.dumps(doc, sort_keys=True, separators=(",", ":"))
    # X events are ts-monotone within every (pid, tid) lane, and all
    # metadata precedes all X events
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert phs.index("X") == len([p for p in phs if p == "M"])
    lanes = {}
    for e in doc["traceEvents"]:
        if e["ph"] != "X":
            continue
        last = lanes.get((e["pid"], e["tid"]))
        assert last is None or e["ts"] >= last, \
            f"lane {(e['pid'], e['tid'])} went backwards at {e['name']}"
        lanes[(e["pid"], e["tid"])] = e["ts"]
    assert len(lanes) > 2


# --------------------------------------------------------------------------
# trace-diff digests + the regression explainer
# --------------------------------------------------------------------------

def test_digest_exact_and_derived():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng)
    d = digest(eng, tracer)
    assert d["v"] == 1 and d["exact"] and d["queries"] == 4
    assert d["snapshot"]["sla.served"] == 4
    assert any(k.startswith("scan/") for k in d["categories"])
    json.dumps(d)                            # JSON-safe, always
    d2 = digest(eng)                         # no tracer: ledger-derived
    assert not d2["exact"]
    assert all(k.startswith("all/") for k in d2["categories"])
    assert d2["categories"]["all/fast_read"] > 0


def test_diff_names_dominant_category():
    base_eng, _, base_tr = tiered_engine(make_table(), fast_frac=0.5)
    run_queries(base_eng)
    new_eng, _, new_tr = tiered_engine(make_table(), fast_frac=0.125)
    run_queries(new_eng)
    rep = diff_traces(base_tr, new_tr)
    assert rep.exact
    dom = rep.dominant()
    # a smaller fast tier shows up as capacity reads owning the delta
    assert dom is not None and dom.category == "capacity_read"
    assert dom.delta_s > 0 and rep.delta_total_s > 0
    assert f"dominant regression: {dom.key}" in rep.render()
    # per-query normalization: query counts divide out
    rep2 = diff_digests(digest(base_eng, base_tr),
                        digest(new_eng, new_tr))
    row = {r.key: r for r in rep2.rows}[dom.key]
    assert row.delta_s == pytest.approx(dom.delta_s, rel=1e-12)


def test_diff_no_regression():
    eng, _, tr = tiered_engine(make_table())
    run_queries(eng)
    rep = diff_traces(tr, tr)
    assert rep.dominant() is None
    assert rep.delta_total_s == 0.0
    assert "no category regressed" in rep.render()


def _obs(categories, queries=4, snapshot=None):
    return {"v": 1, "queries": queries, "exact": True,
            "snapshot": snapshot or {}, "categories": categories}


def test_check_regress_explains_category(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import check_regress
    monkeypatch.setattr(check_regress, "ROOT", tmp_path)
    path = tmp_path / "BENCH_queries.json"
    path.write_text(json.dumps([
        {"scan_agg_gbps": 10.0},
        {"scan_agg_gbps": 10.5,
         "obs": _obs({"scan/capacity_read": 0.4, "scan/fast_read": 0.1},
                     snapshot={"tier.hit_rate": 0.9})},
        {"scan_agg_gbps": 6.0,
         "obs": _obs({"scan/capacity_read": 1.6, "scan/fast_read": 0.1},
                     snapshot={"tier.hit_rate": 0.4})},
    ]))
    ok, msg = check_regress.check_bench("queries")
    assert not ok and "REGRESSION" in msg
    assert ("dominant regressing span category: scan/capacity_read"
            in msg)
    assert "tier.hit_rate" in msg            # snapshot deltas rendered
    # --explain mode produces the JSON artifact without gating
    out = tmp_path / "diff.json"
    assert check_regress.main(["queries", "--explain",
                               "--out", str(out)]) == 0
    payloads = json.loads(out.read_text())
    assert payloads[0]["dominant"] == "scan/capacity_read"
    assert payloads[0]["bench"] == "queries"


def test_check_regress_without_digest(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import check_regress
    monkeypatch.setattr(check_regress, "ROOT", tmp_path)
    path = tmp_path / "BENCH_queries.json"
    path.write_text(json.dumps([{"scan_agg_gbps": 10.0},
                                {"scan_agg_gbps": 10.5},
                                {"scan_agg_gbps": 4.0}]))
    ok, msg = check_regress.check_bench("queries")
    assert not ok and "no obs digest" in msg
    msg, payload = check_regress.explain_bench("queries")
    assert payload is None and "SKIP" in msg


# --------------------------------------------------------------------------
# the what-if hook against the decision surface
# --------------------------------------------------------------------------

def test_whatif_consistent_with_surface():
    monitor, tracer, pe, eng, att = monitored_chaos_run()
    attr = attribute(tracer)
    table_bytes = pe.tiers.fast.capacity / 0.25
    bytes_q = (sum(r.bytes_scanned for r in eng.results)
               / len(eng.results))
    wi = whatif_fast_fraction(                # raises on surface drift
        attr, db_bytes=table_bytes, bytes_per_query=bytes_q,
        sla_s=10.0, current_fraction=0.25,
        hit_curve=zipf_hit_curve(8, 1.2),
        fast_gbps=pe.tiers.fast.gbps, capacity_gbps=pe.tiers.capacity.gbps)
    assert wi["current"]["read_s"] > 0
    rows = wi["rows"]
    assert [r["fast_fraction"] for r in rows] \
        == sorted(r["fast_fraction"] for r in rows)
    # more fast tier never slows the estimated read time
    est = [r["est_read_s"] for r in rows]
    assert all(a >= b - 1e-15 for a, b in zip(est, est[1:]))
    assert wi["best"] is not None            # sla_s=10 s is trivially met
    assert wi["best"]["meets_sla"]


def test_whatif_rejects_bad_inputs():
    with pytest.raises(ValueError, match="read-bound"):
        whatif_fast_fraction(
            {"queue": 5.0}, db_bytes=1e9, bytes_per_query=1e6,
            sla_s=0.01, current_fraction=0.5,
            hit_curve=zipf_hit_curve(8, 1.2),
            fast_gbps=10.0, capacity_gbps=1.0)
    with pytest.raises(ValueError, match="current_fraction"):
        whatif_fast_fraction(
            {"fast_read": 1.0}, db_bytes=1e9, bytes_per_query=1e6,
            sla_s=0.01, current_fraction=1.5,
            hit_curve=zipf_hit_curve(8, 1.2),
            fast_gbps=10.0, capacity_gbps=1.0)
