"""Observability: deterministic tracing, scoped metrics, conservation.

Pins down PR 9's contracts:

- the conservation audit holds on every execution path — plain tiered,
  encoded (compressed store), sharded, grouped, prefetch on, chaos on —
  and *fails* on a deliberately double-charged synthetic ledger;
- a seeded chaos replay exports byte-identical Chrome trace JSON twice;
- launch counters: the default scope sees every launch, two engines'
  scoped registries don't pollute each other;
- the unified snapshot's canonical byte keys agree with both
  PlacementEngine totals and PrefetchPipeline.stats() (the
  overlapping-key normalization regression test);
- the bench regression gate trips on a >30% drop and passes otherwise;
- a flat engine's host-clock spans nest, share one qid, and land in the
  profiler's trace; with tracing off no annotation is opened.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.db import Table
from repro.launch.mesh import make_mesh
from repro.obs import (ConservationError, MetricsRegistry, NullTracer,
                       Tracer, audit, check, chrome_trace,
                       chrome_trace_json, scoped, unified_snapshot,
                       waterfall)
from repro.obs import metrics as obs_metrics
from repro.obs.trace import NULL_TRACE
from repro.query import Query, QueryEngine, ShardedTable
from repro.query.plan import GroupBy, Pred
from repro.resilience import (ChaosHarness, ChunkGuard, FaultSpec,
                              RetryPolicy)
from repro.serve.sla import VirtualClock
from repro.store import EncodedTable
from repro.tier import (PlacementEngine, Policy, TraceSpec, make_trace,
                        paper_tiers, replay_trace)
from repro.tier.prefetch import PrefetchPipeline

N_ROWS, CHUNK_ROWS = 4096, 512


def make_table(seed=1, n_cols=8):
    return Table.synthetic("obs", N_ROWS,
                           {f"c{i:02d}": 8 for i in range(n_cols)},
                           seed=seed)


def tiered_engine(table, *, policy=Policy.CACHE, fast_frac=0.5,
                  compute_w=0.0, **kw):
    from repro.energy.meter import EnergyMeter
    tiers = paper_tiers(table.nbytes * fast_frac, fast_gbps=10.0)
    pe = PlacementEngine.for_table(table, tiers, policy,
                                   chunk_rows=CHUNK_ROWS,
                                   meter=EnergyMeter(tiers, compute_w))
    tracer = Tracer()
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock(), tracer=tracer, **kw)
    return eng, pe, tracer


def run_queries(eng, n=4):
    for _ in range(n):
        q = Query(Pred("c00", "ge", 10), aggregates=("c01",))
        assert eng.submit(q, deadline=eng.clock() + 100.0) is not None
        eng.run()


# --------------------------------------------------------------------------
# conservation audit across execution paths
# --------------------------------------------------------------------------

def test_audit_plain_tiered():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng)
    report = check(tracer, pe.meter)
    assert report.ok and len(report.queries) == 4
    # query-kind bytes match the engine's accounting exactly
    for qa, res in zip(report.queries, eng.results):
        assert sum(qa.span_bytes["query"]) == res.bytes_scanned


def test_audit_with_compute_term():
    eng, pe, tracer = tiered_engine(make_table(), compute_w=7.5)
    run_queries(eng)
    assert pe.meter.compute_j > 0
    check(tracer, pe.meter)


def test_audit_encoded():
    table = make_table()
    enc = EncodedTable.from_table(table, chunk_rows=CHUNK_ROWS)
    eng, pe, tracer = tiered_engine(enc)
    run_queries(eng)
    check(tracer, pe.meter)


def test_audit_sharded():
    st = ShardedTable.shard(make_table(), make_mesh((1,), ("data",)))
    eng, pe, tracer = tiered_engine(st)
    run_queries(eng)
    check(tracer, pe.meter)


def test_audit_grouped():
    table = make_table()
    enc = EncodedTable.from_table(table, chunk_rows=CHUNK_ROWS)
    eng, pe, tracer = tiered_engine(enc)
    q = GroupBy(keys=("c00",), aggs=("c01",),
                where=Pred("c02", "ge", 4))
    assert eng.submit(q, deadline=eng.clock() + 100.0) is not None
    eng.run()
    check(tracer, pe.meter)
    # grouped execution attributed its batched launches to the query
    kinds = tracer.queries[0].span_kinds()
    assert kinds.get("launch", 0) >= 1


def test_audit_prefetch():
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
    check(tracer, pe.meter)
    kinds = {}
    for qt in tracer.queries:
        for k, n in qt.span_kinds().items():
            kinds[k] = kinds.get(k, 0) + n
    assert kinds.get("prefetch_read", 0) > 0, \
        "pipeline never staged a chunk in the trace"
    assert pe.prefetch_streamed_bytes_total == sum(
        sp.nbytes for qt in tracer.queries for sp in qt.spans
        if sp.kind == "prefetch_read")


def chaos_traced_run(n_queries=60, prefetch=True):
    """Seeded fault-injected replay with tracing; fresh state per call."""
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
    tracer = Tracer()
    pe, eng, att = replay_trace(
        enc, qtrace, tiers, Policy.CACHE, sla_s=5e-2,
        chunk_rows=CHUNK_ROWS, chaos=chaos,
        prefetch_bytes=(table.nbytes // 16 if prefetch else 0),
        tracer=tracer)
    return tracer, pe, eng


def test_audit_chaos():
    tracer, pe, eng = chaos_traced_run()
    report = check(tracer, pe.meter)
    assert report.ok
    kinds = {}
    for qt in tracer.queries:
        for k, n in qt.span_kinds().items():
            kinds[k] = kinds.get(k, 0) + n
    # the fault machinery actually fired and was traced
    assert kinds.get("retry", 0) > 0
    assert kinds.get("repair", 0) > 0
    assert kinds.get("prefetch_stall", 0) > 0
    # recovery span bytes == the placement engine's recovery total
    rec_span_b = sum(sp.nbytes for qt in tracer.queries
                     for sp in qt.spans if sp.ledger == "recovery")
    assert rec_span_b == pe.recovery_bytes_total


def test_audit_fails_on_double_charge():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng, n=2)
    check(tracer, pe.meter)
    # charge the same recovery bytes a second time against a traced qid —
    # the PR 6-7 double-charge bug class, now structurally detectable
    pe.meter.charge(0, 4096, qid=tracer.queries[0].qid, kind="recovery")
    report = audit(tracer, pe.meter)
    assert not report.ok
    with pytest.raises(ConservationError, match="recovery"):
        check(tracer, pe.meter)


def test_audit_flags_untraced_ledger_lines():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng, n=1)
    pe.meter.charge(0, 512, qid=999, kind="query")
    report = audit(tracer, pe.meter)
    assert not report.ok
    assert any("untraced" in p for p in report.problems)


# --------------------------------------------------------------------------
# determinism + export
# --------------------------------------------------------------------------

def test_chaos_trace_byte_identical():
    j1 = chrome_trace_json(chaos_traced_run(n_queries=40)[0])
    j2 = chrome_trace_json(chaos_traced_run(n_queries=40)[0])
    assert j1 == j2
    assert len(j1) > 1000


def test_chrome_trace_loadable():
    tracer, pe, eng = chaos_traced_run(n_queries=20)
    doc = json.loads(chrome_trace_json(tracer))
    events = doc["traceEvents"]
    assert events, "empty trace"
    assert {e["ph"] for e in events} <= {"X", "M"}
    xs = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    # one root lane event per served query
    roots = [e for e in xs if e["tid"] == 0]
    assert len(roots) == len(tracer.queries)
    # round-trips through chrome_trace() identically
    assert doc == chrome_trace(tracer)


def test_waterfall_renders():
    tracer, pe, eng = chaos_traced_run(n_queries=10)
    text = waterfall(tracer, max_queries=3)
    assert "read" in text and "q" in text
    assert len(text.splitlines()) > 3


# --------------------------------------------------------------------------
# tracer surface + disabled path
# --------------------------------------------------------------------------

def test_null_tracer_is_allocation_free():
    nt = NullTracer()
    qt = nt.begin_query(1)
    assert qt is NULL_TRACE and not qt.enabled
    qt.begin_run(0.0)
    assert qt.read((0, 0), 1, tier="fast", hit=True) is None
    qt.close(1.0, met=True)
    assert len(nt) == 0


def test_engine_default_has_no_tracing_overhead():
    eng, pe, _ = tiered_engine(make_table())
    eng2 = QueryEngine(make_table(), mode="xla_ref",
                       tiered=PlacementEngine.for_table(
                           make_table(),
                           paper_tiers(make_table().nbytes * 0.5,
                                       fast_gbps=10.0),
                           Policy.CACHE, chunk_rows=CHUNK_ROWS),
                       clock=VirtualClock())
    assert isinstance(eng2.tracer, NullTracer)
    run_queries(eng2, n=1)   # runs clean with tracing off


# --------------------------------------------------------------------------
# host-clock spans of a flat engine
# --------------------------------------------------------------------------

Q6_SHAPED = Query(Pred("c00", "ge", 10) & Pred("c01", "lt", 90)
                  & Pred("c02", "le", 100), aggregates=("c03", "c04"))


def flat_traced_run(n=2):
    st = ShardedTable.shard(make_table(), make_mesh((1,), ("data",)))
    tracer = Tracer()
    eng = QueryEngine(st, mode="xla_ref", tracer=tracer)
    for _ in range(n):
        assert eng.submit(Q6_SHAPED) is not None
        eng.run()
    return tracer


def _one(qt, kind):
    (sp,) = [sp for sp in qt.spans if sp.kind == kind]
    return sp


def _within(inner, outer):
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_flat_tracer_records_host_clock_spans():
    tracer = flat_traced_run()
    assert len(tracer) == 2
    for i, qt in enumerate(tracer.queries):
        assert {sp.attrs["qid"] for sp in qt.spans} == {qt.qid}
        kinds = set(qt.span_kinds())
        assert not kinds & {"launch", "launch_batch", "admission"}
        sub, bind = _one(qt, "query.submit"), _one(qt, "query.bind")
        serve, adm = _one(qt, "query.serve"), _one(qt, "query.admission")
        disp, fin = _one(qt, "query.dispatch"), _one(qt, "query.finalize")
        assert _within(bind, sub)
        assert sub.t1 <= adm.t0 and adm.t1 <= serve.t0
        assert _within(disp, serve) and _within(fin, serve)
        assert disp.t1 <= fin.t0
        assert qt.t_start <= serve.t0 and serve.t1 >= qt.t_end
        assert all(sp.dur_s > 0 for sp in qt.spans)
        if i == 0:     # the first query builds its program
            assert _within(_one(qt, "query.build"), disp)
        else:
            assert "query.build" not in kinds
    # every host-clock kind has a lane of its own in the Chrome export
    lanes = {e["cat"]: e["tid"] for e in chrome_trace(tracer)["traceEvents"]
             if e["ph"] == "X" and e["tid"]}
    assert len({lanes[k] for k in lanes if k.startswith("query.")}) == 7


def test_null_tracer_opens_no_annotation(monkeypatch):
    import jax
    from repro.obs import trace as obs_trace
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        opened.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    st = ShardedTable.shard(make_table(), make_mesh((1,), ("data",)))
    eng = QueryEngine(st, mode="xla_ref")
    eng.submit(Q6_SHAPED)
    eng.run()
    assert opened == []
    assert obs_trace.span("query.dispatch") is obs_trace.span("query.serve")
    with obs_trace.active(NULL_TRACE):
        assert obs_trace.span("query.dispatch") is obs_trace._NO_SPAN
    flat_traced_run(n=1)         # the same path with a tracer opens them
    assert "query.dispatch" in opened


def test_host_spans_land_in_the_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    flat_traced_run(n=1)         # JAX's own compile stays out of the window
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer"):
            tracer = flat_traced_run(n=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "outer" or e.name.startswith("query.")]
    (outer,) = [e for e in events if e.name == "outer"]
    spans = [e for e in events if e.name != "outer"]
    qid = tracer.queries[0].qid
    assert {e.name for e in spans} == {
        "query.submit", "query.bind", "query.admission", "query.serve",
        "query.dispatch", "query.build", "query.finalize"}
    for e in spans:
        assert dict(e.stats)["qid"] == qid
        assert outer.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= outer.start_ns + \
            outer.duration_ns


def test_tiered_tracer_records_no_host_spans():
    eng, pe, tracer = tiered_engine(make_table())
    run_queries(eng, n=2)
    for qt in tracer.queries:
        assert not any(k.startswith("query.") for k in qt.span_kinds())
        assert qt.span_kinds()["admission"] == 1


# --------------------------------------------------------------------------
# scoped metrics: launch counters in the default and engine scopes
# --------------------------------------------------------------------------

def test_dispatch_shims_default_scope():
    default = obs_metrics.default_registry()
    default.reset_launches()
    obs_metrics.count_launch("fam_a", 2)
    obs_metrics.count_launch("fam_b")
    assert default.launch_counts() == {"fam_a": 2, "fam_b": 1}
    assert default.total_launches() == 3
    default.reset_launches()
    assert default.launch_counts() == {}


def test_scoped_isolation_between_engines():
    default = obs_metrics.default_registry()
    default.reset_launches()
    r1, r2 = MetricsRegistry("e1"), MetricsRegistry("e2")
    with scoped(r1):
        obs_metrics.count_launch("fam", 3)
    with scoped(r2):
        obs_metrics.count_launch("fam", 5)
    assert r1.launch_counts() == {"fam": 3}
    assert r2.launch_counts() == {"fam": 5}
    # the default scope still sees the global view
    assert default.launch_counts() == {"fam": 8}
    default.reset_launches()
    # resetting the default does not clear engine scopes
    assert r1.launch_counts() == {"fam": 3}


def test_engine_scope_attributes_launches():
    t = make_table()
    eng, pe, tracer = tiered_engine(t)
    run_queries(eng, n=2)
    assert eng.metrics.launch_counts().get("scan_aggregate") == 2
    # the trace carries one launch span per family per query
    for qt in tracer.queries:
        fams = [sp.attrs["family"] for sp in qt.spans
                if sp.kind == "launch"]
        assert fams == ["scan_aggregate"]


def test_registry_histogram_and_gauge():
    r = MetricsRegistry("x")
    r.gauge("depth").set(3.5)
    h = r.histogram("lat")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["gauges"]["depth"] == 3.5
    assert snap["histograms"]["lat"]["count"] == 3
    assert snap["histograms"]["lat"]["mean"] == 2.0
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)


# --------------------------------------------------------------------------
# unified snapshot: the overlapping-key normalization (satellite fix)
# --------------------------------------------------------------------------

def test_snapshot_normalizes_prefetch_keys():
    table = make_table()
    from repro.energy.meter import EnergyMeter
    tiers = paper_tiers(table.nbytes * 0.25, fast_gbps=10.0)
    pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                   chunk_rows=CHUNK_ROWS,
                                   meter=EnergyMeter(tiers))
    pf = PrefetchPipeline(pe, table.nbytes // 8)
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock(), prefetch=pf)
    run_queries(eng, n=6)
    snap = unified_snapshot(eng)
    # one canonical name per byte stream, cross-checked against both the
    # placement totals and the pipeline's stats() dialect
    assert snap["prefetch.streamed_bytes"] \
        == pe.prefetch_streamed_bytes_total \
        == pf.stats()["streamed_bytes"]
    assert snap["prefetch.wasted_bytes"] \
        == pe.prefetch_wasted_bytes_total == pf.stats()["wasted_bytes"]
    assert snap["tier.recovery_bytes"] == pe.recovery_bytes_total \
        == pe.stats()["recovery_bytes"]
    assert snap["tier.fast_bytes"] == pe.stats()["fast_bytes"]
    assert snap["energy.prefetch_j"] == pe.meter.prefetch_j
    assert snap["sla.served"] == 6


def test_snapshot_detects_key_drift():
    # the placement totals and the pipeline's own ledger are maintained
    # independently; drift one byte apart and the snapshot must refuse to
    # tell two stories
    table = make_table()
    from repro.energy.meter import EnergyMeter
    tiers = paper_tiers(table.nbytes * 0.25, fast_gbps=10.0)
    pe = PlacementEngine.for_table(table, tiers, Policy.CACHE,
                                   chunk_rows=CHUNK_ROWS,
                                   meter=EnergyMeter(tiers))
    pf = PrefetchPipeline(pe, table.nbytes // 8)
    eng = QueryEngine(table, mode="xla_ref", tiered=pe,
                      clock=VirtualClock(), prefetch=pf)
    run_queries(eng, n=6)
    assert pf.streamed_bytes_total > 0   # the pair must be live, not 0==0
    pe.prefetch_streamed_bytes_total += 1
    with pytest.raises(ValueError, match="streamed_bytes"):
        unified_snapshot(eng)


# --------------------------------------------------------------------------
# bench regression gate
# --------------------------------------------------------------------------

def test_check_regress_gate(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import check_regress
    monkeypatch.setattr(check_regress, "ROOT", tmp_path)
    path = tmp_path / "BENCH_queries.json"
    path.write_text(json.dumps(
        [{"scan_agg_gbps": v} for v in (10.0, 11.0, 10.5, 10.8)]))
    ok, msg = check_regress.check_bench("queries")
    assert ok, msg
    # >30% drop from the median trips the gate
    path.write_text(json.dumps(
        [{"scan_agg_gbps": v} for v in (10.0, 11.0, 10.5, 6.0)]))
    ok, msg = check_regress.check_bench("queries")
    assert not ok and "REGRESSION" in msg
    assert check_regress.main(["queries"]) == 1
    # a missing file is a skip, not a failure
    ok, msg = check_regress.check_bench("store")
    assert ok and "SKIP" in msg
