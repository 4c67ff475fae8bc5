"""Query-engine tests: plan shapes, mode parity, sharding, SLA batching.

Parity contract (ISSUE 2): every plan shape — AND/OR, mixed code widths,
sharded vs single-device — produces identical results under
KernelMode.PALLAS and KernelMode.XLA_REF, and matches a numpy oracle over
the decoded values.
"""
import math

import numpy as np
import pytest

from repro.db import Table
from repro.kernels.scan_filter.kernel import TILE_WORDS
from repro.launch.mesh import make_mesh
from repro.obs.metrics import MetricsRegistry, scoped
from repro.query import And, Or, Pred, Query, QueryEngine, ShardedTable
from repro.query.plan import normalize
from repro.query.sharded import shard_rows

MODES = ("pallas", "xla_ref")

# 10_001 rows: not a multiple of any codes-per-word, so every column carries
# tail padding — the validity masks must cancel it under every plan shape
N_ROWS = 10_001
SPEC = {"a": 8, "b": 8, "w": 16, "x": 4}


@pytest.fixture(scope="module")
def table():
    return Table.synthetic("t", N_ROWS, SPEC, seed=3)


@pytest.fixture(scope="module")
def decoded(table):
    return {c: table.columns[c].decode() for c in SPEC}


def oracle(decoded, sel, agg):
    vals = decoded[agg][sel]
    vmax = (1 << (SPEC[agg] - 1)) - 1
    return {"sum": int(vals.sum()) if sel.any() else 0,
            "count": int(sel.sum()),
            "min": int(vals.min()) if sel.any() else vmax,
            "max": int(vals.max()) if sel.any() else 0}


PLAN_SHAPES = [
    # (name, plan factory, numpy selection factory, aggregates)
    # -- same-width single-pred/single-agg shapes take the fused kernel;
    #    cover every composition primitive (ge direct, lt/ne inverted,
    #    gt via constant+1, eq) --
    ("single_pred_fused", lambda: Pred("a", "lt", 50),
     lambda d: d["a"] < 50, ("b",)),
    ("fused_ge", lambda: Pred("a", "ge", 100),
     lambda d: d["a"] >= 100, ("b",)),
    ("fused_gt", lambda: Pred("a", "gt", 100),
     lambda d: d["a"] > 100, ("b",)),
    ("fused_eq", lambda: Pred("a", "eq", 64),
     lambda d: d["a"] == 64, ("b",)),
    ("fused_ne", lambda: Pred("a", "ne", 64),
     lambda d: d["a"] != 64, ("b",)),
    ("and_same_width", lambda: Pred("a", "lt", 50) & Pred("b", "ge", 100),
     lambda d: (d["a"] < 50) & (d["b"] >= 100), ("b",)),
    ("and_mixed_width", lambda: Pred("a", "lt", 50) & Pred("w", "ge", 9000),
     lambda d: (d["a"] < 50) & (d["w"] >= 9000), ("w",)),
    ("or_mixed_width", lambda: Pred("x", "eq", 3) | Pred("w", "lt", 500),
     lambda d: (d["x"] == 3) | (d["w"] < 500), ("a",)),
    ("nested_and_or",
     lambda: And.of(Or.of(Pred("a", "le", 20), Pred("b", "gt", 120)),
                    Pred("x", "ne", 0)),
     lambda d: ((d["a"] <= 20) | (d["b"] > 120)) & (d["x"] != 0), ("b",)),
    ("multi_agg_mixed", lambda: Pred("a", "ge", 64),
     lambda d: d["a"] >= 64, ("b", "w", "x")),
    ("empty_selection", lambda: Pred("x", "gt", 7),
     lambda d: d["x"] > 7, ("a",)),
]


@pytest.mark.parametrize("name,mkplan,mksel,aggs",
                         PLAN_SHAPES, ids=[p[0] for p in PLAN_SHAPES])
def test_plan_shape_parity_all_modes(table, decoded, name, mkplan, mksel,
                                     aggs):
    sel = mksel(decoded)
    want = {a: oracle(decoded, sel, a) for a in aggs}
    got_by_mode = {}
    for mode in MODES:
        eng = QueryEngine(table, mode=mode)
        eng.submit(Query(mkplan(), aggregates=aggs))
        res = eng.run()[0]
        assert res.aggregates == want, (name, mode)
        got_by_mode[mode] = res.aggregates
        assert res.count == int(sel.sum())
    assert got_by_mode["pallas"] == got_by_mode["xla_ref"]


@pytest.mark.parametrize("name,mkplan,mksel,aggs",
                         PLAN_SHAPES, ids=[p[0] for p in PLAN_SHAPES])
def test_sharded_matches_single_device(table, decoded, name, mkplan, mksel,
                                       aggs):
    """1-device mesh in-process; the 8-device run lives in
    tests/multidevice_child.py (device count locks at first jax init)."""
    mesh = make_mesh((1,), ("data",))
    st = ShardedTable.shard(table, mesh)
    sel = mksel(decoded)
    want = {a: oracle(decoded, sel, a) for a in aggs}
    for mode in ("pallas", "xla_ref"):
        eng = QueryEngine(st, mode=mode)
        eng.submit(Query(mkplan(), aggregates=aggs))
        assert eng.run()[0].aggregates == want, (name, mode)


def test_empty_table_returns_identity():
    """Zero-row tables execute cleanly (regression: zero-row Pallas grid
    divided by zero) and return the empty-selection identity."""
    t = Table.synthetic("empty", 0, {"a": 8, "b": 8})
    q = Query(Pred("a", "lt", 5), aggregates=("b",))
    for mode in ("pallas", "xla_ref"):
        eng = QueryEngine(t, mode=mode)
        eng.submit(q)
        res = eng.run()[0]
        assert res.aggregates["b"] == {"sum": 0, "count": 0, "min": 127,
                                       "max": 0}
        assert res.count == 0 and res.selectivity == 0


def test_default_mode_engine_answers_like_the_reference():
    """An engine built with no mode runs PALLAS, the mode every benchmark
    cell runs, and answers a Q6-shaped and a Q1-shaped query over a small
    placed lineitem exactly as the benchmark's numpy reference does."""
    from chipbench import queries, reference, run
    from repro.kernels.dispatch import KernelMode
    _, _, config, q1 = run.load_cell("q1_power")
    q6 = queries.load_traffic("q6_power")
    codes, st = run.place(dict(config, rows=20_000), 7, 1)
    eng = QueryEngine(st)
    assert eng.mode is KernelMode.PALLAS
    for template in (q6["queries"][0], q1["queries"][0]):
        eng.submit(queries.build(template))
        out = eng.run()
        assert len(out) == 1 and not out[0].degraded
        assert out[0].aggregates == reference.answer(template, codes)


def test_engine_sum_exact_beyond_int32():
    """A 16-bit column over a few hundred k rows sums past 2^31: the
    engine must report the exact value, single-device and sharded."""
    t = Table.synthetic("big", 300_000, {"p": 16}, seed=5)
    want = int(t.columns["p"].decode().astype(np.int64).sum())
    assert want > 2**31
    q = Query(Pred("p", "ge", 0), aggregates=("p",))
    for tbl in (t, ShardedTable.shard(t, make_mesh((1,), ("data",)))):
        eng = QueryEngine(tbl)
        eng.submit(q)
        res = eng.run()[0]
        assert res.aggregates["p"]["sum"] == want
        assert res.count == 300_000


# --- shard placement at whole kernel tiles --------------------------------

TILE_SCHEMAS = {"16": (16, 16, 16), "8_16": (8, 16, 16, 8),
                "2_4": (2, 4, 4), "2_8_16": (2, 8, 16)}


def _word_aligned(widths, rows: int, n: int) -> int:
    """rows_per_shard at word alignment alone: the ceiling share rounded
    up to every width's codes-per-word."""
    align = math.lcm(*(32 // b for b in widths))
    return -(-max(1, -(-rows // n)) // align) * align


def _tile_rows(widths) -> int:
    """Rows that fill one TILE_WORDS tile of every column."""
    return TILE_WORDS * max(32 // b for b in widths)


@pytest.mark.parametrize("n", (1, 4))
@pytest.mark.parametrize("schema", sorted(TILE_SCHEMAS))
def test_shard_rows_under_one_tile_keeps_word_alignment(schema, n):
    widths = TILE_SCHEMAS[schema]
    tile = _tile_rows(widths)
    for rows in (0, 1, 10_001, n * (tile - 32) - 3):
        assert shard_rows(widths, rows, n) == _word_aligned(widths, rows,
                                                            n), rows


@pytest.mark.parametrize("n", (1, 4))
@pytest.mark.parametrize("schema", sorted(TILE_SCHEMAS))
def test_shard_rows_whole_tiles_from_one_tile(schema, n):
    """At and past one tile a shard is a whole number of tiles of every
    column, and costs under one tile of rows more than word alignment."""
    widths = TILE_SCHEMAS[schema]
    tile = _tile_rows(widths)
    for rows in (n * tile, n * tile + 1, 3 * n * tile - 5, 600_037_902,
                 1_799_989_091):
        rps = shard_rows(widths, rows, n)
        assert rps * n >= rows
        assert 0 <= rps - _word_aligned(widths, rows, n) < tile, rows
        for b in widths:
            assert rps * b % 32 == 0
            assert (rps * b // 32) % TILE_WORDS == 0, (rows, b)


@pytest.mark.parametrize("rows,pads", ((10_001, True), (65_537, False)),
                         ids=("under_one_tile", "whole_tiles"))
def test_tile_pads_counts_q6_wrapper_pads(rows, pads):
    """A Q6-shaped program over a word-aligned shard pads planes to the
    kernel tiling; over a shard placed at whole tiles it pads nothing.
    Either way the answers are the numpy oracle's."""
    spec = {"d": 16, "x": 16, "q": 16}
    t = Table.synthetic("q6", rows, spec, seed=7)
    st = ShardedTable.shard(t, make_mesh((1,), ("data",)))
    assert (st.rows_per_shard % _tile_rows(spec.values()) != 0) == pads
    q = Query(Pred("d", "ge", 100) & Pred("d", "lt", 9000)
              & Pred("x", "ge", 5) & Pred("x", "le", 7000)
              & Pred("q", "lt", 24000), aggregates=("x", "q"))
    cols = {c: t.columns[c].decode().astype(np.int64) for c in spec}
    sel = ((cols["d"] >= 100) & (cols["d"] < 9000) & (cols["x"] >= 5)
           & (cols["x"] <= 7000) & (cols["q"] < 24000))
    reg = MetricsRegistry("tile_pads")
    with scoped(reg):
        got = st.execute(q.plan(), q.aggregates, mode="pallas")
    assert (reg.counter("tile_pads").value > 0) == pads
    for a in q.aggregates:
        v = cols[a][sel]
        assert got[a] == {"sum": int(v.sum()), "count": int(sel.sum()),
                          "min": int(v.min()), "max": int(v.max())}


Q6_NARROW = {"l_shipdate": 16, "l_quantity": 8, "l_discount": 8}
Q6_WIDE = dict.fromkeys(Q6_NARROW, 16)


def q6_table(rows: int, widths: dict) -> Table:
    """Q6's three lineitem columns over their TPC-H domains (ship day
    codes up to 2,526, quantity 1-50, discount 0-10) at `widths`."""
    from repro.db.columnar import BitPackedColumn
    rng = np.random.default_rng(17)
    t = Table("lineitem")
    for name, hi in (("l_shipdate", 2526), ("l_quantity", 50),
                     ("l_discount", 10)):
        t.add(BitPackedColumn.from_values(
            name, rng.integers(name == "l_quantity", hi + 1, rows),
            widths[name]))
    return t


@pytest.mark.parametrize("widths,repacks", ((Q6_NARROW, 1), (Q6_WIDE, 0)),
                         ids=("narrow", "one_width"))
@pytest.mark.parametrize("placed", ("flat", "sharded"))
def test_q6_at_narrow_widths_takes_one_repack(widths, repacks, placed):
    """TPC-H Q6 through QueryEngine in mode pallas, over lineitem at its
    own widths (16, 8, 8 bits) and at one width: the numpy answer, with
    one mask repack (the ship-date mask to the aggregates' 8 bits) at
    mixed widths and none at one width."""
    t = q6_table(20_001, widths)
    table = (t if placed == "flat"
             else ShardedTable.shard(t, make_mesh((1,), ("data",))))
    q = Query(And.of(Pred("l_shipdate", "ge", 731),
                     Pred("l_shipdate", "lt", 1096),
                     Pred("l_discount", "ge", 5),
                     Pred("l_discount", "le", 7),
                     Pred("l_quantity", "lt", 24)),
              ("l_discount", "l_quantity"))
    c = {n: t.columns[n].decode().astype(np.int64) for n in widths}
    sel = ((c["l_shipdate"] >= 731) & (c["l_shipdate"] < 1096)
           & (c["l_discount"] >= 5) & (c["l_discount"] <= 7)
           & (c["l_quantity"] < 24))
    eng = QueryEngine(table, mode="pallas")
    eng.submit(q)
    got = eng.run()[0].aggregates
    for a in q.aggregates:
        v = c[a][sel]
        assert got[a] == {"sum": int(v.sum()), "count": int(sel.sum()),
                          "min": int(v.min()), "max": int(v.max())}
    assert eng.metrics.counter("mask_repacks").value == repacks


class TestPlanLayer:
    def test_operators_build_flattened_trees(self):
        p = Pred("a", "lt", 3) & Pred("b", "ge", 1) & Pred("x", "eq", 2)
        assert isinstance(p, And) and len(p.children) == 3
        q = Pred("a", "lt", 3) | Pred("b", "ge", 1)
        assert isinstance(q, Or) and len(q.children) == 2

    def test_bad_op_raises(self):
        with pytest.raises(ValueError, match="unknown predicate op"):
            Pred("a", "like", 3)

    def test_negative_constant_raises(self):
        with pytest.raises(ValueError, match="negative"):
            Pred("a", "lt", -1)

    def test_empty_aggregates_raises(self):
        with pytest.raises(ValueError, match="aggregate"):
            Query(Pred("a", "lt", 3), aggregates=())

    def test_normalize_legacy_list_is_conjunction(self):
        plan = normalize([Pred("a", "lt", 3), Pred("b", "ge", 1)])
        assert isinstance(plan, And)
        with pytest.raises(ValueError, match="at least one predicate"):
            normalize([])

    def test_unknown_column_raises_at_submit(self, table):
        eng = QueryEngine(table)
        with pytest.raises(ValueError, match="unknown column"):
            eng.submit(Query(Pred("nope", "lt", 3), aggregates=("a",)))

    def test_constant_beyond_payload_raises(self, table):
        eng = QueryEngine(table)
        with pytest.raises(ValueError, match="payload max"):
            eng.submit(Query(Pred("x", "lt", 99), aggregates=("a",)))


class TestEngineSLA:
    class Clock:
        """Deterministic clock advancing a tick per observation."""

        def __init__(self, tick=0.01):
            self.t = 0.0
            self.tick = tick

        def __call__(self):
            self.t += self.tick
            return self.t

    def test_infeasible_deadline_rejected(self, table):
        clock = self.Clock()
        # 1e-6 GB/s => any query estimates ~minutes of service time
        eng = QueryEngine(table, clock=clock, est_gbps=1e-6)
        qid = eng.submit(Query(Pred("a", "lt", 50), aggregates=("b",)),
                         deadline=0.001)
        assert qid is None
        assert eng.rejected == [1]
        assert eng.run() == []

    def test_edf_order_and_reports(self, table):
        eng = QueryEngine(table, clock=self.Clock(),
                          est_gbps=1e9)          # everything feasible
        q = Query(Pred("a", "lt", 50), aggregates=("b",))
        ids = [eng.submit(q, deadline=d) for d in (math.inf, 500.0, 100.0)]
        results = eng.run()
        assert [r.qid for r in results] == [ids[2], ids[1], ids[0]]
        s = eng.summary()
        assert s["served"] == 3 and s["rejected"] == 0
        assert s["sla_attainment"] == 1.0
        assert s["latency_p99_s"] >= s["latency_p50_s"] > 0
        assert s["measured_gbps"] > 0

    def test_measured_throughput_feeds_admission(self, table):
        eng = QueryEngine(table, est_gbps=1e9)
        eng.submit(Query(Pred("a", "lt", 50), aggregates=("b",)))
        eng.run()
        assert eng.measured_bps == pytest.approx(
            eng.bytes_total / eng.seconds_total)

    def test_model_check_and_provision(self, table):
        eng = QueryEngine(table)
        eng.submit(Query(Pred("a", "lt", 50), aggregates=("b",)))
        eng.run()
        mc = eng.model_check()
        assert mc["chips"] == 1
        assert 0 < mc["measured_gbps"]
        assert 0 < mc["attained_fraction"] < 1   # interpret mode << model
        adv = eng.provision(sla_s=0.1)
        assert adv.design.compute_chips >= 1
        assert adv.design.response_time <= 0.1 * 1.01

    def test_model_check_before_any_query_raises(self, table):
        """Regression: zero measured throughput is a degenerate model
        comparison, not a silent row of zeros."""
        with pytest.raises(ValueError, match="model_check"):
            QueryEngine(table).model_check()

    def test_calibration_guards_degenerate_throughput(self):
        from repro.core.advisor import calibrated_system
        from repro.core.systems import DIE_STACKED
        for bad in (0.0, -5.0, float("nan"), float("inf")):
            with pytest.raises(ValueError, match="degenerate"):
                calibrated_system(DIE_STACKED, bad)
        ok = calibrated_system(DIE_STACKED, 8e9)
        assert ok.chip_peak_perf == pytest.approx(8e9)


class TestLegacyWrappers:
    """db.queries routes through the same execution path."""

    def test_scan_query_mask_layout(self, table, decoded):
        from repro.db.queries import scan_query
        from repro.kernels.scan_filter.ref import unpack_mask
        mask = scan_query(table, [Pred("a", "lt", 50), Pred("w", "ge", 9000)])
        sel = np.asarray(unpack_mask(mask, 8))[:N_ROWS]
        np.testing.assert_array_equal(
            sel, (decoded["a"] < 50) & (decoded["w"] >= 9000))

    def test_tail_padding_never_matches(self):
        """Seed bug: pack() tail codes (value 0) matched lt/le predicates."""
        from repro.db.queries import scan_aggregate_query
        t = Table.synthetic("tail", 10, {"a": 8, "b": 8}, seed=0)
        av, bv = t.columns["a"].decode(), t.columns["b"].decode()
        r = scan_aggregate_query(t, [Pred("a", "le", 127)], "b")
        assert int(r["count"]) == 10          # not 12 (2 pad codes)
        assert int(r["sum"]) == int(bv.sum())
