"""The fused conjunctive scan+aggregate (kernels/scan_aggregate
`scan_conjunction`) and the plan shapes `physical.execute` routes to it.

Every case answers like the numpy oracle and like the mask path it
replaces (`eval_mask` + one `aggregate` per column), field for field, in
interpret-mode Pallas and in the jnp oracle mode. The sharded psum over
several devices is `tests/multidevice_child.py`'s `conjunction` check.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.db.columnar import BitPackedColumn, Table
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.scan_aggregate import ops as fused_ops
from repro.kernels.scan_filter.kernel import TILE_WORDS
from repro.launch.mesh import make_mesh
from repro.query import And, Or, Pred, Query, QueryEngine
from repro.query import physical
from repro.query.sharded import ShardedTable

MAX = "max"          # stands for the width's payload max in a case
SIX_TILES = "six tiles"   # rows filling six kernel tiles, less a few


def _cases():
    """id -> (rows, leaves, aggregates); a leaf's constant MAX is the
    payload max, an int is taken modulo it; rows SIX_TILES fill six
    kernel tiles at the case's width, less 77."""
    rows = 5_001                         # a part word at every width
    out = {f"one_leaf_{op}": (rows, (("a", op, 45),), ("b",))
           for op in ("lt", "le", "gt", "ge", "eq", "ne")}
    out.update({
        "gt_at_max": (rows, (("a", "gt", MAX),), ("a", "b")),
        "le_at_max": (rows, (("a", "le", MAX),), ("b",)),
        "le_at_max_among_others": (rows, (("a", "lt", 70), ("c", "le", MAX),
                                          ("b", "ge", 9)), ("a", "c")),
        "gt_at_max_among_others": (rows, (("a", "lt", 70), ("b", "gt", MAX)),
                                   ("b",)),
        "two_leaves_one_column": (rows, (("a", "ge", 20), ("a", "lt", 90)),
                                  ("a",)),
        "five_leaves_three_columns": (rows, (("a", "ge", 20),
                                             ("a", "lt", 90),
                                             ("b", "ge", 5), ("b", "le", 70),
                                             ("c", "lt", 100)),
                                      ("b", "c")),
        "three_aggregates": (rows, (("a", "ne", 3), ("c", "eq", 11)),
                             ("a", "b", "c")),
        "zero_rows_selected": (rows, (("a", "lt", 10), ("a", "ge", 60)),
                               ("b", "c")),
        # six (256, 128)-word reduction tiles: two grid steps of three,
        # and a ragged validity tail in the last word
        "many_tiles_ragged": (SIX_TILES,
                              (("a", "ge", 7), ("b", "lt", 100),
                               ("c", "ne", 0)), ("a", "c")),
    })
    return out


CASES = _cases()


def _table(rows: int, bits: int, seed: int) -> Table:
    rng = np.random.default_rng(seed)
    vmax = (1 << (bits - 1)) - 1
    t = Table("t")
    for name in "abc":
        # codes over [0, 127] at both widths, plus the width's max, so
        # every leaf selects some rows and some not
        codes = rng.integers(0, 128, rows)
        codes[rng.integers(0, rows, 50)] = vmax
        t.add(BitPackedColumn.from_values(name, codes, bits))
    return t


def _constant(c, bits: int) -> int:
    vmax = (1 << (bits - 1)) - 1
    return vmax if c == MAX else c % vmax


def _numpy(table: Table, leaves, aggs, bits: int) -> dict:
    codes = {n: table.columns[n].decode().astype(np.int64)
             for n in table.columns}
    cmp = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
           "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}
    sel = np.ones(table.num_rows, bool)
    for col, op, c in leaves:
        sel &= cmp[op](codes[col], c)
    vmax = (1 << (bits - 1)) - 1
    return {a: {"sum": int(codes[a][sel].sum()), "count": int(sel.sum()),
                "min": int(codes[a][sel].min(initial=vmax)),
                "max": int(codes[a][sel].max(initial=0))} for a in aggs}


def _finalize(out: dict) -> dict:
    return {a: agg_ops.finalize(d) for a, d in agg_ops.fetch(out).items()}


@pytest.mark.parametrize("mode", ("pallas", "xla_ref"))
@pytest.mark.parametrize("bits", (8, 16))
@pytest.mark.parametrize("case", sorted(CASES))
def test_conjunction_matches_numpy_and_mask_path(case, bits, mode):
    rows, leaves, aggs = CASES[case]
    if rows == SIX_TILES:
        rows = 6 * TILE_WORDS * (32 // bits) - 77
    leaves = tuple((col, op, _constant(c, bits)) for col, op, c in leaves)
    table = _table(rows, bits, seed=rows + bits)
    slices = physical.table_slices(table)
    got = _finalize(fused_ops.scan_conjunction(
        {n: s.words for n, s in slices.items()}, slices["a"].valid, leaves,
        aggs, bits, mode=mode))
    plan = And.of(*(Pred(*leaf) for leaf in leaves))
    mask, _ = physical.eval_mask(plan, slices, mode)
    masked = _finalize({a: agg_ops.aggregate(slices[a].words, mask, bits,
                                             mode=mode) for a in aggs})
    want = _numpy(table, leaves, aggs, bits)
    assert got == masked == want


Q6 = Query(And.of(Pred("l_shipdate", "ge", 731),
                  Pred("l_shipdate", "lt", 1096),
                  Pred("l_discount", "ge", 5), Pred("l_discount", "le", 7),
                  Pred("l_quantity", "lt", 24)),
           ("l_discount", "l_quantity"))
ONE_WIDTH = {"l_shipdate": 16, "l_quantity": 16, "l_discount": 16}
NARROW = {"l_shipdate": 16, "l_quantity": 8, "l_discount": 8}
ROUTES = {
    # query, widths, (scan_fused, scan_masked), launches of one query
    "q6_one_width": (Q6, ONE_WIDTH, (1, 0), {"scan_aggregate": 1}),
    "single_pred": (Query(Pred("l_quantity", "lt", 24), ("l_discount",)),
                    ONE_WIDTH, (1, 0), {"scan_aggregate": 1}),
    "q6_narrow": (Q6, NARROW, (0, 1),
                  {"scan_filter": 5, "mask_repack": 1, "aggregate": 2}),
    "or_one_width": (Query(Or.of(Pred("l_quantity", "lt", 5),
                                 Pred("l_discount", "eq", 3)),
                           ("l_discount",)),
                     ONE_WIDTH, (0, 1), {"scan_filter": 2, "aggregate": 1}),
    "and_over_or": (Query(And.of(Pred("l_shipdate", "ge", 731),
                                 Or.of(Pred("l_quantity", "lt", 5),
                                       Pred("l_discount", "eq", 3))),
                          ("l_quantity",)),
                    ONE_WIDTH, (0, 1), {"scan_filter": 3, "aggregate": 1}),
}


@pytest.mark.parametrize("placed", ("flat", "sharded"))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_plan_shape_and_layout_pick_the_path(route, placed):
    """A Pred or an And of Preds whose columns share one layout takes the
    one-pass kernel (`scan_fused` 1, one `scan_aggregate` launch a
    query); an Or, an And over a subtree, or mixed widths build masks
    (`scan_masked` 1). The answers equal the jnp oracle mode's."""
    q, widths, (fused, masked), launches = ROUTES[route]
    rng = np.random.default_rng(31)
    t = Table("lineitem")
    for name, hi in (("l_shipdate", 2526), ("l_quantity", 50),
                     ("l_discount", 10)):
        t.add(BitPackedColumn.from_values(name, rng.integers(0, hi + 1,
                                                             9_999),
                                          widths[name]))
    table = (t if placed == "flat"
             else ShardedTable.shard(t, make_mesh((1,), ("data",))))
    eng = QueryEngine(table, mode="pallas")
    eng.submit(q)
    got = eng.run()[0].aggregates
    counters = (eng.metrics.counter("scan_fused").value,
                eng.metrics.counter("scan_masked").value)
    assert counters == (fused, masked)
    assert eng.metrics.launch_counts() == launches
    want = _finalize(physical.execute(q.plan(), q.aggregates,
                                      physical.table_slices(t),
                                      mode="xla_ref"))
    assert got == want

