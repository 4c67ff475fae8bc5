"""The generator, the work count, the reference and the peaks table, on
the CPU at small sizes."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import queries, reference, run, tpch

ROOT = Path(__file__).resolve().parents[2]
SF001_ROWS = 60175           # lineitem rows at SF 0.01


def small(config_name: str, rows: int) -> dict:
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      f"{config_name}.json").read_text())
    return dict(cfg, rows=rows)


@pytest.fixture(scope="module")
def sf001():
    return tpch.generate(small("lineitem_sf100", SF001_ROWS), 20261016)


def template(traffic: str) -> dict:
    return queries.load_traffic(traffic)["queries"][0]


def test_row_count_and_code_ranges(sf001):
    cfg = small("lineitem_sf100", SF001_ROWS)
    assert set(sf001) == set(cfg["columns"])
    for name, codes in sf001.items():
        assert codes.size == SF001_ROWS
        assert codes.max() < 1 << (cfg["columns"][name] - 1)
    assert 1 <= sf001["l_shipdate"].min() and sf001["l_shipdate"].max() \
        <= 2526
    assert sf001["l_quantity"].min() == 1 and sf001["l_quantity"].max() == 50
    assert sf001["l_discount"].max() == 10 and sf001["l_tax"].max() == 8


def test_lines_share_their_order_date():
    cfg = small("lineitem_sf100", 5000)
    lines = tpch.lines_per_order(cfg, 3)
    assert lines.sum() == 5000 and lines.min() >= 1 and lines.max() <= 7
    codes = tpch.generate(cfg, 3)
    # each order's lines ship within 121 days of one shared order date
    starts = np.concatenate([[0], np.cumsum(lines, dtype=np.int64)[:-1]])
    spread = (np.maximum.reduceat(codes["l_shipdate"], starts)
              - np.minimum.reduceat(codes["l_shipdate"], starts))
    assert spread.max() <= 120


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 7])
def test_same_seed_same_table(seed):
    cfg = small("lineitem_sf100", 3000)
    a, b = tpch.generate(cfg, seed), tpch.generate(cfg, seed)
    assert all(np.array_equal(a[c], b[c]) for c in a)
    other = tpch.generate(cfg, seed + 1)
    assert not np.array_equal(a["l_quantity"], other["l_quantity"])


def test_q6_selectivity(sf001):
    sel = reference.selection(template("q6_power")["where"], sf001)
    assert 0.015 <= sel.mean() <= 0.025


def test_q1_selectivity_and_groups(sf001):
    t = template("q1_power")
    sel = reference.selection(t["where"], sf001)
    assert 0.97 <= sel.mean() <= 0.99
    ans = reference.answer(t, sf001)
    assert sorted(ans["groups"]) == [0, 1, 2, 3]
    assert ans["count"] == int(sel.sum())


def test_rfls_follows_the_dates(sf001):
    """AF/RF only where the line was received by the current date, NO
    only where it shipped after it."""
    ship, rfls = sf001["l_shipdate"], sf001["l_rfls"]
    cur = 1263
    assert (ship[rfls == tpch.RFLS.index("NO")] > cur).all()
    assert (ship[np.isin(rfls, [0, 1, 3])] <= cur).all()


@pytest.mark.parametrize("traffic,want", [
    # 16-bit shipdate: ceil(rows/2) words; 8-bit columns: ceil(rows/4)
    ("q6_power", 300018951 * 4 + 2 * 150009476 * 4),
    ("q1_power", 300018951 * 4 + 4 * 150009476 * 4),
])
def test_needed_bytes_hand_counts(traffic, want):
    cfg = small("lineitem_sf100", 600037902)
    assert queries.template_bytes(template(traffic), cfg) == want


def test_needed_bytes_counts_a_column_once():
    cols = {"a": 8, "b": 16}
    assert tpch.needed_bytes(cols, 10, ["a", "a", "b"]) == 3 * 4 + 5 * 4


@pytest.mark.parametrize("traffic", ["q6_power", "q1_power"])
def test_reference_matches_engine_xla_ref(sf001, traffic):
    from repro.db.columnar import BitPackedColumn, Table
    from repro.launch.mesh import make_mesh
    from repro.query import QueryEngine, ShardedTable
    cfg = small("lineitem_sf100", SF001_ROWS)
    table = Table("t")
    for name, bits in cfg["columns"].items():
        table.add(BitPackedColumn.from_values(name, sf001[name], bits))
    eng = QueryEngine(ShardedTable.shard(table, make_mesh((1,), ("data",))),
                      mode="xla_ref")
    t = template(traffic)
    eng.submit(queries.build(t))
    (res,) = eng.run()
    want = reference.answer(t, sf001)
    assert reference.gap(res.aggregates, want) == 0
    assert res.aggregates == want


def test_gap_reads_every_field():
    want = {"a": {"sum": 10, "count": 2, "min": 1, "max": 9}}
    assert reference.gap(want, want) == 0
    assert reference.gap({"a": dict(want["a"], max=12)}, want) == 3
    assert reference.gap(None, want) == 10       # an answer that never came
    assert reference.gap({"a": dict(want["a"], extra=0)}, want) == 1


def test_missing_device_kind_raises():
    assert run.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.device_peaks("TPU v99")
