"""Grouped aggregation & hash join tests (ISSUE 8).

Parity contract: GroupBy/HashJoin produce results bit-identical to the
numpy oracle under PALLAS, XLA_REF, and AUTO — on plain tables, over the
compressed store (all three per-chunk strategies: fused RLE, dense
accumulator planes, host sort/hash fallback), and through the tiered
engine. The fused RLE path must stay ONE batched launch with no scatter
and no fallback; grouped queries must charge physical bytes into the
tier and energy ledgers like any scan.
"""
import numpy as np
import pytest

from repro.db.columnar import BitPackedColumn, Table
from repro.kernels.group_aggregate import ops as gops
from repro.obs.metrics import default_registry
from repro.query import GroupBy, HashJoin, Pred, QueryEngine
from repro.query import relational
from repro.query.plan import And
from repro.serve.sla import VirtualClock
from repro.store import EncodedTable
from repro.store.exec import execute_grouped_encoded
from repro.tier.placement import PlacementEngine, Policy
from repro.tier.tiers import paper_tiers

MODES = ("pallas", "xla_ref")
N_ROWS = 6001          # ragged vs every codes-per-word and the chunking
CHUNK_ROWS = 1024


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(3)
    t = Table("t")
    t.add(BitPackedColumn.from_values(          # sorted low-card -> RLE
        "r", np.sort(rng.integers(0, 8, N_ROWS)), 8))
    t.add(BitPackedColumn.from_values(          # clustered -> FOR
        "f", 40 + rng.integers(0, 8, N_ROWS), 8))
    t.add(BitPackedColumn.from_values(          # 16-bit clustered -> FOR
        "w", 9000 + rng.integers(0, 100, N_ROWS), 16))
    t.add(BitPackedColumn.from_values(          # uniform -> plain
        "u", rng.integers(0, 128, N_ROWS), 8))
    return t


@pytest.fixture(scope="module")
def encoded(table):
    return EncodedTable.from_table(table, chunk_rows=CHUNK_ROWS)


@pytest.fixture(scope="module")
def dim():
    d = Table("dim")
    d.add(BitPackedColumn.from_values("r", np.array([1, 3, 5, 99]), 8))
    d.add(BitPackedColumn.from_values("u", np.array([2, 7, 50, 90]), 8))
    return d


def _np_grouped(table, key, aggs, sel):
    """Independent numpy ground truth (no repro.query.relational code)."""
    cols = {n: c.decode().astype(np.int64)
            for n, c in table.columns.items()}
    k = cols[key][sel]
    groups = {}
    for kv in np.unique(k):
        m = sel & (cols[key] == kv)
        groups[int(kv)] = {
            "count": int(m.sum()),
            "sums": {a: int(cols[a][m].sum()) for a in sorted(aggs)}}
    return {"groups": groups, "count": int(sel.sum())}


# --------------------------------------------------------------------------
# bind / error paths
# --------------------------------------------------------------------------

def test_groupby_unknown_column_raises(table):
    with pytest.raises(ValueError, match="zz"):
        relational.execute_grouped(GroupBy("zz"), table)
    with pytest.raises(ValueError, match="zz"):
        relational.execute_grouped(GroupBy("r", ("zz",)), table)
    with pytest.raises(ValueError, match="zz"):
        relational.execute_grouped(
            GroupBy("r", where=Pred("zz", "lt", 3)), table)


def test_groupby_aggregate_over_key_raises():
    with pytest.raises(ValueError, match="group key"):
        GroupBy("r", ("r",))


def test_groupby_multi_key_raises():
    with pytest.raises(ValueError, match="one group-key"):
        GroupBy(("r", "u"))


def test_join_build_side_missing_column_raises(table):
    with pytest.raises(ValueError, match="no column"):
        HashJoin(table, "r", "zz")


def test_join_key_width_mismatch_names_both_sides(table, dim):
    # probe "w" is 16-bit, build "r" is 8-bit
    j = HashJoin(dim, "w", "r")
    with pytest.raises(ValueError) as e:
        relational.bind_check(j, table.columns)
    msg = str(e.value)
    assert "16-bit" in msg and "8-bit" in msg
    assert "'w'" in msg and "'r'" in msg


def test_engine_submit_runs_bind_checks(table, dim):
    eng = QueryEngine(table)
    with pytest.raises(ValueError, match="zz"):
        eng.submit(GroupBy("zz"))
    with pytest.raises(ValueError, match="width mismatch"):
        eng.submit(HashJoin(dim, "w", "r"))


# --------------------------------------------------------------------------
# plain-table parity (dense strategy + wide-key fallback)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_groupby_plain_matches_numpy(table, mode):
    q = GroupBy("r", ("u", "f"), where=Pred("u", "lt", 90))
    cols = {n: c.decode().astype(np.int64)
            for n, c in table.columns.items()}
    want = _np_grouped(table, "r", ("u", "f"), cols["u"] < 90)
    assert relational.execute_grouped(q, table, mode=mode) == want
    assert relational.execute_grouped_oracle(q, table) == want


@pytest.mark.parametrize("mode", MODES)
def test_groupby_mixed_width_predicate(table, mode):
    # 8-bit key grouped under a 16-bit predicate: the unpacked planes
    # have different padded lengths and must land on one row axis
    q = GroupBy("r", ("u",), where=And((Pred("w", "ge", 9030),
                                        Pred("f", "lt", 45))))
    assert relational.execute_grouped(q, table, mode=mode) \
        == relational.execute_grouped_oracle(q, table)


@pytest.mark.parametrize("mode", MODES)
def test_hash_join_semantics(table, dim, mode):
    # probe keys restricted to the build side's distinct keys; key 99
    # never occurs in the fact table and must not appear as a group
    j = HashJoin(dim, "r", "r", aggs=("u",), where=Pred("f", "lt", 46))
    got = relational.execute_grouped(j, table, mode=mode)
    cols = {n: c.decode().astype(np.int64)
            for n, c in table.columns.items()}
    sel = (cols["f"] < 46) & np.isin(cols["r"], [1, 3, 5, 99])
    assert got == _np_grouped(table, "r", ("u",), sel)
    assert set(got["groups"]) <= {1, 3, 5}


def test_count_only_histogram(table):
    got = relational.execute_grouped(GroupBy("r"), table)
    r = table.columns["r"].decode()
    assert got["count"] == N_ROWS
    for k, g in got["groups"].items():
        assert g["count"] == int((r == k).sum()) and g["sums"] == {}


def test_empty_selection_and_zero_rows(table):
    q = GroupBy("r", ("u",), where=Pred("u", "gt", 127))
    assert relational.execute_grouped(q, table) \
        == relational.empty_result()
    empty = Table("e")
    empty.add(BitPackedColumn.from_values("r", np.zeros(0, np.int64), 8))
    assert relational.execute_grouped(GroupBy("r"), empty) \
        == relational.empty_result()


def test_wide_key_takes_fallback_and_matches(table):
    # 16-bit key spans ~100 codes > nothing, but force the cliff: shrink
    # the dense cutoff, the documented strategy knob
    q = GroupBy("w", ("u",))
    want = relational.execute_grouped_oracle(q, table)
    saved = relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS
    try:
        relational.DENSE_MAX_GROUPS = gops.DENSE_MAX_GROUPS = 4
        before = dict(default_registry().launch_counts())
        got = relational.execute_grouped(q, table)
    finally:
        relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS = saved
    delta = {k: v - before.get(k, 0)
             for k, v in default_registry().launch_counts().items()}
    assert delta.get("group_aggregate_fallback", 0) >= 1
    assert delta.get("group_aggregate", 0) == 0
    assert got == want == relational.execute_grouped(q, table)


# --------------------------------------------------------------------------
# encoded store: the three per-chunk strategies
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_encoded_grouped_parity(table, encoded, mode):
    for q in (GroupBy("r", ("u", "f")),
              GroupBy("f", ("w",), where=Pred("u", "lt", 64)),
              GroupBy("r"),                         # count-only: RLE path
              GroupBy("r", where=Pred("r", "le", 4))):
        assert execute_grouped_encoded(q, encoded, mode=mode) \
            == relational.execute_grouped_oracle(q, table), q


def test_rle_pregrouped_is_one_launch_no_scatter(table, encoded):
    """The ISSUE's launch-observability acceptance: a count-only GroupBy
    on the RLE key takes ONE batched run-accumulation launch — no dense
    plane, no host fallback."""
    q = GroupBy("r", where=Pred("r", "lt", 6))
    execute_grouped_encoded(q, encoded, mode="xla_ref")     # warm
    before = dict(default_registry().launch_counts())
    got = execute_grouped_encoded(q, encoded, mode="xla_ref")
    delta = {k: v - before.get(k, 0)
             for k, v in default_registry().launch_counts().items()
             if v != before.get(k, 0)}
    assert delta == {"group_aggregate_rle": 1}, delta
    assert got == relational.execute_grouped_oracle(q, table)


def test_encoded_forced_fallback_parity(table, encoded):
    q = GroupBy("r", ("u",))
    want = relational.execute_grouped_oracle(q, table)
    saved = relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS
    try:
        relational.DENSE_MAX_GROUPS = gops.DENSE_MAX_GROUPS = 0
        before = dict(default_registry().launch_counts())
        got = execute_grouped_encoded(q, encoded, mode="xla_ref")
    finally:
        relational.DENSE_MAX_GROUPS, gops.DENSE_MAX_GROUPS = saved
    assert got == want
    delta = {k: v - before.get(k, 0)
             for k, v in default_registry().launch_counts().items()}
    assert delta.get("group_aggregate_fallback", 0) == encoded.n_chunks


@pytest.mark.parametrize("mode", ("pallas", "xla_ref"))
def test_encoded_join_parity(table, encoded, dim, mode):
    j = HashJoin(dim, "u", "u", aggs=("f",), where=Pred("r", "lt", 7))
    assert execute_grouped_encoded(j, encoded, mode=mode) \
        == relational.execute_grouped_oracle(j, table)


# --------------------------------------------------------------------------
# engine integration: routing + tier/energy accounting
# --------------------------------------------------------------------------

def test_engine_grouped_result_shape(table, dim):
    eng = QueryEngine(table)
    q = GroupBy("r", ("u",), where=Pred("u", "lt", 90))
    eng.submit(q)
    (r,) = eng.run()
    want = relational.execute_grouped_oracle(q, table)
    assert r.aggregates == want and r.count == want["count"]
    assert r.bytes_scanned == eng.bytes_scanned(q) > 0
    eng.submit(HashJoin(dim, "r", "r", aggs=("u",)))
    (r,) = eng.run()
    assert r.aggregates == relational.execute_grouped_oracle(
        HashJoin(dim, "r", "r", aggs=("u",)), table)


def test_grouped_charges_tier_and_energy(table, encoded):
    """A grouped query streams physical (compressed) bytes through the
    placement engine and lands on the energy ledger, same as a scan."""
    clock = VirtualClock()
    pe = PlacementEngine.for_table(
        encoded, paper_tiers(max(1, encoded.nbytes // 2)), Policy.CACHE,
        chunk_rows=CHUNK_ROWS)
    eng = QueryEngine(encoded, clock=clock, tiered=pe)
    q = GroupBy("r", ("u",), where=Pred("f", "lt", 45))
    eng.submit(q, deadline=clock() + 100.0)
    (r,) = eng.run()
    assert r.aggregates == relational.execute_grouped_oracle(q, table)
    assert r.tier is not None and r.tier["service_s"] > 0
    assert r.tier["energy_j"] > 0
    s = eng.summary()
    assert s["bytes_scanned"] == r.bytes_scanned > 0
    # physical bytes: the compressed footprint of r+u+f, not the logical
    assert r.bytes_scanned < r.logical_bytes
    assert s["energy"]["total_j"] > 0


@pytest.mark.parametrize("slab_rows", [1024, 1 << 24])
@pytest.mark.parametrize("mode", ("pallas", "xla_ref"))
def test_sharded_grouped_slabs_match_numpy(table, monkeypatch, slab_rows,
                                           mode):
    """The sharded grouped path unpacks one slab of rows per kernel launch
    and merges the normalized planes on the device: several slabs plus a
    ragged tail give the same answer as one slab and as numpy."""
    from repro.launch.mesh import make_mesh
    from repro.obs.metrics import MetricsRegistry, scoped
    from repro.query import sharded
    monkeypatch.setattr(sharded, "GROUP_SLAB_ROWS", slab_rows)
    st = sharded.ShardedTable.shard(table, make_mesh((1,), ("data",)))
    q = GroupBy("u", ("w", "f"), where=Pred("w", "lt", 9050))
    sel = table.columns["w"].decode() < 9050
    reg = MetricsRegistry("slabs")
    with scoped(reg):
        got = st.execute_grouped(q, mode=mode)
    assert got == _np_grouped(table, "u", ("w", "f"), sel)
    # "w" is 16-bit, the key 8-bit: the slab path, not the packed one
    assert reg.counter("grouped_slabs").value == 1
    assert reg.counter("grouped_packed").value == 0


def _same_width_queries(dim):
    """Grouped queries whose key and value columns are all 8-bit, under
    predicates at 8 and 16 bits, with the rows each selects."""
    return {
        "groupby_16bit_pred": (
            GroupBy("r", ("u", "f"), where=Pred("w", "lt", 9050)),
            lambda c: c["w"] < 9050),
        "groupby_no_where": (GroupBy("u", ("r",)), lambda c: c["u"] >= 0),
        "count_only": (GroupBy("r", where=Pred("f", "ge", 44)),
                       lambda c: c["f"] >= 44),
        "join": (HashJoin(dim, "u", "u", aggs=("f", "r"),
                          where=Pred("w", "ge", 9020) | Pred("r", "lt", 2)),
                 lambda c: ((c["w"] >= 9020) | (c["r"] < 2))
                 & np.isin(c["u"], [2, 7, 50, 90])),
    }


@pytest.mark.parametrize("name", ["groupby_16bit_pred", "groupby_no_where",
                                  "count_only", "join"])
@pytest.mark.parametrize("mode", ("pallas", "xla_ref"))
def test_sharded_grouped_packed_match_numpy(table, dim, name, mode):
    """Key and value columns at one code width: the sharded grouped path
    groups the packed words in one kernel launch, under the plan's mask
    built in the key's layout, with numpy's answers."""
    from repro.launch.mesh import make_mesh
    from repro.obs.metrics import MetricsRegistry, scoped
    from repro.query import sharded
    st = sharded.ShardedTable.shard(table, make_mesh((1,), ("data",)))
    q, mksel = _same_width_queries(dim)[name]
    cols = {n: c.decode().astype(np.int64)
            for n, c in table.columns.items()}
    reg = MetricsRegistry("packed")
    with scoped(reg):
        got = st.execute_grouped(q, mode=mode)
    assert got == _np_grouped(table, q.key, q.aggs, mksel(cols))
    assert reg.counter("grouped_packed").value == 1
    assert reg.counter("grouped_slabs").value == 0
