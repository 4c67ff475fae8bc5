"""Compressed store tests: encodings, selector, scan-over-compressed
parity, byte accounting, tier interplay, and the compression axis of the
decision surface.

Parity contract (ISSUE 5): every encoding and query shape produces
results bit-identical to the plain-format engine under PALLAS, XLA_REF,
and AUTO, including through the sharded delta view — and every path
returns the same empty-selection identity (count=0, sum=0, min=vmax,
max=0 at the logical width).
"""
import numpy as np
import pytest

from repro.db.columnar import BitPackedColumn, Table
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.scan_compressed import ops as rle_ops
from repro.kernels.scan_compressed import ref as rle_ref
from repro.launch.mesh import make_mesh
from repro.obs.metrics import default_registry
from repro.query import And, Or, Pred, Query, QueryEngine
from repro.store import (EncodedTable, Encoding, ShardedEncodedTable,
                         encode_chunk, execute_encoded)
from repro.store.exec import fixup_base, identity_ints, translate_pred

MODES = ("pallas", "xla_ref")

# 6001 rows: not a multiple of any codes-per-word or the chunking, so
# every column carries tail padding in its last chunk
N_ROWS = 6001
CHUNK_ROWS = 1024


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(3)
    t = Table("t")
    t.add(BitPackedColumn.from_values(          # sorted low-card -> RLE
        "r", np.sort(rng.integers(0, 8, N_ROWS)), 8))
    t.add(BitPackedColumn.from_values(          # clustered -> FOR at 4
        "f", 40 + rng.integers(0, 8, N_ROWS), 8))
    t.add(BitPackedColumn.from_values(          # 16-bit clustered -> FOR
        "w", 9000 + rng.integers(0, 100, N_ROWS), 16))
    t.add(BitPackedColumn.from_values(          # uniform -> plain
        "u", rng.integers(0, 128, N_ROWS), 8))
    t.add(BitPackedColumn.from_values(          # narrow width
        "x", rng.integers(0, 8, N_ROWS), 4))
    return t


@pytest.fixture(scope="module")
def encoded(table):
    return EncodedTable.from_table(table, chunk_rows=CHUNK_ROWS)


@pytest.fixture(scope="module")
def decoded(table):
    return {c: table.columns[c].decode() for c in table.columns}


# --------------------------------------------------------------------------
# encodings & selector
# --------------------------------------------------------------------------
class TestEncoding:
    def test_roundtrip_every_column(self, table, encoded):
        for name, col in table.columns.items():
            np.testing.assert_array_equal(encoded.columns[name].decode(),
                                          col.decode())

    def test_selector_picks_the_expected_formats(self, encoded):
        assert set(encoded.columns["r"].encodings().items()) >= \
            {("rle", len(encoded.columns["r"].chunks))}
        assert encoded.columns["f"].encodings()["for"] > 0
        assert encoded.columns["w"].encodings()["for"] > 0
        assert encoded.columns["u"].encodings()["plain"] == \
            len(encoded.columns["u"].chunks)

    def test_never_larger_than_plain(self, encoded):
        for col in encoded.columns.values():
            for ch in col.chunks:
                assert ch.nbytes <= ch.stats.plain_nbytes, (col.name,
                                                            ch.encoding)
        assert encoded.nbytes < encoded.logical_nbytes
        assert encoded.ratio > 1.5

    def test_forced_encoding_roundtrip(self):
        codes = np.asarray([5, 5, 5, 9, 9, 0, 1, 2, 3], np.uint32)
        for enc in Encoding:
            ch = encode_chunk(codes, 8, enc)
            assert ch.encoding is enc
            np.testing.assert_array_equal(ch.decode(), codes)

    def test_for_chunk_packs_at_narrower_width(self):
        ch = encode_chunk(1000 + np.arange(8, dtype=np.uint32), 16)
        assert ch.encoding is Encoding.FOR
        assert ch.width == 4 and ch.base == 1000

    def test_oversized_chunk_rejected(self):
        with pytest.raises(ValueError, match="MAX_CHUNK_ROWS"):
            encode_chunk(np.zeros(70000, np.uint32), 8)

    def test_too_wide_codes_rejected(self):
        with pytest.raises(ValueError, match="payload max"):
            encode_chunk(np.asarray([300], np.uint32), 8)

    def test_unknown_pinned_column_rejected(self, table):
        with pytest.raises(ValueError, match="unknown column"):
            EncodedTable.from_table(table, encodings={"nope": Encoding.RLE})

    def test_zero_row_chunk_every_encoding(self):
        for enc in (None, *Encoding):
            ch = encode_chunk(np.zeros(0, np.uint32), 8, enc)
            assert ch.n_rows == 0 and ch.nbytes == 0
            assert ch.decode().size == 0

    def test_placement_chunking_must_match_store(self, encoded):
        col = next(iter(encoded.columns.values()))
        with pytest.raises(ValueError, match="multiple of the store"):
            col.chunk_physical_bytes(CHUNK_ROWS + 8)
        merged = col.chunk_physical_bytes(2 * CHUNK_ROWS)
        assert sum(merged) == col.nbytes


# --------------------------------------------------------------------------
# scan-over-compressed parity (the acceptance core)
# --------------------------------------------------------------------------
PLAN_SHAPES = [
    # (name, plan factory, aggregates) — every encoding x shape combo:
    ("rle_fused_self_agg", lambda: Pred("r", "lt", 4), ("r",)),
    ("rle_fused_eq", lambda: Pred("r", "eq", 3), ("r",)),
    ("rle_fused_ne", lambda: Pred("r", "ne", 3), ("r",)),
    ("rle_pred_other_agg", lambda: Pred("r", "ge", 6), ("f",)),
    ("for_fused_same_width", lambda: Pred("f", "ge", 44), ("f",)),
    ("for_cross_column", lambda: Pred("f", "lt", 44), ("w",)),
    ("for16_pred", lambda: Pred("w", "ge", 9050), ("u",)),
    ("plain_pred_for_agg", lambda: Pred("u", "lt", 64), ("w",)),
    ("and_mixed_encodings",
     lambda: Pred("f", "ge", 42) & Pred("w", "lt", 9080), ("w", "x")),
    ("or_mixed_widths",
     lambda: Pred("x", "eq", 3) | Pred("w", "lt", 9010), ("u",)),
    ("nested_and_or",
     lambda: And.of(Or.of(Pred("r", "le", 2), Pred("u", "gt", 120)),
                    Pred("x", "ne", 0)), ("f",)),
    ("multi_agg_all_encodings", lambda: Pred("f", "ge", 43),
     ("r", "f", "w", "u", "x")),
    ("empty_selection_rle", lambda: Pred("r", "gt", 7), ("r",)),
    ("empty_selection_for", lambda: Pred("f", "lt", 40), ("w",)),
    ("all_match_for", lambda: Pred("w", "ge", 0), ("w",)),
    ("below_frame_constant", lambda: Pred("w", "lt", 5), ("w",)),
]


@pytest.mark.parametrize("name,mkplan,aggs", PLAN_SHAPES,
                         ids=[p[0] for p in PLAN_SHAPES])
def test_encoded_matches_plain_all_modes(table, encoded, name, mkplan,
                                         aggs):
    q = Query(mkplan(), aggregates=aggs)
    got_by_mode = {}
    for mode in MODES:
        e_plain = QueryEngine(table, mode=mode)
        e_comp = QueryEngine(encoded, mode=mode)
        e_plain.submit(q)
        e_comp.submit(q)
        want, got = e_plain.run()[0], e_comp.run()[0]
        assert got.aggregates == want.aggregates, (name, mode)
        assert got.count == want.count
        got_by_mode[mode] = got.aggregates
    assert got_by_mode["pallas"] == got_by_mode["xla_ref"]


@pytest.mark.parametrize("name,mkplan,aggs", PLAN_SHAPES,
                         ids=[p[0] for p in PLAN_SHAPES])
def test_sharded_encoded_matches_plain(table, encoded, name, mkplan, aggs):
    """1-device mesh in-process; the 8-device run lives in
    tests/multidevice_child.py (device count locks at first jax init)."""
    st = ShardedEncodedTable.shard(encoded, make_mesh((1,), ("data",)))
    q = Query(mkplan(), aggregates=aggs)
    for mode in ("pallas", "xla_ref"):
        e_plain = QueryEngine(table, mode=mode)
        e_shard = QueryEngine(st, mode=mode)
        e_plain.submit(q)
        e_shard.submit(q)
        assert e_shard.run()[0].aggregates == e_plain.run()[0].aggregates, \
            (name, mode)


def test_sharded_view_is_compressed(encoded):
    st = ShardedEncodedTable.shard(encoded, make_mesh((1,), ("data",)))
    assert st.nbytes < sum(c.logical_nbytes
                           for c in encoded.columns.values())
    assert st.n_shards == 1 and st.num_rows == encoded.num_rows


# --------------------------------------------------------------------------
# empty-selection / zero-row identities (satellite)
# --------------------------------------------------------------------------
class TestIdentities:
    def test_identity_constants(self):
        assert identity_ints(8) == {"sum": 0, "count": 0, "min": 127,
                                    "max": 0}

    def test_rle_kernel_empty_runs(self):
        for mode in MODES:
            d = rle_ops.rle_scan_aggregate(
                np.zeros(0, np.int32), np.zeros(0, np.int32), 3, "lt", 8,
                mode=mode)
            assert agg_ops.finalize(d) == identity_ints(8)

    def test_rle_kernel_no_match(self):
        v = np.asarray([5, 9, 5], np.int32)
        l = np.asarray([4, 4, 4], np.int32)
        for mode in MODES:
            d = rle_ops.rle_scan_aggregate(v, l, 100, "gt", 8, mode=mode)
            assert agg_ops.finalize(d) == identity_ints(8)

    def test_fixup_never_leaks_delta_sentinel(self):
        """A FOR chunk's empty selection must collapse to the *logical*
        identity, not base + delta-domain sentinel."""
        delta_empty = {"sum": 0, "count": 0, "min": 7, "max": 0}  # 4-bit
        assert fixup_base(delta_empty, base=40, code_bits=8) == \
            identity_ints(8)

    def test_zero_row_encoded_table(self):
        t = Table("empty")
        t.add(BitPackedColumn.from_values("a", np.zeros(0, np.uint32), 8))
        t.add(BitPackedColumn.from_values("b", np.zeros(0, np.uint32), 8))
        et = EncodedTable.from_table(t)
        q = Query(Pred("a", "lt", 5), aggregates=("b",))
        for mode in ("pallas", "xla_ref"):
            eng = QueryEngine(et, mode=mode)
            eng.submit(q)
            res = eng.run()[0]
            assert res.aggregates["b"] == identity_ints(8)
            assert res.count == 0

    def test_empty_selection_identical_across_paths(self, table, encoded):
        """count=0 must produce bit-identical dicts on plain, encoded,
        and sharded-encoded paths under every mode."""
        q = Query(Pred("f", "lt", 40), aggregates=("f", "w"))
        st = ShardedEncodedTable.shard(encoded,
                                       make_mesh((1,), ("data",)))
        outs = []
        for tbl in (table, encoded, st):
            for mode in ("pallas", "xla_ref"):
                eng = QueryEngine(tbl, mode=mode)
                eng.submit(q)
                outs.append(eng.run()[0].aggregates)
        assert all(o == {"f": identity_ints(8), "w": identity_ints(16)}
                   for o in outs), outs


# --------------------------------------------------------------------------
# the scan_compressed kernel family
# --------------------------------------------------------------------------
class TestRLEKernel:
    @pytest.mark.parametrize("op", ("lt", "le", "gt", "ge", "eq", "ne"))
    def test_kernel_matches_ref_and_rows(self, op):
        rng = np.random.default_rng(5)
        v = rng.integers(0, 128, 300).astype(np.int32)
        l = rng.integers(0, 5, 300).astype(np.int32)   # zero-length runs
        rows = np.repeat(v, l)
        want_sel = {"lt": rows < 64, "le": rows <= 64, "gt": rows > 64,
                    "ge": rows >= 64, "eq": rows == 64,
                    "ne": rows != 64}[op]
        want = {
            "sum": int(rows[want_sel].sum()),
            "count": int(want_sel.sum()),
            "min": int(rows[want_sel].min()) if want_sel.any() else 127,
            "max": int(rows[want_sel].max()) if want_sel.any() else 0,
        }
        for mode in MODES:
            got = agg_ops.finalize(rle_ops.rle_scan_aggregate(
                v, l, 64, op, 8, mode=mode))
            assert got == want, (op, mode)

    def test_sum_exact_at_chunk_bound(self):
        """vmax runs filling a max chunk: the sum partial grazes int32."""
        v = np.full(64, 127, np.int32)
        l = np.full(64, 512, np.int32)          # 32768 rows of 127
        for mode in ("pallas", "xla_ref"):
            got = agg_ops.finalize(rle_ops.rle_scan_aggregate(
                v, l, 0, "ge", 8, mode=mode))
            assert got["sum"] == 127 * 32768 and got["count"] == 32768

    def test_block_rows_sweep_bit_exact(self):
        rng = np.random.default_rng(6)
        v = rng.integers(0, 8, 1000).astype(np.int32)
        l = rng.integers(1, 9, 1000).astype(np.int32)
        want = agg_ops.finalize(rle_ref.rle_scan_aggregate_ref(
            v, l, 4, "ge", 8))
        for br in (1, 2, 3, 8):
            got = agg_ops.finalize(rle_ops.rle_scan_aggregate(
                v, l, 4, "ge", 8, block_rows=br, mode="pallas"))
            assert got == want, br

    def test_bad_op_raises(self):
        with pytest.raises(ValueError, match="unknown predicate op"):
            rle_ops.rle_scan_aggregate(np.zeros(1, np.int32),
                                       np.ones(1, np.int32), 1, "like", 8)


# --------------------------------------------------------------------------
# plan translation into the delta domain
# --------------------------------------------------------------------------
class TestTranslation:
    @pytest.mark.parametrize("op", ("lt", "le", "gt", "ge", "eq", "ne"))
    def test_translation_semantics_exhaustive(self, op):
        """For every constant around and beyond the frame, the translated
        predicate selects exactly the rows the logical one does."""
        base, width = 40, 4                     # deltas 0..7 representable
        deltas = np.arange(8)
        codes = base + deltas
        fn = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
              "ge": np.greater_equal, "eq": np.equal,
              "ne": np.not_equal}[op]
        for c in range(0, 128):
            top, tc = translate_pred(op, c, base, width)
            want = fn(codes, c)
            got = {"lt": deltas < tc, "le": deltas <= tc,
                   "gt": deltas > tc, "ge": deltas >= tc,
                   "eq": deltas == tc, "ne": deltas != tc}[top]
            np.testing.assert_array_equal(got, want, err_msg=f"{op} {c}")

    def test_unknown_op_raises(self):
        with pytest.raises(ValueError, match="unknown predicate op"):
            translate_pred("like", 3, 0, 8)


# --------------------------------------------------------------------------
# byte accounting: physical vs logical
# --------------------------------------------------------------------------
class TestBytes:
    def test_physical_less_than_logical_on_compressed(self, encoded):
        eng = QueryEngine(encoded)
        eng.submit(Query(Pred("f", "ge", 44), aggregates=("w",)))
        res = eng.run()[0]
        assert 0 < res.bytes_scanned < res.logical_bytes
        s = eng.summary()
        assert s["logical_bytes"] > s["bytes_scanned"]
        assert s["effective_gbps"] > s["measured_gbps"] > 0

    def test_plain_table_logical_equals_physical(self, table):
        eng = QueryEngine(table)
        eng.submit(Query(Pred("u", "lt", 64), aggregates=("u",)))
        res = eng.run()[0]
        assert res.bytes_scanned == res.logical_bytes
        s = eng.summary()
        assert s["effective_gbps"] == s["measured_gbps"]

    def test_rle_column_physical_is_tiny(self, encoded):
        eng = QueryEngine(encoded)
        eng.submit(Query(Pred("r", "lt", 4), aggregates=("r",)))
        res = eng.run()[0]
        assert res.bytes_scanned < 0.05 * res.logical_bytes


# --------------------------------------------------------------------------
# tier placement over the compressed store
# --------------------------------------------------------------------------
class TestTier:
    def test_placement_universe_holds_physical_bytes(self, encoded):
        from repro.tier import PlacementEngine, Policy, paper_tiers
        tiers = paper_tiers(encoded.logical_nbytes * 0.25, fast_gbps=8.0)
        pe = PlacementEngine.for_table(encoded, tiers, Policy.STATIC,
                                       chunk_rows=CHUNK_ROWS)
        assert pe.total_bytes == encoded.nbytes

    def test_hit_rate_improves_at_fixed_capacity(self, table, encoded):
        """The acceptance bar: same absolute fast-tier bytes, strictly
        higher byte-weighted hit rate once chunks are compressed."""
        from repro.tier import (Policy, TraceSpec, make_trace, paper_tiers,
                                replay_trace)
        tiers = paper_tiers(table.nbytes * 0.25, fast_gbps=8.0)
        trace = make_trace(table, TraceSpec(n_queries=60, skew=1.1,
                                            seed=7))
        pe_p, _, _ = replay_trace(table, trace, tiers, Policy.CACHE,
                                  chunk_rows=CHUNK_ROWS)
        pe_e, eng_e, _ = replay_trace(encoded, trace, tiers, Policy.CACHE,
                                      chunk_rows=CHUNK_ROWS)
        assert pe_e.hit_rate > pe_p.hit_rate
        # the meter billed the *physical* bytes
        assert eng_e.summary()["energy"]["memory_j"] > 0
        assert (pe_e.fast_bytes_total + pe_e.capacity_bytes_total
                < pe_p.fast_bytes_total + pe_p.capacity_bytes_total)

    def test_sharded_encoded_tiered_runs(self, encoded):
        from repro.serve.sla import VirtualClock
        from repro.tier import PlacementEngine, Policy, paper_tiers
        st = ShardedEncodedTable.shard(encoded,
                                       make_mesh((1,), ("data",)))
        tiers = paper_tiers(st.nbytes * 0.25, fast_gbps=8.0)
        pe = PlacementEngine.for_table(st, tiers, Policy.CACHE,
                                       chunk_rows=CHUNK_ROWS)
        assert pe.total_bytes == st.nbytes
        eng = QueryEngine(st, mode="xla_ref", tiered=pe,
                          clock=VirtualClock())
        eng.submit(Query(Pred("f", "ge", 44), aggregates=("w",)))
        res = eng.run()[0]
        assert res.tier is not None and res.tier["service_s"] > 0


# --------------------------------------------------------------------------
# the compression axis of the decision surface
# --------------------------------------------------------------------------
class TestSurface:
    DB = 16 * (1 << 40)
    BPQ = 0.2 * 16 * (1 << 40)

    def test_ratio_one_reproduces_datasheet_verdict(self):
        from repro.energy.tco import cheapest_architecture
        base = cheapest_architecture(self.DB, self.BPQ, 0.010, 1e6)
        with_axis = cheapest_architecture(self.DB, self.BPQ, 0.010, 1e6,
                                          compression_ratio=1.0)
        assert base["winner"] == with_axis["winner"] == "die-stacked"
        assert with_axis["usd_per_query"] == base["usd_per_query"]
        loose = cheapest_architecture(self.DB, self.BPQ, 0.060, 1e6,
                                      compression_ratio=1.0)
        assert loose["winner"] == "traditional"

    def test_compression_flips_the_10ms_cell(self):
        from repro.energy.tco import cheapest_architecture
        flipped = cheapest_architecture(self.DB, self.BPQ, 0.010, 1e6,
                                        compression_ratio=8.0)
        assert flipped["winner"] == "traditional"
        win = next(c for c in flipped["candidates"]
                   if c["name"] == "traditional")
        assert win["compressed"] is True
        ds = next(c for c in flipped["candidates"]
                  if c["name"] == "die-stacked")
        assert ds["compressed"] is False      # hardware bandwidth instead

    def test_crossover_finite_at_10ms(self):
        from repro.energy.tco import compression_crossover_ratio
        x = compression_crossover_ratio(self.DB, self.BPQ, 0.010, 1e6)
        assert x is not None and 1.0 < x < 64.0
        # already-winning cell: crossover is 1.0 by definition
        assert compression_crossover_ratio(self.DB, self.BPQ, 0.060,
                                           1e6) == 1.0
        # unreachable within the search bound: honest None
        assert compression_crossover_ratio(self.DB, self.BPQ, 0.010, 1e6,
                                           max_ratio=1.5) is None

    def test_surface_grows_a_ratio_axis(self):
        from repro.energy.tco import decision_surface
        surf = decision_surface(self.DB, self.BPQ, slas=(0.010,),
                                skews=(None,), power_budgets_w=(1e6,),
                                compression_ratios=(1.0, 8.0))
        assert len(surf["cells"]) == 2
        by_ratio = {c["compression_ratio"]: c["winner"]
                    for c in surf["cells"]}
        assert by_ratio[1.0] == "die-stacked"
        assert by_ratio[8.0] == "traditional"

    def test_bandwidth_rich_systems_stay_uncompressed(self):
        """A custom HBM-class spec (TPU) must keep the datasheet
        workload on the compression axis — the prefix list is the
        explicit contract, not an accident of Table-1 naming."""
        from repro.core.systems import TPU_V5E, TRADITIONAL, \
            as_paper_system
        from repro.energy.tco import cheapest_architecture
        tpu = as_paper_system(TPU_V5E)
        cell = cheapest_architecture(
            self.DB, self.BPQ, 0.010, 1e7, skew=None,
            systems=(TRADITIONAL, tpu), compression_ratio=8.0)
        by_name = {c["name"]: c for c in cell["candidates"]}
        assert by_name[tpu.name]["compressed"] is False
        assert by_name["traditional"]["compressed"] is True

    def test_ratio_validation(self):
        from repro.energy.tco import cheapest_architecture
        for bad in (0.5, 0.0, -1.0, float("nan"), float("inf")):
            with pytest.raises(ValueError, match="compression_ratio"):
                cheapest_architecture(self.DB, self.BPQ, 0.010, 1e6,
                                      compression_ratio=bad)

    def test_advise_cost_passthrough(self):
        from repro.core.advisor import advise_cost
        cell = advise_cost(self.DB, self.BPQ, 0.010, 1e6,
                           compression_ratio=8.0)
        assert cell["winner"] == "traditional"
        assert cell["compression_ratio"] == 8.0


# --------------------------------------------------------------------------
# validation messages (satellite)
# --------------------------------------------------------------------------
class TestValidationMessages:
    def test_from_values_names_column_bits_and_max(self):
        with pytest.raises(ValueError,
                           match=r"column 'a'.*max code 300.*8-bit.*127"):
            BitPackedColumn.from_values("a", [1, 300], 8)

    def test_from_values_names_negative_min(self):
        with pytest.raises(ValueError, match=r"column 'a'.*min code -2"):
            BitPackedColumn.from_values("a", [-2, 3], 8)

    def test_table_add_names_column_and_counts(self):
        t = Table("t")
        t.add(BitPackedColumn.from_values("a", [1, 2, 3], 8))
        with pytest.raises(ValueError, match=r"'b' has 2 rows.*has 3"):
            t.add(BitPackedColumn.from_values("b", [1, 2], 8))

    def test_scan_filter_bad_op_is_value_error(self):
        from repro.kernels.scan_filter import ops as scan_ops
        from repro.kernels.scan_filter import ref as scan_ref
        packed = scan_ref.pack(np.asarray([1, 2], np.uint32), 8)
        with pytest.raises(ValueError, match="unknown predicate op"):
            scan_ops.scan_filter(packed, 1, "like", 8)
        with pytest.raises(ValueError, match="unknown predicate op"):
            scan_ref.scan_ref(packed, 1, "like", 8)


class TestBatchedLaunches:
    """The tentpole's observable: all same-encoding chunks of a column
    group execute as ONE kernel launch, counted in obs.metrics."""

    def test_one_launch_per_group_not_per_chunk(self, encoded):
        default = default_registry()
        plan, aggs = Pred("f", "ge", 42), ("u",)
        default.reset_launches()
        execute_encoded(plan, aggs, encoded, mode="xla_ref", batched=False)
        per_chunk = default.total_launches()
        default.reset_launches()
        execute_encoded(plan, aggs, encoded, mode="xla_ref", batched=True)
        batched = default.total_launches()
        assert per_chunk >= encoded.n_chunks       # the old loop: >= 1/chunk
        assert batched < encoded.n_chunks          # batched: 1 per group
        # fused single-pred/single-agg over one width group -> exactly 1
        assert default.launch_counts().get("scan_aggregate") == 1

    def test_rle_chunks_batch_into_one_launch(self, encoded):
        default = default_registry()
        default.reset_launches()
        execute_encoded(Pred("r", "lt", 3), ("r",), encoded,
                        mode="xla_ref", batched=True)
        assert default.launch_counts().get("scan_compressed") == 1
        default.reset_launches()
        execute_encoded(Pred("r", "lt", 3), ("r",), encoded,
                        mode="xla_ref", batched=False)
        assert default.launch_counts().get("scan_compressed") == \
            encoded.n_chunks

    @pytest.mark.parametrize("batched", (True, False))
    def test_translate_plan_memoized_on_frame_tuple(self, monkeypatch,
                                                    batched):
        """Chunks sharing a (base, width) frame translate the plan once
        per execute call, not once per chunk — the satellite regression:
        a plain column's frames are identical across chunks, so N chunks
        must cost exactly one translation."""
        import repro.store.exec as X

        rng = np.random.default_rng(0)
        t = Table("m")
        t.add(BitPackedColumn.from_values("a", rng.integers(0, 128, 4096),
                                          8))
        t.add(BitPackedColumn.from_values("b", rng.integers(0, 128, 4096),
                                          8))
        enc = EncodedTable.from_table(
            t, chunk_rows=512,
            encodings={"a": Encoding.PLAIN, "b": Encoding.PLAIN})
        assert enc.n_chunks == 8
        calls = []
        real = X.translate_plan
        monkeypatch.setattr(X, "translate_plan",
                            lambda plan, frames: calls.append(1) or
                            real(plan, frames))
        got = execute_encoded(Pred("a", "lt", 64), ("b",), enc,
                              mode="xla_ref", batched=batched)
        assert len(calls) == 1            # 8 chunks, 1 shared frame
        want = execute_encoded(Pred("a", "lt", 64), ("b",), enc,
                               mode="xla_ref", batched=batched)
        assert got == want
