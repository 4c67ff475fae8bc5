"""Data pipeline determinism + DB query correctness."""
import numpy as np

from repro.data import DataConfig, SyntheticLM
from repro.db import Predicate, Table, scan_aggregate_query
from repro.db.queries import bytes_scanned, scan_query
from repro.kernels.scan_filter.ref import unpack_mask


class TestPipeline:
    def test_restart_bitwise_reproducible(self):
        ds = SyntheticLM(DataConfig(seed=7, global_batch=4, seq_len=32))
        a = ds.batch(10)
        b = ds.batch(10)     # "restarted" pipeline at the same step
        np.testing.assert_array_equal(a["inputs"], b["inputs"])
        np.testing.assert_array_equal(a["labels"], b["labels"])
        assert not np.array_equal(ds.batch(11)["inputs"], a["inputs"])

    def test_labels_are_shifted_inputs(self):
        ds = SyntheticLM(DataConfig(global_batch=2, seq_len=16))
        b = ds.batch(0)
        np.testing.assert_array_equal(b["inputs"][:, 1:], b["labels"][:, :-1])

    def test_host_sharding_partitions_batch(self):
        ds = SyntheticLM(DataConfig(global_batch=8, seq_len=8))
        full = ds.batch(3)
        parts = [ds.local_batch(3, process_index=i, process_count=4)
                 for i in range(4)]
        got = np.concatenate([p["inputs"] for p in parts])
        np.testing.assert_array_equal(got, full["inputs"])

    def test_embeddings_mode(self):
        ds = SyntheticLM(DataConfig(global_batch=2, seq_len=8, embed_dim=16))
        b = ds.batch(0)
        assert b["inputs"].shape == (2, 8, 16)
        assert b["labels"].shape == (2, 8)

    def test_vocab_bound(self):
        ds = SyntheticLM(DataConfig(global_batch=4, seq_len=64,
                                    vocab_size=100))
        for s in range(3):
            assert ds.batch(s)["inputs"].max() < 100

    def test_prefetcher(self):
        from repro.data.pipeline import Prefetcher
        ds = SyntheticLM(DataConfig(global_batch=2, seq_len=8))
        pf = Prefetcher(ds, start_step=0, depth=2)
        s0, b0 = pf.next()
        s1, b1 = pf.next()
        pf.close()
        assert (s0, s1) == (0, 1)
        np.testing.assert_array_equal(b0["inputs"], ds.local_batch(0)["inputs"])


class TestQueries:
    def setup_method(self):
        self.t = Table.synthetic("t", 10_000, {"a": 8, "b": 8, "c": 16},
                                 seed=3)
        self.av = self.t.columns["a"].decode()
        self.bv = self.t.columns["b"].decode()

    def test_single_predicate(self):
        mask = scan_query(self.t, [Predicate("a", "lt", 50)])
        sel = np.asarray(unpack_mask(mask, 8))[:self.t.num_rows]
        np.testing.assert_array_equal(sel, self.av < 50)

    def test_conjunction(self):
        r = scan_aggregate_query(
            self.t, [Predicate("a", "lt", 50), Predicate("b", "ge", 100)],
            agg_column="b")
        sel = (self.av < 50) & (self.bv >= 100)
        assert int(r["count"]) == int(sel.sum())
        assert int(r["sum"]) == int(self.bv[sel].sum())
        if sel.any():
            assert int(r["min"]) == int(self.bv[sel].min())
            assert int(r["max"]) == int(self.bv[sel].max())

    def test_bytes_scanned(self):
        n = bytes_scanned(self.t, [Predicate("a", "lt", 10)], "b")
        assert n == self.t.columns["a"].nbytes + self.t.columns["b"].nbytes

    def test_kernel_and_ref_paths_agree(self):
        for mode in ("pallas", "xla_ref"):
            r = scan_aggregate_query(self.t, [Predicate("a", "ge", 64)],
                                     "a", mode=mode)
            sel = self.av >= 64
            assert int(r["count"]) == int(sel.sum())
