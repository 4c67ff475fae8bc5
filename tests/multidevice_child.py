"""Child process for multi-device tests: 8 host devices via XLA_FLAGS.

Run by tests/test_dist_multidevice.py (device count locks at first jax
import, so these cannot run inside the main pytest process).
Each check prints 'OK <name>' on success; exits nonzero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.compression import (compressed_psum_pod,
                                    error_feedback_compress)
from repro.dist.pipeline_parallel import bubble_fraction, gpipe
from repro.launch.mesh import make_mesh


def check_pipeline():
    mesh = make_mesh((4,), ("pod",))
    s, m, d = 4, 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (s, d, d)) / np.sqrt(d)
    xs = jax.random.normal(key, (m, 2, d))

    def stage(w, x):
        return jnp.tanh(x @ w)

    got = gpipe(stage, ws, xs, mesh=mesh, axis="pod")

    want = xs
    for i in range(s):
        want = jnp.tanh(want @ ws[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(m, s) - 3 / 11) < 1e-9
    print("OK pipeline")


def check_pipeline_lowers_on_2d_mesh():
    """PP on 'pod' composes with DP on 'data' (lowering check)."""
    mesh = make_mesh((4, 2), ("pod", "data"))
    s, m, d = 4, 4, 8
    ws = jax.ShapeDtypeStruct((s, d, d), jnp.float32)
    xs = jax.ShapeDtypeStruct((m, 4, d), jnp.float32)

    def stage(w, x):
        return jnp.tanh(x @ w)

    def run(ws, xs):
        return gpipe(stage, ws, xs, mesh=mesh, axis="pod")

    jax.jit(run,
            in_shardings=(NamedSharding(mesh, P("pod")),
                          NamedSharding(mesh, P(None, "data"))),
            ).lower(ws, xs).compile()
    print("OK pipeline_2d_lowering")


def check_compression():
    mesh = make_mesh((4, 2), ("pod", "data"))
    key = jax.random.PRNGKey(1)
    g = {"a": jax.random.normal(key, (64, 32)),
         "b": jax.random.normal(key, (8,)) * 10}
    # replicate across devices
    g = jax.tree.map(lambda x: jax.device_put(
        x, NamedSharding(mesh, P())), g)
    got = compressed_psum_pod(g, mesh, axis="pod")
    want = jax.tree.map(lambda x: 4.0 * x, g)   # psum of 4 identical shards
    for k in g:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 2e-2, (k, rel)   # int8 quantization error bound
    print("OK compression")


def check_error_feedback():
    key = jax.random.PRNGKey(2)
    g = {"w": jax.random.normal(key, (128,))}
    res = None
    acc_sent = jnp.zeros((128,))
    acc_true = jnp.zeros((128,))
    for _ in range(50):
        sent, res = error_feedback_compress(g, res)
        acc_sent += sent["w"]
        acc_true += g["w"]
    # error feedback: accumulated sent converges to accumulated true
    rel = float(jnp.max(jnp.abs(acc_sent - acc_true))
                / jnp.max(jnp.abs(acc_true)))
    assert rel < 1e-2, rel
    print("OK error_feedback")


def check_sharded_train_step():
    """End-to-end: real train step on a (2,4) production-shaped mesh."""
    from repro.configs import SHAPES, get_config
    from repro.configs.base import ShapeSpec
    from repro.data import DataConfig, SyntheticLM, make_global_batch
    from repro.launch import specs
    import dataclasses

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced(num_layers=2, d_model=64,
                                               num_heads=4, num_kv_heads=2,
                                               dtype="float32")
    shape = ShapeSpec("tiny", "train", seq_len=32, global_batch=4)
    jitted, abstract = specs.build_train(cfg, shape, mesh)
    # materialize real state + batch with the same shardings
    from repro.train import optim, step as step_lib
    state, state_axes = step_lib.init_state(jax.random.PRNGKey(0), cfg,
                                            optim.AdamWConfig())
    from repro.dist.sharding import sharding_tree
    rules = specs.rules_for(cfg, shape)
    st_sh = sharding_tree(state, state_axes, mesh, rules)
    state = jax.tree.map(jax.device_put, state, st_sh)
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=4))
    batch = make_global_batch(ds.batch(0), mesh,
                              {"inputs": P("data"), "labels": P("data")})
    losses = []
    for _ in range(3):
        state, metrics = jitted(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    print("OK sharded_train_step", losses)


def check_elastic_rescale():
    """Train on a (2,4) mesh, checkpoint, restore onto an (8,1) mesh and
    continue — the final state must equal an uninterrupted run (the mesh
    is a deployment detail, not part of the math)."""
    import tempfile

    import jax.numpy as jnp

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.data import DataConfig, SyntheticLM, make_global_batch
    from repro.dist.sharding import sharding_tree
    from repro.launch import specs
    from repro.train import optim, step as step_lib

    cfg = get_config("internlm2-1.8b").reduced(num_layers=2, d_model=64,
                                               num_heads=4, num_kv_heads=2,
                                               dtype="float32")
    shape = ShapeSpec("tiny", "train", seq_len=32, global_batch=8)
    opt_cfg = optim.AdamWConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=8))

    def setup(mesh):
        jitted, _ = specs.build_train(cfg, shape, mesh, opt_cfg=opt_cfg)
        state, axes = step_lib.init_state(jax.random.PRNGKey(0), cfg,
                                          opt_cfg)
        sh = sharding_tree(state, axes, mesh, specs.rules_for(cfg, shape))
        return jitted, state, sh

    def run(jitted, state, mesh, steps_from, steps_to):
        for s in range(steps_from, steps_to):
            batch = make_global_batch(ds.batch(s), mesh,
                                      {"inputs": P("data"),
                                       "labels": P("data")})
            state, _ = jitted(state, batch)
        return state

    # uninterrupted reference on mesh A
    mesh_a = make_mesh((2, 4), ("data", "model"))
    jit_a, state0, sh_a = setup(mesh_a)
    state0 = jax.tree.map(jax.device_put, state0, sh_a)
    ref = run(jit_a, state0, mesh_a, 0, 4)

    # 2 steps on mesh A -> checkpoint -> restore on mesh B -> 2 more
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        jit_a2, s0, _ = setup(mesh_a)
        s0 = jax.tree.map(jax.device_put, s0, sh_a)
        mid = run(jit_a2, s0, mesh_a, 0, 2)
        mgr.save(2, mid)

        mesh_b = make_mesh((8, 1), ("data", "model"))
        jit_b, skeleton, sh_b = setup(mesh_b)
        restored, meta = mgr.restore(skeleton, shardings=sh_b)
        assert meta["step"] == 2
        final = run(jit_b, restored, mesh_b, 2, 4)

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5), ref, final)
    print("OK elastic_rescale")


def check_sharded_query_engine():
    """8-shard scan-aggregate must match the single-device oracle
    bit-exactly: AND/OR, mixed widths, fused path, non-divisible rows."""
    from repro.db import Table
    from repro.query import Pred, Query, QueryEngine, ShardedTable

    table = Table.synthetic("t", 100_001, {"a": 8, "b": 8, "w": 16, "x": 4},
                            seed=11)
    mesh = make_mesh((8,), ("data",))
    st = ShardedTable.shard(table, mesh)
    assert st.n_shards == 8
    queries = [
        Query(Pred("a", "lt", 64), aggregates=("b",)),          # fused
        Query(Pred("a", "lt", 50) & Pred("w", "ge", 9000),      # mixed AND
              aggregates=("w", "b")),
        Query(Pred("x", "eq", 3) | Pred("w", "lt", 500),        # mixed OR
              aggregates=("a",)),
    ]
    single = QueryEngine(table, mode="pallas")
    sharded = QueryEngine(st, mode="pallas")
    for q in queries:
        single.submit(q)
        sharded.submit(q)
        want = single.run()[0]
        got = sharded.run()[0]
        assert got.aggregates == want.aggregates, (q, got.aggregates,
                                                   want.aggregates)
        assert got.count == want.count
    assert sharded.summary()["measured_gbps"] > 0
    mc = sharded.model_check()
    assert mc["chips"] == 8 and mc["measured_gbps"] > 0
    print("OK sharded_query_engine")


def check_compressed_store():
    """8-shard scans over the compressed delta view must match the plain
    single-device oracle bit-exactly — every encoding, fused and general
    shapes, empty selections, non-divisible rows."""
    import numpy as np

    from repro.db.columnar import BitPackedColumn, Table
    from repro.query import Pred, Query, QueryEngine
    from repro.store import EncodedTable, ShardedEncodedTable

    rng = np.random.default_rng(13)
    n = 100_001
    table = Table("t")
    table.add(BitPackedColumn.from_values(
        "r", np.sort(rng.integers(0, 8, n)), 8))             # RLE
    table.add(BitPackedColumn.from_values(
        "f", 40 + rng.integers(0, 8, n), 8))                 # FOR
    table.add(BitPackedColumn.from_values(
        "w", 9000 + rng.integers(0, 100, n), 16))            # FOR 16->8
    table.add(BitPackedColumn.from_values(
        "u", rng.integers(0, 128, n), 8))                    # plain
    encoded = EncodedTable.from_table(table, chunk_rows=4096)
    mesh = make_mesh((8,), ("data",))
    st = ShardedEncodedTable.shard(encoded, mesh)
    assert st.n_shards == 8
    assert st.nbytes < sum(4 * int(c.words.size)
                           for c in table.columns.values()), \
        "delta view should be smaller than the plain device footprint"
    queries = [
        Query(Pred("r", "lt", 4), aggregates=("r",)),        # RLE col
        Query(Pred("f", "ge", 44), aggregates=("w",)),       # FOR x FOR
        Query(Pred("f", "ge", 42) & Pred("w", "lt", 9080),   # mixed AND
              aggregates=("w", "u")),
        Query(Pred("f", "lt", 40), aggregates=("f",)),       # empty
        Query(Pred("w", "ge", 0), aggregates=("w",)),        # all-match
    ]
    single = QueryEngine(table, mode="pallas")
    sharded = QueryEngine(st, mode="pallas")
    for q in queries:
        single.submit(q)
        sharded.submit(q)
        want = single.run()[0]
        got = sharded.run()[0]
        assert got.aggregates == want.aggregates, (q, got.aggregates,
                                                   want.aggregates)
        assert got.count == want.count
    assert sharded.summary()["measured_gbps"] > 0
    print("OK compressed_store")


def check_resilience():
    """Degraded-mode shard failover on 8 shards: any subset of lost
    shards re-executes from the host copy bit-exactly (plain + encoded),
    all-shards-lost raises typed, and the engine-level chaos path keeps
    every answer exact while charging recovery traffic."""
    from repro.db import Table
    from repro.query import Pred, Query, QueryEngine, ShardedTable
    from repro.resilience import (ChaosHarness, DegradedResultError,
                                  FaultSpec, execute_degraded)
    from repro.serve.sla import VirtualClock
    from repro.store import EncodedTable, ShardedEncodedTable
    from repro.tier.placement import PlacementEngine, Policy
    from repro.tier.tiers import paper_tiers

    table = Table.synthetic("t", 100_001, {"a": 8, "b": 8, "w": 16},
                            seed=11)
    mesh = make_mesh((8,), ("data",))
    st = ShardedTable.shard(table, mesh)
    se = ShardedEncodedTable.shard(EncodedTable.from_table(table), mesh)
    queries = [
        Query(Pred("a", "lt", 64), aggregates=("b",)),           # fused
        Query(Pred("a", "lt", 50) & Pred("w", "ge", 9000),       # mixed AND
              aggregates=("w", "b")),
        Query(Pred("a", "gt", 127), aggregates=("b",)),          # empty sel
    ]
    for sharded in (st, se):
        for q in queries:
            want = sharded.execute(q.plan(), q.aggregates)
            for lost in ([0], [7], [3, 5], list(range(7))):
                got, rec_b = execute_degraded(sharded, q.plan(),
                                              q.aggregates, lost)
                assert got == want, (lost, got, want)
                assert rec_b > 0
            try:
                execute_degraded(sharded, q.plan(), q.aggregates,
                                 list(range(8)))
                raise AssertionError("all-shards-lost did not raise")
            except DegradedResultError:
                pass

    # engine-level: seeded shard dropouts, every answer exact, recovery
    # bytes on the ledger; same seed -> same resilience summary
    def chaos_run():
        clock = VirtualClock()
        pe = PlacementEngine.for_table(st, paper_tiers(st.nbytes // 2),
                                       Policy.CACHE, chunk_rows=4096)
        eng = QueryEngine(st, mode="pallas", clock=clock, tiered=pe,
                          chaos=ChaosHarness(
                              FaultSpec(seed=5, shard_loss_rate=0.5)))
        want = st.execute(queries[0].plan(), queries[0].aggregates)
        for _ in range(10):
            eng.submit(queries[0], deadline=clock() + 10.0)
            r = eng.run()[0]
            assert r.aggregates == want and not r.degraded
        return eng.summary()
    s1, s2 = chaos_run(), chaos_run()
    assert s1["resilience"] == s2["resilience"]
    assert s1["resilience"]["shard_losses"] > 0
    assert s1["resilience"]["shard_recoveries"] == \
        s1["resilience"]["shard_losses"]
    assert s1["tier"]["recovery_bytes"] > 0
    print("OK resilience")


def check_relational():
    """8-shard GroupBy/HashJoin must match the single-device numpy oracle
    bit-exactly — plain and compressed delta views, the build side
    broadcast to every shard, and degraded re-execution for any
    lost-shard subset (all-lost raises typed)."""
    from repro.db.columnar import BitPackedColumn, Table
    from repro.query import GroupBy, HashJoin, Pred, relational
    from repro.query.sharded import ShardedTable
    from repro.resilience import DegradedResultError
    from repro.resilience.recover import execute_grouped_degraded
    from repro.store import EncodedTable, ShardedEncodedTable

    rng = np.random.default_rng(17)
    n = 100_001
    table = Table("t")
    table.add(BitPackedColumn.from_values(
        "r", np.sort(rng.integers(0, 8, n)), 8))             # RLE
    table.add(BitPackedColumn.from_values(
        "f", 40 + rng.integers(0, 8, n), 8))                 # FOR
    table.add(BitPackedColumn.from_values(
        "w", 9000 + rng.integers(0, 100, n), 16))            # FOR 16-bit
    table.add(BitPackedColumn.from_values(
        "u", rng.integers(0, 128, n), 8))                    # plain
    dim = Table("dim")
    dim.add(BitPackedColumn.from_values(
        "u", np.array([2, 7, 50, 90, 127]), 8))
    mesh = make_mesh((8,), ("data",))
    st = ShardedTable.shard(table, mesh)
    se = ShardedEncodedTable.shard(
        EncodedTable.from_table(table, chunk_rows=4096), mesh)
    queries = [
        GroupBy("r", ("u", "w")),                            # multi-agg
        GroupBy("f", ("w",), where=Pred("u", "lt", 64)),     # filtered
        GroupBy("r", where=Pred("r", "lt", 5)),              # count-only
        HashJoin(dim, "u", "u", aggs=("f",),                 # join clip
                 where=Pred("r", "lt", 7)),
        GroupBy("u", ("r",), where=Pred("u", "gt", 127)),    # empty sel
    ]
    for q in queries:
        want = relational.execute_grouped_oracle(q, table)
        for sharded in (st, se):
            got = sharded.execute_grouped(q)
            assert got == want, (q, got["count"], want["count"])
            for lost in ([0], [3, 5], list(range(7))):
                d, rec_b = execute_grouped_degraded(sharded, q, lost)
                assert d == want, (q, lost)
                assert rec_b > 0
            try:
                execute_grouped_degraded(sharded, q, list(range(8)))
                raise AssertionError("all-shards-lost did not raise")
            except DegradedResultError:
                pass
    print("OK relational")


def check_d2h_fetches():
    """Blocking device-to-host reads per served query on a table sharded
    over 4 devices: a Q6-shaped query reads all its aggregates at once, a
    Q1-shaped grouped query one plane stack per value column."""
    from repro.db import Table
    from repro.query import GroupBy, Pred, Query, QueryEngine, ShardedTable

    table = Table.synthetic("t", 20_000, {"d": 16, "q": 8, "x": 8, "k": 8},
                            seed=3)
    st = ShardedTable.shard(table, make_mesh((4,), ("data",)))
    eng = QueryEngine(st, mode="xla_ref")
    q6 = Query(Pred("d", "ge", 100) & Pred("d", "lt", 9000)
               & Pred("x", "ge", 5) & Pred("x", "le", 70)
               & Pred("q", "lt", 24), aggregates=("x", "q"))
    q1 = GroupBy("k", ("q", "x", "d"), where=Pred("d", "le", 20000))
    for query, want in ((q6, 1), (q1, 3)):
        for _ in range(2):       # cold (program built) and warm alike
            before = eng.metrics.counter("d2h_fetches").value
            eng.submit(query)
            eng.run()
            got = eng.metrics.counter("d2h_fetches").value - before
            assert got == want, (query, got, want)
    print("OK d2h_fetches")


def check_whole_tile_shards():
    """Mixed-width tables just past one kernel tile per shard, on 1 and 4
    devices: each shard is placed at whole tiles of every column, the
    wrappers pad nothing, and the Pallas answers equal numpy's."""
    from repro.db import Table
    from repro.kernels.scan_filter.kernel import TILE_WORDS
    from repro.obs.metrics import MetricsRegistry, scoped
    from repro.query import Pred, Query, ShardedTable

    spec = {"a": 8, "b": 8, "w": 16}
    tile_rows = TILE_WORDS * 4                 # one tile of the 8-bit codes
    queries = [
        (Query(Pred("a", "lt", 64), aggregates=("b",)),          # fused
         lambda c: c["a"] < 64),
        (Query(Pred("a", "lt", 50) & Pred("w", "ge", 9000)       # mixed AND
               & Pred("b", "le", 100), aggregates=("w", "b")),
         lambda c: (c["a"] < 50) & (c["w"] >= 9000) & (c["b"] <= 100)),
        (Query(Pred("b", "eq", 3) | Pred("w", "lt", 500),        # mixed OR
               aggregates=("a",)),
         lambda c: (c["b"] == 3) | (c["w"] < 500)),
    ]
    for n in (1, 4):
        table = Table.synthetic("t", n * tile_rows + 777, spec, seed=13)
        cols = {c: table.columns[c].decode().astype(np.int64)
                for c in spec}
        st = ShardedTable.shard(table, make_mesh((n,), ("data",)))
        assert st.rows_per_shard == 2 * tile_rows, st.rows_per_shard
        reg = MetricsRegistry("tiles")
        for q, mksel in queries:
            sel = mksel(cols)
            with scoped(reg):
                got = st.execute(q.plan(), q.aggregates, mode="pallas")
            for a in q.aggregates:
                v = cols[a][sel]
                want = {"sum": int(v.sum()), "count": int(sel.sum()),
                        "min": int(v.min()), "max": int(v.max())}
                assert got[a] == want, (n, q, a, got[a], want)
        assert reg.counter("tile_pads").value == 0
    print("OK whole_tile_shards")


def check_q6_narrow():
    """TPC-H Q6 over lineitem at its own widths (ship date 16 bits,
    quantity and discount 8) on 8 shards below one tile and on 4 shards
    at whole tiles: numpy's answers, one mask repack in each program,
    and no pad over whole tiles."""
    from repro.db.columnar import BitPackedColumn, Table
    from repro.kernels.scan_filter.kernel import TILE_WORDS
    from repro.obs.metrics import MetricsRegistry, scoped
    from repro.query import And, Pred, Query, ShardedTable

    q = Query(And.of(Pred("l_shipdate", "ge", 731),
                     Pred("l_shipdate", "lt", 1096),
                     Pred("l_discount", "ge", 5), Pred("l_discount", "le", 7),
                     Pred("l_quantity", "lt", 24)),
              ("l_discount", "l_quantity"))
    rng = np.random.default_rng(19)
    for n, rows in ((8, 100_001), (4, 4 * TILE_WORDS * 4 + 777)):
        table = Table("lineitem")
        for name, lo, hi, bits in (("l_shipdate", 0, 2526, 16),
                                   ("l_quantity", 1, 50, 8),
                                   ("l_discount", 0, 10, 8)):
            table.add(BitPackedColumn.from_values(
                name, rng.integers(lo, hi + 1, rows), bits))
        c = {k: table.columns[k].decode().astype(np.int64)
             for k in table.columns}
        sel = ((c["l_shipdate"] >= 731) & (c["l_shipdate"] < 1096)
               & (c["l_discount"] >= 5) & (c["l_discount"] <= 7)
               & (c["l_quantity"] < 24))
        st = ShardedTable.shard(table, make_mesh((n,), ("data",)))
        reg = MetricsRegistry("q6")
        with scoped(reg):
            got = st.execute(q.plan(), q.aggregates, mode="pallas")
        for a in q.aggregates:
            v = c[a][sel]
            want = {"sum": int(v.sum()), "count": int(sel.sum()),
                    "min": int(v.min()), "max": int(v.max())}
            assert got[a] == want, (n, a, got[a], want)
        assert reg.counter("mask_repacks").value == 1
        assert (reg.counter("tile_pads").value == 0) == (n == 4)
    print("OK q6_narrow")


def check_grouped_packed():
    """Grouped queries whose key and value columns share one code width,
    on 8 shards below one kernel tile and on 2 at whole tiles: one packed
    kernel per shard (`grouped_packed` 1, `grouped_slabs` 0, no pad over
    whole tiles) and numpy's answers; a value column at another width
    still takes the slab path."""
    from repro.db.columnar import BitPackedColumn, Table
    from repro.kernels.scan_filter.kernel import TILE_WORDS
    from repro.obs.metrics import MetricsRegistry, scoped
    from repro.query import GroupBy, HashJoin, Pred, relational
    from repro.query.sharded import ShardedTable

    rng = np.random.default_rng(23)
    dim = Table("dim")
    dim.add(BitPackedColumn.from_values(
        "k", np.array([0, 2, 3, 9, 100]), 8))
    queries = [
        (GroupBy("k", ("q", "x", "t"), where=Pred("d", "le", 2436)), 1),
        (GroupBy("k", where=Pred("q", "lt", 30) & Pred("d", "ge", 900)), 1),
        (HashJoin(dim, "k", "k", aggs=("x",), where=Pred("t", "ne", 4)), 1),
        (GroupBy("k", ("d",), where=Pred("x", "lt", 5)), 0),   # 16-bit value
    ]
    for n, rows in ((8, 100_001), (2, 2 * 2 * TILE_WORDS * 4 + 777)):
        table = Table("lineitem")
        for name, hi, bits in (("d", 2526, 16), ("q", 50, 8), ("x", 10, 8),
                               ("t", 8, 8), ("k", 3, 8)):
            table.add(BitPackedColumn.from_values(
                name, rng.integers(0, hi + 1, rows), bits))
        st = ShardedTable.shard(table, make_mesh((n,), ("data",)))
        for q, packed in queries:
            reg = MetricsRegistry("grouped")
            with scoped(reg):
                got = st.execute_grouped(q, mode="pallas")
            assert got == relational.execute_grouped_oracle(q, table), \
                (n, q)
            assert reg.counter("grouped_packed").value == packed, (n, q)
            assert reg.counter("grouped_slabs").value == 1 - packed, (n, q)
            if packed and n == 2:
                assert reg.counter("tile_pads").value == 0, q
    print("OK grouped_packed")


def check_serve_step_sharded():
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch import specs

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("mixtral-8x22b").reduced(num_layers=2, dtype="float32")
    shape = ShapeSpec("tinydec", "decode", seq_len=64, global_batch=4)
    jitted, abstract = specs.build_serve(cfg, shape, mesh)
    jitted.lower(*abstract).compile()
    print("OK serve_step_sharded_lowering")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = {
        "pipeline": check_pipeline,
        "pipeline2d": check_pipeline_lowers_on_2d_mesh,
        "compression": check_compression,
        "ef": check_error_feedback,
        "train": check_sharded_train_step,
        "serve": check_serve_step_sharded,
        "elastic": check_elastic_rescale,
        "query": check_sharded_query_engine,
        "store": check_compressed_store,
        "resilience": check_resilience,
        "relational": check_relational,
        "d2h": check_d2h_fetches,
        "tiles": check_whole_tile_shards,
        "q6_narrow": check_q6_narrow,
        "grouped_packed": check_grouped_packed,
    }
    if which == "all":
        for fn in checks.values():
            fn()
    else:
        checks[which]()
    print("CHILD_DONE")
