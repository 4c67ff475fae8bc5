"""Compile the query-side kernels and served-path programs for a described
TPU v5e, at real plane shapes, without a chip.

Interpret mode accepts layouts the TPU compiler (Mosaic) refuses — scalar
stores to VMEM, blocks that break the (8, 128) tiling rule — so every
query-side Pallas entry point is lowered with interpret=False against a
`v5e:2x2` topology description and must produce a `tpu_custom_call`.
The topology is described inside a fixture (never at import), so every
pytest worker collects the same tests and only the worker running this
file loads the TPU compiler.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.aggregate import kernel as agg_k
from repro.kernels.group_aggregate import kernel as group_k
from repro.kernels.group_aggregate import ops as group_ops
from repro.kernels.mask_repack import kernel as repack_k
from repro.kernels.scan_aggregate import kernel as fused_k
from repro.kernels.scan_compressed import kernel as rle_k
from repro.kernels.scan_filter import kernel as scan_k
from repro.obs.metrics import MetricsRegistry, scoped
from repro.query import And, GroupBy, Pred, Query
from repro.query.physical import ColumnSlice
from repro.query.sharded import ShardedTable, shard_rows

ROWS = 1 << 16            # (ROWS, 128) words per plane: 32 MiB
CHUNKS = 8
TABLE_ROWS = 1 << 30      # the one-chip smoke table
SCHEMA = {"a": 8, "b": 8, "c": 16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _kernel_cases(s):
    """entry point -> (fn, arg shapes); `s(shape, dtype)` places a shape
    on the described chip."""
    w2 = s((ROWS, 128), jnp.uint32)
    w3 = s((CHUNKS, ROWS, 128), jnp.uint32)
    i2 = s((ROWS, 128), jnp.int32)
    i3 = s((CHUNKS, ROWS, 128), jnp.int32)
    scal = s((CHUNKS,), jnp.int32)
    off = {"interpret": False}
    return {
        "scan_packed": (lambda w: scan_k.scan_packed(
            w, 5, op="ge", code_bits=8, **off), (w2,)),
        "aggregate_packed": (lambda w, m: agg_k.aggregate_packed(
            w, m, code_bits=8, **off), (w2, w2)),
        "aggregate_batched_packed": (lambda w, m:
                                     agg_k.aggregate_batched_packed(
                                         w, m, code_bits=16, **off),
                                     (w3, w3)),
        "scan_aggregate_packed": (lambda p, a, v:
                                  fused_k.scan_aggregate_packed(
                                      p, a, v, constant=3, op="eq",
                                      invert=True, code_bits=8, **off),
                                  (w2, w2, w2)),
        "scan_aggregate_batched_packed": (
            lambda c, f, p, a, v: fused_k.scan_aggregate_batched_packed(
                c, f, p, a, v, code_bits=8, **off),
            (scal, scal, w3, w3, w3)),
        "rle_scan_aggregate_packed": (lambda v, n:
                                      rle_k.rle_scan_aggregate_packed(
                                          v, n, constant=3, op="lt",
                                          code_bits=8, **off), (i2, i2)),
        "rle_scan_aggregate_batched_packed": (
            lambda v, n: rle_k.rle_scan_aggregate_batched_packed(
                v, n, constant=3, op="ne", code_bits=16, **off), (i3, i3)),
        "group_sum_count_batched_planes": (
            lambda k, v, m, g: group_k.group_sum_count_batched_planes(
                k, v, m, g, **off), (i3, i3, i3, s((128,), jnp.int32))),
        "rle_group_accumulate_batched_planes": (
            lambda v, n, g: group_k.rle_group_accumulate_batched_planes(
                v, n, g, pred=("ge", 3, True), **off),
            (i3, i3, s((13,), jnp.int32))),
        "group_sum_count_packed": (
            lambda k, m, v, g: group_k.group_sum_count_packed(
                k, m, (v, v, v), g, code_bits=8, block_rows=1024, **off),
            (w2, w2, w2, s((13,), jnp.int32))),
        # twenty value columns: the block shrinks to fit the kernel's VMEM
        "group_sum_count_packed_20_values": (
            lambda k, m, v, g: group_k.group_sum_count_packed(
                k, m, (v,) * 20, g, code_bits=8,
                block_rows=group_ops.packed_block_rows(ROWS, 8, 22), **off),
            (w2, w2, w2, s((4,), jnp.int32))),
        "repack_mask_packed_16to8": (lambda m: repack_k.repack_mask_packed(
            m, from_bits=16, to_bits=8, **off), (w2,)),
        "repack_mask_packed_2to16": (lambda m: repack_k.repack_mask_packed(
            m, from_bits=2, to_bits=16, **off), (w2,)),
    }


KERNELS = ("scan_packed", "aggregate_packed", "aggregate_batched_packed",
           "scan_aggregate_packed", "scan_aggregate_batched_packed",
           "rle_scan_aggregate_packed", "rle_scan_aggregate_batched_packed",
           "group_sum_count_batched_planes", "group_sum_count_packed",
           "group_sum_count_packed_20_values",
           "rle_group_accumulate_batched_planes", "repack_mask_packed_16to8",
           "repack_mask_packed_2to16")


@pytest.mark.parametrize("entry", KERNELS)
def test_kernel_compiles_for_v5e(entry, one_chip, no_persistent_cache):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_cases(s)[entry]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


SERVED = {
    "fused": Query(Pred("a", "lt", 40), ("b",)),
    "mask_and_aggregate": Query(And.of(Pred("a", "lt", 64),
                                       Pred("b", "ge", 32)), ("a", "b")),
    "grouped": GroupBy("a", ("b",), where=Pred("c", "lt", 16384)),
}


@pytest.mark.parametrize("shape", sorted(SERVED))
def test_served_program_compiles_for_v5e(shape, topo, no_persistent_cache,
                                         monkeypatch):
    """The sharded engine's per-query program over a 2^30-row table on one
    described chip: kernels compile, and the program fits the chip."""
    # the engine resolves interpret mode from the backend it runs on,
    # which here is the CPU; steer it to the chip's compiled branch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    st = ShardedTable(table=None, mesh=mesh, axis="data",
                      rows_per_shard=TABLE_ROWS,
                      slices={n: ColumnSlice(None, None, b)
                              for n, b in SCHEMA.items()})
    plane = NamedSharding(mesh, P("data"))

    def planes(name):
        sds = jax.ShapeDtypeStruct((TABLE_ROWS * SCHEMA[name] // 32,),
                                   jnp.uint32, sharding=plane)
        return [sds, sds]

    q = SERVED[shape]
    if shape == "grouped":
        names = st._referenced(q.plan(), q.aggs + (q.key,))
        fn = st._build_grouped(q.plan(), q.key, q.aggs, "pallas")
        args = [jax.ShapeDtypeStruct((128,), jnp.int32,
                                     sharding=NamedSharding(mesh, P()))]
    else:
        names = st._referenced(q.plan(), q.aggregates)
        fn = st._build(q.plan(), q.aggregates, "pallas")
        args = []
    for n in names:
        args += planes(n)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # table planes + temporaries within a v5e chip's 16 GB of HBM
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30


# TPC-H Q6 at its validation parameters over its three columns, one 16-bit
# code width (the benchmark's q6_power traffic)
Q6 = Query(And.of(Pred("l_shipdate", "ge", 731),
                  Pred("l_shipdate", "lt", 1096),
                  Pred("l_discount", "ge", 5), Pred("l_discount", "le", 7),
                  Pred("l_quantity", "lt", 24)),
           ("l_discount", "l_quantity"))
Q6_SCHEMA = {"l_shipdate": 16, "l_quantity": 16, "l_discount": 16}


@pytest.mark.parametrize("chips,rows,temp_bytes",
                         ((1, 600_037_902, 6.1e9),
                          (4, 1_799_989_091, 4.6e9)),
                         ids=("sf100_1chip", "sf300_4chip"))
def test_q6_program_over_whole_tiles_pads_nothing(chips, rows, temp_bytes,
                                                  topo, no_persistent_cache,
                                                  monkeypatch):
    """Q6's served program over a shard placed at whole kernel tiles
    (SF 100 on one chip, SF 300 on four): no pad, no plane-sized slice,
    no `tile_pads`, and the mask temporaries shrink to the masks."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    rps = shard_rows(Q6_SCHEMA.values(), rows, chips)
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    st = ShardedTable(table=None, mesh=mesh, axis="data",
                      rows_per_shard=rps,
                      slices={n: ColumnSlice(None, None, b)
                              for n, b in Q6_SCHEMA.items()})
    plane = jax.ShapeDtypeStruct((rps * chips * 16 // 32,), jnp.uint32,
                                 sharding=NamedSharding(mesh, P("data")))
    n_planes = 2 * len(st._referenced(Q6.plan(), Q6.aggregates))
    reg = MetricsRegistry("q6")
    with scoped(reg):
        compiled = st._build(Q6.plan(), Q6.aggregates, "pallas").lower(
            *[plane] * n_planes).compile()
    hlo = compiled.as_text()
    assert " pad(" not in hlo
    sliced = [math.prod(int(d) for d in dims.split(",") if d)
              for dims in re.findall(r"\[([\d,]*)\]\S* slice\(", hlo)]
    assert sliced and max(sliced) < scan_k.TILE_WORDS, sliced
    assert reg.counter("tile_pads").value == 0
    assert compiled.memory_analysis().temp_size_in_bytes <= temp_bytes


def test_q6_program_at_narrow_widths_fits_one_chip(topo, no_persistent_cache,
                                                   monkeypatch):
    """Q6 over lineitem at its own widths (16, 8, 8 bits), SF 100 on one
    chip: the ship-date mask reaches the aggregates' 8-bit layout through
    one `repack_mask_packed` kernel, and no buffer has a minor dimension
    of 2 or 4 (a (words, codes per word) mask pads those to 128 lanes:
    76.8 GB for this table)."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    schema = {"l_shipdate": 16, "l_quantity": 8, "l_discount": 8}
    rps = shard_rows(schema.values(), 600_037_902, 1)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    st = ShardedTable(table=None, mesh=mesh, axis="data",
                      rows_per_shard=rps,
                      slices={n: ColumnSlice(None, None, b)
                              for n, b in schema.items()})
    args = []
    for n in st._referenced(Q6.plan(), Q6.aggregates):
        plane = jax.ShapeDtypeStruct((rps * schema[n] // 32,), jnp.uint32,
                                     sharding=NamedSharding(mesh, P("data")))
        args += [plane, plane]
    reg = MetricsRegistry("q6_narrow")
    with scoped(reg):
        compiled = st._build(Q6.plan(), Q6.aggregates, "pallas").lower(
            *args).compile()
    hlo = compiled.as_text()
    assert re.search(r"%repack_mask_packed\S* = \S+ custom-call\(", hlo)
    assert reg.counter("mask_repacks").value == 1
    minor = set(re.findall(r"\b[a-z]+\d*\[[\d,]*,(\d+)\]", hlo))
    assert minor and not minor & {"2", "4"}, minor
    # q6_power's bound; measured 2,400,287,232: the two 1.2 GB ship-date
    # masks live at once before their AND, the 8-bit masks reuse them
    assert compiled.memory_analysis().temp_size_in_bytes <= 6.1e9


def test_q1_program_groups_the_packed_words(topo, no_persistent_cache,
                                            monkeypatch):
    """TPC-H Q1 over SF 100's lineitem at its own widths (ship date 16
    bits, key and values 8) on one chip: the ship-date mask reaches the
    key's layout through one repack and one packed kernel groups all
    three value columns; no slab loop, and the temporaries are the two
    masks, not the slab path's int32 planes (2.61 GB)."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    schema = {"l_shipdate": 16, "l_quantity": 8, "l_discount": 8,
              "l_tax": 8, "l_rfls": 8}
    q = GroupBy("l_rfls", ("l_quantity", "l_discount", "l_tax"),
                where=Pred("l_shipdate", "le", 2436))
    rps = shard_rows(schema.values(), 600_047_616, 1)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    st = ShardedTable(table=None, mesh=mesh, axis="data",
                      rows_per_shard=rps,
                      slices={n: ColumnSlice(None, None, b)
                              for n, b in schema.items()})
    args = [jax.ShapeDtypeStruct((4,), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))]
    for n in st._referenced(q.plan(), q.aggs + (q.key,)):
        plane = jax.ShapeDtypeStruct((rps * schema[n] // 32,), jnp.uint32,
                                     sharding=NamedSharding(mesh, P("data")))
        args += [plane, plane]
    reg = MetricsRegistry("q1")
    with scoped(reg):
        compiled = st._build_grouped(q.plan(), q.key, q.aggs,
                                     "pallas").lower(*args).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = \S+ custom-call\(", hlo)
    assert sorted(calls) == ["group_sum_count_packed",
                             "repack_mask_packed", "scan_packed"], calls
    assert not re.search(r"\bwhile\(", hlo)
    assert {k: reg.counter(k).value for k in ("grouped_packed",
                                              "grouped_slabs",
                                              "mask_repacks",
                                              "tile_pads")} == {
        "grouped_packed": 1, "grouped_slabs": 0, "mask_repacks": 1,
        "tile_pads": 0}
    # measured 1,800,239,616: the 1.2 GB ship-date mask and its 0.6 GB
    # repack into the key's layout
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.85e9
