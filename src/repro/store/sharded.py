"""Sharded execution over a compressed store: the delta view on a mesh.

Row-aligned encodings are what shard: a FOR plane is a plain BitWeaving
plane in delta space, so a compressed table shards by building one global
frame of reference per column (base = column min, payload width = span
width), bit-packing the deltas, and handing that *delta table* to the
unmodified `query.sharded.ShardedTable` — per-shard Pallas scans on
compressed words, psum-combined planes, validity masks, the whole
machinery unchanged. Queries translate into the delta domain on the way
in (store.exec.translate_plan) and aggregates fix up their base on the
way out, in exact host ints after the psum.

RLE is a chunk-local layout (runs do not align across shard boundaries),
so sharding re-encodes every column — including RLE-chosen ones — into
the global FOR frame; the device-resident bytes the tier/energy ledgers
charge are the delta words. Columns whose span needs the full logical
width shard at today's plain size: the view never exceeds the plain
format, mirroring `choose_encoding`'s guarantee.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.db.columnar import BitPackedColumn, Table
from repro.obs import metrics as obs_metrics
from repro.query.sharded import ShardedTable
from repro.store.encode import EncodedTable, width_for_span
from repro.store.exec import fixup_base, translate_plan


@dataclass(frozen=True)
class _ColMeta:
    """The metadata surface the engine reads per column: logical width
    for plan validation, physical (device-resident, compressed) bytes
    for admission, logical bytes beside them."""
    code_bits: int
    nbytes: int
    logical_nbytes: int


class ShardedEncodedTable:
    """An EncodedTable partitioned row-wise along one mesh axis.

    Duck-types ShardedTable where QueryEngine touches it: `columns`,
    `num_rows`, `n_shards`, `nbytes`, `slices`, `execute`, `chunk_bytes`.
    """

    def __init__(self, store: EncodedTable, inner: ShardedTable,
                 frames: dict[str, tuple[int, int]]):
        self.store = store
        self.inner = inner
        self.frames = frames           # column -> (base, payload width)

    @classmethod
    def shard(cls, store: EncodedTable, mesh,
              axis: str = "data") -> "ShardedEncodedTable":
        if not store.columns:
            raise ValueError("cannot shard an empty encoded table")
        delta = Table(f"{store.name}-delta")
        frames: dict[str, tuple[int, int]] = {}
        for name, col in store.columns.items():
            codes = col.decode()
            base = int(codes.min()) if codes.size else 0
            width = (width_for_span(int(codes.max()) - base)
                     if codes.size else 2)
            frames[name] = (base, width)
            delta.add(BitPackedColumn.from_values(
                name, codes - np.uint32(base), width))
        return cls(store, ShardedTable.shard(delta, mesh, axis), frames)

    # --- metadata ---------------------------------------------------------
    @property
    def columns(self) -> dict[str, _ColMeta]:
        out = {}
        for name, col in self.store.columns.items():
            dev = 4 * int(self.inner.slices[name].words.size)
            out[name] = _ColMeta(col.code_bits, dev, col.logical_nbytes)
        return out

    @property
    def num_rows(self) -> int:
        return self.store.num_rows

    @property
    def n_shards(self) -> int:
        return self.inner.n_shards

    @property
    def nbytes(self) -> int:
        """Device-resident compressed bytes (shard padding included)."""
        return self.inner.nbytes

    @property
    def slices(self):
        """Delta-word device slices — the tier placement universe, so
        placement chunks hold compressed bytes."""
        return self.inner.slices

    # --- tier accounting --------------------------------------------------
    def chunk_bytes(self, plan, aggregates, chunk_rows: int) -> dict:
        """Per-(column, chunk) device-resident *compressed* bytes this
        query streams (same chunk ids as PlacementEngine.for_table)."""
        return self.inner.chunk_bytes(plan, aggregates, chunk_rows)

    # --- execution --------------------------------------------------------
    def execute(self, plan, aggregates, mode=None) -> dict:
        """Per-shard scan over compressed delta words, psum combine,
        exact host-int base fix-up; bit-identical to the plain table."""
        aggregates = tuple(aggregates)
        raw = self.inner.execute(translate_plan(plan, self.frames),
                                 aggregates, mode=mode)
        return {a: fixup_base(raw[a], self.frames[a][0],
                              self.store.columns[a].code_bits)
                for a in aggregates}

    def execute_grouped(self, query, mode=None) -> dict:
        """GroupBy/HashJoin over the sharded compressed view: the where
        plan translates into the delta domain, the group domain shifts by
        the key's frame base, and the per-shard dense kernels run on delta
        words directly. Host-side absorb restores logical keys
        (key_base=kbase) and value sums (sum += vbase * count), both
        exact, so the result is bit-identical to every other surface."""
        from repro.query import relational
        relational.bind_check(query, self.columns)
        if self.num_rows == 0:
            return relational.empty_result()
        kbase, _ = self.frames[query.key]
        dmin, dmax = self.inner.key_code_range(query.key)
        if dmax < dmin:
            return relational.empty_result()
        domain = relational.group_domain(query, kbase + dmin,
                                         kbase + dmax)
        if len(domain) == 0:
            return relational.empty_result()
        if not relational.dense_ok(domain):
            obs_metrics.count_launch("group_aggregate_fallback",
                                     self.n_shards)
            return relational.execute_grouped_oracle(
                query, self.store.decode_table())
        planes = self.inner.execute_grouped_planes(
            translate_plan(query.plan(), self.frames), query.key,
            query.aggs, np.asarray(domain) - kbase, mode=mode)
        first = query.aggs[0] if query.aggs else ""
        part = relational.new_partial()
        for name, stack in planes.items():
            vbase = self.frames[name][0] if name else 0
            for i in range(stack.shape[0]):
                relational.absorb_plane(
                    part, np.asarray(domain) - kbase, stack[i],
                    name or None, base=vbase, key_base=kbase,
                    count_source=(name == first))
        return relational.finalize(part)
