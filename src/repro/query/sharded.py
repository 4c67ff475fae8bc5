"""Row-wise table sharding across a mesh + per-shard query execution.

"Processing Data Where It Makes Sense" at cluster scale: each device holds a
contiguous row range of every column and scans it locally; only the four
aggregate scalars per shard cross the interconnect (psum/pmin/pmax inside a
shard_map). Rows are padded so one shard boundary works for every column:
rows_per_shard is a multiple of every column's codes-per-word (lcm), hence
each column's word array splits evenly on the same row boundaries despite
mixed code widths; a shard of at least one kernel tile is further a whole
number of tiles of every column (`shard_rows`), so the kernel wrappers
neither pad nor slice its planes. Validity masks cancel all padding rows.

The paper's provisioning model maps directly: chips = shards, and per-shard
scan throughput is what `core_perf` claims each chip sustains — the query
engine compares the two.

A served query's host stages are spans of the active query trace
(`obs.trace.span`): `query.dispatch` from the plan-cache lookup to the
return of the enqueued program (`query.build` inside it on a miss), then
`query.finalize` for the blocking reads and host merge; each blocking
device-to-host read counts one `d2h_fetches`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.scan_filter import ref as packref
from repro.kernels.scan_filter.kernel import TILE_WORDS
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.query import physical
from repro.query.physical import ColumnSlice
from repro.query.plan import columns_of


# rows a shard unpacks per grouped-kernel launch on the slab path, where
# a value column is at another width than the key (int32 planes of 64 MiB)
GROUP_SLAB_ROWS = 1 << 24


def shard_rows(code_bits, num_rows: int, n_shards: int) -> int:
    """Rows each of `n_shards` shards holds of a `num_rows`-row table whose
    columns have the widths `code_bits`: the ceiling share, rounded up to a
    word boundary of every width. A shard that already holds a whole
    TILE_WORDS tile of every column is rounded up further, to a whole
    number of them, at a cost of under one tile of rows: each plane then
    reshapes to (rows, LANES) in whole kernel blocks, and the wrappers
    emit no pad and no slice. Smaller shards stay word-aligned only."""
    cpw = math.lcm(*(32 // b for b in code_bits))
    rows = -(-max(1, -(-num_rows // n_shards)) // cpw) * cpw
    tile = TILE_WORDS * cpw
    return rows if rows < tile else -(-rows // tile) * tile


_zero_extend = jax.jit(lambda a, n: jnp.pad(a, (0, n - a.shape[0])),
                       static_argnums=1)


def _put_zero_extended(words, n_words: int, sharding):
    """Host `words` zero-extended to `n_words`, placed with `sharding`.
    Each device receives only its own rows, straight from the host array,
    and a shard that runs past the data gets its zero tail on its device:
    no host copy of a column is made, and nothing else stays resident."""
    parts = []
    for dev, idx in sharding.addressable_devices_indices_map(
            (n_words,)).items():
        lo, hi, _ = idx[0].indices(n_words)
        part = jax.device_put(words[lo:hi], dev)
        if part.shape[0] < hi - lo:
            part = _zero_extend(part, hi - lo)
        parts.append(part)
    return jax.make_array_from_single_device_arrays((n_words,), sharding,
                                                    parts)


def _merge_planes(a: dict, b: dict) -> dict:
    """Add normalized [sum_lo, sum_hi, count] planes, renormalized —
    exact in int32 while the shard's hi plane and count stay < 2^31."""
    out = {}
    for name, p in a.items():
        q = b[name]
        lo = p[:, 0] + q[:, 0]
        out[name] = jnp.stack([lo & 0xFFFF, p[:, 1] + q[:, 1] + (lo >> 16),
                               p[:, 2] + q[:, 2]], axis=1)
    return out


def _fetch_planes(stacked: dict) -> dict:
    """Device plane stacks -> host numpy, each one counted blocking
    read."""
    out = {}
    for name, v in stacked.items():
        obs_metrics.count("d2h_fetches")
        out[name] = np.asarray(v)
    return out


@dataclass
class ShardedTable:
    """A repro.db Table partitioned row-wise along one mesh axis."""

    table: Any                      # the logical (host) Table
    mesh: Any
    axis: str
    rows_per_shard: int
    slices: dict[str, ColumnSlice]  # device arrays, sharded along `axis`
    _jitted: dict = field(default_factory=dict, repr=False)

    @property
    def num_rows(self) -> int:
        return self.table.num_rows

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def columns(self):              # metadata view, same duck type as Table
        return self.table.columns

    @property
    def nbytes(self) -> int:
        """Device-resident bytes (includes shard-alignment padding)."""
        return sum(int(s.words.size) * 4 for s in self.slices.values())

    @classmethod
    def shard(cls, table, mesh, axis: str = "data") -> "ShardedTable":
        if not table.columns:
            raise ValueError("cannot shard an empty table")
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r}; axes are "
                             f"{tuple(mesh.shape)}")
        n = int(mesh.shape[axis])
        rps = shard_rows([c.code_bits for c in table.columns.values()],
                         table.num_rows, n)
        total_rows = rps * n
        sharding = NamedSharding(mesh, P(axis))
        slices = {}
        for name, col in table.columns.items():
            n_words = total_rows * col.code_bits // 32
            valid = packref.valid_mask(n_words, table.num_rows,
                                       col.code_bits)
            slices[name] = ColumnSlice(
                _put_zero_extended(np.asarray(col.words, np.uint32),
                                   n_words, sharding),
                jax.device_put(valid, sharding), col.code_bits)
        return cls(table, mesh, axis, rps, slices)

    # --- tier accounting --------------------------------------------------
    def chunk_bytes(self, plan, aggregates,
                    chunk_rows: int) -> dict[tuple[str, int], int]:
        """Per-(column, chunk) *device-resident* bytes this query streams
        (shard-alignment padding included — padded words cross the memory
        bus like real ones), reported to the tier placement engine. Chunk
        ids live in the padded row space; when `chunk_rows` divides
        rows_per_shard no chunk straddles a shard boundary."""
        return physical.chunk_universe(
            self.slices,
            physical.align_chunk_rows(self.table.columns, chunk_rows),
            names=self._referenced(plan, tuple(aggregates)))

    # --- execution --------------------------------------------------------
    def _referenced(self, plan, aggregates: tuple) -> tuple:
        return tuple(sorted(columns_of(plan) | set(aggregates)))

    def _args(self, names) -> list:
        """The (words, valid) device arrays of `names`, flat, in order."""
        return [a for n in names
                for a in (self.slices[n].words, self.slices[n].valid)]

    def _call(self, key, build, *args):
        """The program cached under `key`, called on `args` (enqueued, not
        awaited); a miss builds it, and building it and its first call's
        lowering are the `query.build` span."""
        fn = self._jitted.get(key)
        if fn is not None:
            return fn(*args)
        with obs_trace.span("query.build"):
            fn = self._jitted[key] = build()
            return fn(*args)

    def execute(self, plan, aggregates, mode=None) -> dict:
        """Per-shard scan+aggregate with a psum combine; returns
        {agg_column: {sum, count, min, max}} as exact host ints.

        Compiled executions are cached per (plan, aggregates, mode) — plans
        are frozen dataclasses, so the query shape is the cache key.
        """
        aggregates = tuple(aggregates)
        key = (plan, aggregates, None if mode is None else str(mode))
        with obs_trace.span("query.dispatch"):
            out = self._call(
                key, lambda: self._build(plan, aggregates, mode),
                *self._args(self._referenced(plan, aggregates)))
        return physical.finalize_aggs(out)

    def execute_partials(self, plan, aggregates, mode=None) -> list[dict]:
        """Per-shard finalized aggregates in shard order (exact host ints).

        The degraded-mode combine surface: resilience.recover merges the
        surviving shards' partials with lost shards re-executed from the
        host copy, instead of the all-shards psum. Merging all partials
        equals `execute` bit for bit — the psum'd planes are themselves
        per-shard sums, and finalize is linear in the planes.
        """
        aggregates = tuple(aggregates)
        key = (plan, aggregates, None if mode is None else str(mode),
               "partials")
        # {col: {field: (n_shards,) device arrays}}
        stacked = self._call(
            key, lambda: self._build_partials(plan, aggregates, mode),
            *self._args(self._referenced(plan, aggregates)))
        return [physical.finalize_aggs(
                    {col: {k: v[i] for k, v in d.items()}
                     for col, d in stacked.items()})
                for i in range(self.n_shards)]

    def _build_partials(self, plan, aggregates: tuple, mode):
        names = self._referenced(plan, aggregates)
        bits = {n: self.slices[n].code_bits for n in names}
        axis = self.axis

        def per_shard(*flat):
            slices = {n: ColumnSlice(flat[2 * i], flat[2 * i + 1], bits[n])
                      for i, n in enumerate(names)}
            out = physical.execute(plan, aggregates, slices, mode=mode)
            # no psum: each shard contributes its (1,) slice of the
            # stacked per-shard output instead of a combined scalar
            return jax.tree.map(lambda x: jnp.reshape(x, (1,)), out)

        return jax.jit(jax.shard_map(per_shard, mesh=self.mesh,
                                     in_specs=(P(axis),) * (2 * len(names)),
                                     out_specs=P(axis), check_vma=False))

    # --- degraded-mode recovery source ------------------------------------
    def shard_row_range(self, shard: int) -> tuple[int, int]:
        """Logical (unpadded) row range [lo, hi) shard `shard` owns; empty
        when the shard holds only alignment padding."""
        if shard < 0 or shard >= self.n_shards:
            raise ValueError(f"shard={shard} outside [0, {self.n_shards})")
        lo = shard * self.rows_per_shard
        return lo, max(lo, min(lo + self.rows_per_shard, self.num_rows))

    def host_shard_slices(self, shard: int, names=None
                          ) -> dict[str, ColumnSlice]:
        """One shard's row range bound from the logical (host) table — the
        capacity-tier replica degraded execution re-reads when that
        shard's device copy is lost. rows_per_shard is word-aligned for
        every column, so the word slice is exact; a fresh validity mask
        cancels rows past num_rows."""
        lo, hi = self.shard_row_range(shard)
        out = {}
        for name in (sorted(names) if names is not None else
                     self.table.columns):
            col = self.table.columns[name]
            cpw = 32 // col.code_bits
            w0 = lo // cpw
            w1 = min(w0 + self.rows_per_shard // cpw, int(col.words.size))
            words = np.asarray(col.words)[w0:w1]
            valid = packref.valid_mask(words.size, hi - lo, col.code_bits)
            out[name] = ColumnSlice(jnp.asarray(words), jnp.asarray(valid),
                                    col.code_bits)
        return out

    def _build(self, plan, aggregates: tuple, mode):
        names = self._referenced(plan, aggregates)
        bits = {n: self.slices[n].code_bits for n in names}
        axis = self.axis

        def per_shard(*flat):
            slices = {n: ColumnSlice(flat[2 * i], flat[2 * i + 1], bits[n])
                      for i, n in enumerate(names)}
            return physical.execute(plan, aggregates, slices, mode=mode,
                                    axis=axis)

        # check_vma=False: pallas_call has no replication rule; the outputs
        # are psum-combined and genuinely replicated
        return jax.jit(jax.shard_map(per_shard, mesh=self.mesh,
                                     in_specs=(P(axis),) * (2 * len(names)),
                                     out_specs=P(), check_vma=False))

    # --- grouped execution (GroupBy / HashJoin) ---------------------------
    def key_code_range(self, key: str) -> tuple[int, int]:
        """Observed (kmin, kmax) of a column's codes over the logical
        rows — what bounds the dense group domain. Cached per column on
        the host table (codes are immutable)."""
        cached = self._jitted.get(("range", key))
        if cached is None:
            col = self.table.columns[key]
            cached = self._jitted[("range", key)] = packref.code_range(
                col.words, col.code_bits, col.num_rows)
        return cached

    def execute_grouped_planes(self, plan, key: str, aggs: tuple, domain,
                               mode=None) -> dict:
        """Per-shard grouped accumulator planes, the all-gather combine
        surface: {value_column_or_'': (n_shards, n_groups, 3)} int32
        stacks, one normalized [sum_lo, sum_hi, count] plane per shard
        per value column (one '' plane when aggs is empty).

        `domain` (sorted group keys in THIS table's code domain — the
        delta domain for the encoded view) broadcasts replicated to every
        shard, which is exactly how a join's build side ships. Merging
        the shard planes host-side equals an unsharded execution bit for
        bit: the planes are normalized per shard and the partial algebra
        is associative in exact ints."""
        return _fetch_planes(self._dispatch_grouped(plan, key, aggs,
                                                    domain, mode))

    def _dispatch_grouped(self, plan, key: str, aggs, domain, mode):
        """Enqueue the grouped program: {name: (n_shards, n_groups, 3)}
        device stacks, not yet awaited."""
        aggs = tuple(aggs)
        cache_key = (plan, key, aggs,
                     None if mode is None else str(mode), "grouped")
        with obs_trace.span("query.dispatch"):
            return self._call(
                cache_key,
                lambda: self._build_grouped(plan, key, aggs, mode),
                jnp.asarray(np.asarray(domain), jnp.int32),
                *self._args(self._referenced(plan, aggs + (key,))))

    def _build_grouped(self, plan, key: str, aggs: tuple, mode):
        """The per-shard grouped program. Where the key and every value
        column share one code width, field i of word w is the same row in
        each, so one packed kernel groups the words as they lie, under the
        plan's mask built in the key's layout. Otherwise the shard unpacks
        slab by slab to int32 planes and groups those."""
        from repro.kernels.group_aggregate import ops as gops
        names = self._referenced(plan, aggs + (key,))
        bits = {n: self.slices[n].code_bits for n in names}
        axis = self.axis
        value_cols = aggs if aggs else ("",)

        def packed(gk, *flat):
            obs_metrics.count("grouped_packed")
            slices = {n: ColumnSlice(flat[2 * i], flat[2 * i + 1], bits[n])
                      for i, n in enumerate(names)}
            k = slices[key]
            # every leaf's mask is ANDed with its column's validity, and
            # all columns are valid on the same rows: the mask selects no
            # row past the table, so the key's validity adds nothing
            mask, _ = physical.eval_mask(plan, slices, mode,
                                         layout=(bits[key], k.words.shape[0]))
            planes = gops.group_sum_count_packed(
                k.words, mask, [slices[a].words for a in aggs], gk,
                code_bits=bits[key], mode=mode)
            return {name: planes[i][None]
                    for i, name in enumerate(value_cols)}

        same_width = all(bits[a] == bits[key] for a in aggs)
        return jax.jit(jax.shard_map(
            packed if same_width else self._grouped_slabs(
                plan, key, value_cols, names, bits, mode),
            mesh=self.mesh,
            in_specs=(P(),) + (P(axis),) * (2 * len(names)),
            out_specs=P(axis), check_vma=False))

    def _grouped_slabs(self, plan, key: str, value_cols: tuple, names,
                       bits: dict, mode):
        """The per-shard body for value columns at another width than the
        key: a loop over slabs of rows, each unpacked to int32 code
        planes, filtered by the plan on the codes, and grouped once per
        value column; the normalized planes merge on the device."""
        from repro.kernels.group_aggregate import ops as gops
        from repro.query import relational
        # the kernel reads int32 code planes, 4 B per row per column: a
        # shard unpacks one slab of rows at a time, so the planes stay
        # small next to the packed table at any table size
        slab = min(GROUP_SLAB_ROWS, self.rows_per_shard)
        n_slabs, tail = divmod(self.rows_per_shard, slab)

        def slab_planes(gk, flat, row0, n_rows):
            cols, valid = {}, None
            for i, n in enumerate(names):
                cpw = 32 // bits[n]
                words = jax.lax.dynamic_slice_in_dim(
                    flat[2 * i], row0 // cpw, n_rows // cpw)
                cols[n] = jnp.asarray(packref.unpack(words, bits[n]),
                                      jnp.int32)
                if n == key:
                    valid = packref.unpack_mask(
                        jax.lax.dynamic_slice_in_dim(
                            flat[2 * i + 1], row0 // cpw, n_rows // cpw),
                        bits[n])
            sel = relational.eval_plan_codes(plan, cols) & valid
            keys3 = gops.lift_chunks([cols[key]])
            sel3 = gops.lift_chunks([sel.astype(jnp.int32)])
            return {name: gops.group_sum_count_batched(
                        keys3, gops.lift_chunks([cols[name]]) if name
                        else jnp.zeros_like(keys3), sel3, gk, mode=mode)[0]
                    for name in value_cols}

        def per_shard(gk, *flat):
            obs_metrics.count("grouped_slabs")
            acc = {name: jnp.zeros((gk.shape[0], 3), jnp.int32)
                   for name in value_cols}
            if n_slabs:
                acc = jax.lax.fori_loop(
                    0, n_slabs, lambda s, a: _merge_planes(
                        a, slab_planes(gk, flat, s * slab, slab)), acc)
            if tail:
                acc = _merge_planes(
                    acc, slab_planes(gk, flat, n_slabs * slab, tail))
            return {name: p[None] for name, p in acc.items()}

        return per_shard

    def execute_grouped(self, query, mode=None) -> dict:
        """GroupBy/HashJoin across the mesh: per-shard dense accumulator
        planes all-gathered and merged in exact host ints. Group domains
        past the dense cutoff fall back to the host numpy path (counted
        as group_aggregate_fallback launches), still bit-exact."""
        from repro.query import relational
        relational.bind_check(query, self.table.columns)
        if self.num_rows == 0:
            return relational.empty_result()
        kmin, kmax = self.key_code_range(query.key)
        domain = relational.group_domain(query, kmin, kmax)
        if len(domain) == 0:
            return relational.empty_result()
        if not relational.dense_ok(domain):
            obs_metrics.count_launch("group_aggregate_fallback",
                                     self.n_shards)
            return relational.execute_grouped_oracle(query, self.table)
        stacked = self._dispatch_grouped(query.plan(), query.key,
                                         query.aggs, domain, mode)
        first = query.aggs[0] if query.aggs else ""
        with obs_trace.span("query.finalize"):
            part = relational.new_partial()
            for name, stack in _fetch_planes(stacked).items():
                for i in range(stack.shape[0]):
                    relational.absorb_plane(part, domain, stack[i],
                                            name or None,
                                            count_source=(name == first))
            return relational.finalize(part)
