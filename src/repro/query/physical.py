"""Physical query execution: kernel-dispatch operators over packed columns.

A logical Plan tree binds to a table's packed columns as `ColumnSlice`s
(words + validity mask + width) and executes bottom-up:

- every leaf Pred is a dispatch-routed scan (repro.kernels.scan_filter)
  whose mask is ANDed with the column's validity mask, so rows that exist
  only as padding — the pack()-to-a-word-multiple tail, or shard-alignment
  rows — can never match a predicate (the seed's scan counted tail-pad
  codes that happened to satisfy the predicate);
- AND/OR combine masks word-wise: siblings at one code width first, in
  their own layout, then one repack (repro.kernels.mask_repack) per other
  width to the node's layout; a query whose aggregates share one width
  forms its mask in their layout, and the final mask is repacked once per
  other aggregate width;
- each aggregate column reduces the selection through the dispatch-routed
  masked aggregate, and the dominant single-predicate/single-aggregate
  query takes the fused scan+aggregate kernel instead (no mask HBM
  round-trip);
- under `axis=...` (inside a shard_map) the four scalars combine across
  shards with psum/pmin/pmax — the only bytes that cross the interconnect.

Everything is traceable jnp/Pallas: the same function executes single-device
and per-shard inside repro.query.sharded's shard_map.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.mask_repack import ops as repack_ops
from repro.kernels.scan_aggregate import ops as fused_ops
from repro.kernels.scan_filter import ops as scan_ops
from repro.obs import trace as obs_trace
from repro.query.plan import And, Or, Plan, Pred, columns_of


@dataclass(frozen=True)
class ColumnSlice:
    """One column's packed words + validity mask, bound for execution.

    `valid` has a delimiter bit set exactly for rows < num_rows; all
    evaluation happens masked by it.
    """
    words: Any                  # (n_words,) uint32
    valid: Any                  # (n_words,) uint32 delimiter-bit mask
    code_bits: int


def table_slices(table) -> dict[str, ColumnSlice]:
    """Bind a repro.db Table's columns for single-device execution."""
    return {name: ColumnSlice(col.words, col.valid_words, col.code_bits)
            for name, col in table.columns.items()}


def bind_check(plan: Plan, aggregates, columns: dict) -> None:
    """Validate a logical plan against table metadata; raises ValueError."""
    known = set(columns)
    missing = (columns_of(plan) | set(aggregates)) - known
    if missing:
        raise ValueError(f"unknown column(s) {sorted(missing)}; table has "
                         f"{sorted(known)}")

    def walk(node):
        if isinstance(node, Pred):
            bits = columns[node.column].code_bits
            vmax = (1 << (bits - 1)) - 1
            if node.constant > vmax:
                raise ValueError(
                    f"constant {node.constant} exceeds the {bits}-bit "
                    f"payload max {vmax} of column {node.column!r}")
        else:
            for c in node.children:
                walk(c)

    walk(plan)


def eval_mask(plan: Plan, slices: dict[str, ColumnSlice], mode=None,
              layout: tuple[int, int] | None = None):
    """Evaluate a predicate tree -> (packed mask, code_bits of its layout).

    The mask comes in `layout`, (code_bits, words), or by default in the
    leftmost leaf's. A node combines the children of one layout in that
    layout first, then repacks each other layout's combined mask once to
    its own. Always validity-masked.
    """
    combine = jnp.bitwise_or if isinstance(plan, Or) else jnp.bitwise_and
    if isinstance(plan, Pred):
        s = slices[plan.column]
        m = scan_ops.scan_filter(s.words, plan.constant, plan.op,
                                 s.code_bits, mode=mode) & s.valid
        parts = {(s.code_bits, m.shape[0]): m}
    elif isinstance(plan, (And, Or)):
        parts = {}
        for c in plan.children:
            m, b = eval_mask(c, slices, mode)
            key = (b, m.shape[0])
            parts[key] = combine(parts[key], m) if key in parts else m
    else:
        raise ValueError(f"unknown plan node {type(plan).__name__!r}")
    bits, words = layout or next(iter(parts))
    out = None
    for (b, _), m in parts.items():
        m = repack_ops.repack_mask(m, b, bits, words, mode=mode)
        out = m if out is None else combine(out, m)
    return out, bits


def _psum_aggs(d: dict, axis: str) -> dict:
    """Cross-shard combine: the masked-aggregate fields are associative.
    Sum planes are normalized (< 2^16 lo per shard), so the psum stays
    int32-exact; the planes are reassembled host-side by finalize_aggs."""
    return {"sum_lo": jax.lax.psum(d["sum_lo"], axis),
            "sum_hi": jax.lax.psum(d["sum_hi"], axis),
            "count": jax.lax.psum(d["count"], axis),
            "min": jax.lax.pmin(d["min"], axis),
            "max": jax.lax.pmax(d["max"], axis)}


def finalize_aggs(out: dict) -> dict:
    """{column: device aggregate dict} -> {column: exact host-int dict}
    with the 16-bit sum planes reassembled (the only step allowed to
    exceed int32, hence Python ints), after one blocking read of them
    all."""
    with obs_trace.span("query.finalize"):
        return {col: agg_ops.finalize(d)
                for col, d in agg_ops.fetch(out).items()}


def referenced_bytes(plan: Plan, aggregates, columns: dict) -> int:
    """Bytes a query streams from memory — every referenced column's
    *physical* footprint (compressed for repro.store columns; the model's
    `percent accessed` numerator either way)."""
    return sum(columns[c].nbytes
               for c in columns_of(plan) | set(aggregates))


def referenced_logical_bytes(plan: Plan, aggregates, columns: dict) -> int:
    """Bytes the query covers in the plain format — equal to
    referenced_bytes on uncompressed tables; on a compressed store the
    physical/logical ratio is the bandwidth multiplier compression buys."""
    return sum(getattr(columns[c], "logical_nbytes", columns[c].nbytes)
               for c in columns_of(plan) | set(aggregates))


# --- chunk-granular accounting (repro.tier placement) ---------------------

def align_chunk_rows(columns: dict, chunk_rows: int) -> int:
    """Round `chunk_rows` up so a row-range boundary is a word boundary
    for every column (multiple of each width's codes-per-word). The one
    alignment invariant shared by tier chunking and shard splitting
    (sharded.shard_rows starts from the same word boundary)."""
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows={chunk_rows} must be >= 1")
    align = math.lcm(*(32 // c.code_bits for c in columns.values()))
    return -(-chunk_rows // align) * align


def column_chunk_bytes(total_words: int, code_bits: int,
                       chunk_rows: int) -> list[int]:
    """Packed bytes per row-chunk of one column (last chunk ragged).
    `chunk_rows` must already be word-aligned for this width."""
    wpc = chunk_rows * code_bits // 32
    return [4 * (min((i + 1) * wpc, total_words) - i * wpc)
            for i in range(-(-total_words // wpc))]


def chunk_universe(source: dict, chunk_rows: int,
                   names=None) -> dict[tuple[str, int], int]:
    """(column, chunk-index) -> bytes over `source` columns (objects with
    `.words`/`.code_bits` — table columns or sharded slices). The single
    enumeration shared by the placement universe, flat-table accounting,
    and sharded accounting, so chunk-id semantics cannot diverge.
    `chunk_rows` must already be aligned (align_chunk_rows)."""
    out: dict[tuple[str, int], int] = {}
    for name in (sorted(names) if names is not None else source):
        col = source[name]
        if hasattr(col, "chunk_physical_bytes"):
            # repro.store encoded columns carry their own per-chunk
            # (compressed) byte counts; chunk ids stay row-range-aligned
            per_chunk = col.chunk_physical_bytes(chunk_rows)
        else:
            per_chunk = column_chunk_bytes(int(col.words.size),
                                           col.code_bits, chunk_rows)
        for i, b in enumerate(per_chunk):
            out[(name, i)] = b
    return out


def referenced_chunk_bytes(plan: Plan, aggregates, columns: dict,
                           chunk_rows: int) -> dict[tuple[str, int], int]:
    """Per-(column, chunk) bytes a query streams — the access record the
    tier placement engine charges. Scans stream every chunk of every
    referenced column; the split across tiers is the placement engine's
    decision, the byte totals are this layer's ground truth."""
    return chunk_universe(columns, align_chunk_rows(columns, chunk_rows),
                          names=columns_of(plan) | set(aggregates))


def execute(plan: Plan, aggregates: tuple, slices: dict[str, ColumnSlice],
            mode=None, axis: str | None = None) -> dict:
    """Run a bound plan -> {agg_column: {sum, count, min, max}}.

    Traceable: called directly for single-device tables and per-shard
    inside shard_map (axis names the mesh axis to combine over).
    """
    out: dict[str, dict] = {}
    fused = (isinstance(plan, Pred) and len(aggregates) == 1
             and slices[plan.column].code_bits
             == slices[aggregates[0]].code_bits
             and slices[plan.column].words.shape
             == slices[aggregates[0]].words.shape)
    if fused:
        p, a = slices[plan.column], slices[aggregates[0]]
        out[aggregates[0]] = fused_ops.scan_aggregate(
            p.words, a.words, p.valid, plan.constant, plan.op, p.code_bits,
            mode=mode)
    else:
        layouts = list(dict.fromkeys(
            (slices[c].code_bits, slices[c].words.shape[0])
            for c in aggregates))
        mask, mbits = eval_mask(plan, slices, mode,
                                layouts[0] if len(layouts) == 1 else None)
        masks = {key: repack_ops.repack_mask(mask, mbits, *key, mode=mode)
                 for key in layouts}
        for col in aggregates:
            s = slices[col]
            out[col] = agg_ops.aggregate(
                s.words, masks[(s.code_bits, s.words.shape[0])],
                s.code_bits, mode=mode)
    if axis is not None:
        out = {col: _psum_aggs(d, axis) for col, d in out.items()}
    return out
