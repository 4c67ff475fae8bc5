"""Query execution over an EncodedTable: scan the compressed bytes.

Default path (`batched=True`): every chunk of a column executes in ONE
kernel launch per (column-group, encoding) instead of one per chunk.

- RLE chunks of the dominant single-pred/single-agg-same-column query
  batch through `scan_compressed.rle_scan_aggregate_batched` — all run
  planes stacked, one grid, one (n_chunks, 5) partial plane;
- everything else is *width-unified*: the chunks touched by a query are
  grouped by W = max payload width of the involved columns, the narrower
  side repacked to W host-side (a delta payload always fits a wider
  field; the reverse never happens because W is the max), and then
  - single-pred/single-agg groups take ONE batched fused launch
    (`scan_aggregate_batched`) whose per-chunk translated constants ride
    in as scalar-prefetched data (each FOR chunk subtracts its own base);
  - And/Or trees and multi-aggregate queries take one batched mask per
    leaf (`scan_filter_batched`) + one batched masked aggregate per
    aggregate column — launches scale with plan size, not chunk count.

Per-chunk (1, 5) partial rows are sliced out host-side, finalized,
base-fixed and accumulated exactly as the per-chunk loop
(`batched=False`, kept as the parity oracle) — results are bit-identical
to it and to the plain-format engine regardless of encoding mix, and
every path lands on the same empty-selection identity (count=0, sum=0,
min=vmax, max=0 at the *logical* width). `translate_plan` is memoized on
the frame tuple, so N chunks sharing a frame translate once.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.scan_aggregate import ops as fused_ops
from repro.kernels.scan_compressed import ops as rle_ops
from repro.kernels.scan_filter import ops as scan_ops
from repro.kernels.scan_filter.ref import codes_per_word, pack, valid_mask
from repro.obs import metrics as obs_metrics
from repro.query import physical
from repro.query.physical import ColumnSlice
from repro.query.plan import And, Or, Plan, Pred, columns_of
from repro.store.encode import Encoding, EncodedTable


def identity_ints(code_bits: int) -> dict:
    """The empty-selection aggregate as exact host ints — the one answer
    every path (PALLAS / XLA_REF / sharded / encoded) must agree on."""
    return {"sum": 0, "count": 0, "min": (1 << (code_bits - 1)) - 1,
            "max": 0}


def fixup_base(agg: dict, base: int, code_bits: int) -> dict:
    """Translate a finalized delta-domain aggregate back to code space.

    Exact in Python ints (base*count exceeds int32 long before the planes
    would); an empty selection collapses to the canonical logical-width
    identity — the delta-domain min sentinel must not leak."""
    if agg["count"] == 0:
        return identity_ints(code_bits)
    if base == 0:
        return dict(agg)
    return {"sum": agg["sum"] + base * agg["count"],
            "count": agg["count"],
            "min": agg["min"] + base,
            "max": agg["max"] + base}


def translate_pred(op: str, constant: int, base: int,
                   width: int) -> tuple[str, int]:
    """Rewrite `col <op> constant` into the delta domain of a FOR chunk
    (codes = base + delta, deltas in [0, 2^(width-1)-1]).

    Out-of-range constants clamp to tautologies the kernels already
    short-circuit: `ge 0` matches every valid row, `gt dvmax` matches
    none — so the result is always a plain Pred and the unmodified
    physical operators execute it."""
    dvmax = (1 << (width - 1)) - 1
    c = constant - base
    all_, none = ("ge", 0), ("gt", dvmax)
    if op == "ge":
        o = all_ if c <= 0 else none if c > dvmax else (op, c)
    elif op == "gt":
        o = all_ if c < 0 else none if c >= dvmax else (op, c)
    elif op == "lt":
        o = none if c <= 0 else all_ if c > dvmax else (op, c)
    elif op == "le":
        o = none if c < 0 else all_ if c >= dvmax else (op, c)
    elif op == "eq":
        o = (op, c) if 0 <= c <= dvmax else none
    elif op == "ne":
        o = (op, c) if 0 <= c <= dvmax else all_
    else:
        raise ValueError(f"unknown predicate op {op!r}")
    return o


def translate_plan(plan: Plan, frames: dict[str, tuple[int, int]]) -> Plan:
    """Rewrite every leaf of a plan into its column's delta domain.
    `frames` maps column -> (base, payload width); base 0 at the logical
    width leaves a leaf unchanged."""
    if isinstance(plan, Pred):
        base, width = frames[plan.column]
        op, c = translate_pred(plan.op, plan.constant, base, width)
        return Pred(plan.column, op, c)
    if isinstance(plan, And):
        return And.of(*(translate_plan(p, frames) for p in plan.children))
    if isinstance(plan, Or):
        return Or.of(*(translate_plan(p, frames) for p in plan.children))
    raise ValueError(f"unknown plan node {type(plan).__name__!r}")


def jnp_pack_codes(vals, code_bits: int):
    """In-graph inverse of scan_filter.ref.unpack: row codes -> packed
    words (rows padded to a word multiple with zeros)."""
    c = codes_per_word(code_bits)
    vals = jnp.asarray(vals, jnp.uint32)
    vals = jnp.pad(vals, (0, (-vals.shape[0]) % c)).reshape(-1, c)
    shifts = jnp.arange(c, dtype=jnp.uint32) * code_bits
    return jnp.bitwise_or.reduce(vals << shifts[None, :], axis=1)


def rle_rows(chunk):
    """In-graph decode of an RLE chunk to its row codes (the fallback for
    plan shapes the run kernel does not cover)."""
    ends = jnp.cumsum(jnp.asarray(chunk.lengths, jnp.int32))
    idx = jnp.searchsorted(ends, jnp.arange(chunk.n_rows), side="right")
    return jnp.asarray(chunk.values, jnp.uint32)[idx]


@dataclass(frozen=True)
class _Bound:
    """One chunk of one column, bound for execution: a ColumnSlice plus
    the frame that maps its payload back to logical codes."""
    slice: ColumnSlice
    base: int


def _bind_chunk(col, ci: int) -> _Bound:
    ch = col.chunks[ci]
    if ch.encoding is Encoding.RLE:
        words = jnp_pack_codes(rle_rows(ch), ch.code_bits)
        return _Bound(ColumnSlice(words, ch.valid, ch.code_bits), 0)
    return _Bound(ColumnSlice(ch.words, ch.valid, ch.width), ch.base)


def _accumulate(total: dict, part: dict) -> None:
    total["sum"] += part["sum"]
    total["count"] += part["count"]
    total["min"] = min(total["min"], part["min"])
    total["max"] = max(total["max"], part["max"])


def _translate_cached(plan: Plan, frames: dict, cache: dict) -> Plan:
    """Memoized translate_plan: chunks sharing an identical
    (base, width) frame map translate once per query."""
    key = tuple(sorted(frames.items()))
    tp = cache.get(key)
    if tp is None:
        tp = cache[key] = translate_plan(plan, frames)
    return tp


@dataclass(frozen=True)
class _BoundGroup:
    """All of one column's chunks in a width group, bound for one batched
    launch: stacked packed planes at the group width W plus per-chunk
    frame bases (0 for decoded-RLE and plain chunks)."""
    words: jnp.ndarray          # (n_chunks, n_words) uint32 at width W
    valid: jnp.ndarray          # (n_chunks, n_words) packed validity
    bases: tuple


def _bind_group(col, cids, W: int) -> _BoundGroup:
    """Bind chunks `cids` of a column at the unified width W.

    A chunk narrower than W (smaller FOR delta width, or RLE decoded to
    logical codes) repacks host-side — always exact, since W is the max
    width in the group and payloads only ever widen. Ragged chunks pad to
    the widest with zero words whose validity bits are 0."""
    words_np, bases = [], []
    for ci in cids:
        ch = col.chunks[ci]
        if ch.encoding is Encoding.RLE:
            words_np.append(pack(ch.decode(), W))
            bases.append(0)
        elif ch.width == W:
            words_np.append(np.asarray(ch.words, np.uint32))
            bases.append(ch.base)
        else:
            delta = (ch.decode().astype(np.int64) - ch.base).astype(
                np.uint32)
            words_np.append(pack(delta, W))
            bases.append(ch.base)
    nw = max(w.size for w in words_np)
    words3 = np.zeros((len(cids), nw), np.uint32)
    valid3 = np.zeros((len(cids), nw), np.uint32)
    for k, (ci, w) in enumerate(zip(cids, words_np)):
        words3[k, :w.size] = w
        valid3[k] = valid_mask(nw, col.chunks[ci].n_rows, W)
    return _BoundGroup(jnp.asarray(words3), jnp.asarray(valid3),
                       tuple(bases))


def _bind_group_cached(col, cids, W: int) -> _BoundGroup:
    """Bound planes are query-independent, so they cache on the column,
    keyed by (W, cids) and validated by chunk object identity: chunk
    payloads are immutable, and every mutation path (quarantine repair)
    *replaces* the chunk object, which invalidates the entry here."""
    cache = col.__dict__.setdefault("_bind_cache", {})
    key = (W, tuple(cids))
    hit = cache.get(key)
    if hit is not None:
        chunks_then, bg = hit
        if all(col.chunks[ci] is ch for ci, ch in zip(key[1], chunks_then)):
            return bg
    bg = _bind_group(col, cids, W)
    cache[key] = (tuple(col.chunks[ci] for ci in cids), bg)
    return bg


def _batched_mask(tplans, bound, W: int, mode):
    """Packed selection masks for a width group, one batched dispatch per
    plan *leaf* (the per-chunk translated plans share the tree structure;
    only leaf constants differ). Mirrors physical.eval_mask: leaf mask
    AND validity, And/Or combined wordwise."""
    def rec(nodes):
        n0 = nodes[0]
        if isinstance(n0, Pred):
            g = bound[n0.column]
            triples = [scan_ops.canonical_pred(nd.op, nd.constant, W)
                       for nd in nodes]
            m = scan_ops.scan_filter_batched(g.words, triples, W,
                                             mode=mode)
            return m & g.valid
        subs = [rec([nd.children[k] for nd in nodes])
                for k in range(len(n0.children))]
        combine = jnp.bitwise_and if isinstance(n0, And) else jnp.bitwise_or
        acc = subs[0]
        for s in subs[1:]:
            acc = combine(acc, s)
        return acc
    return rec(tplans)


def _row_dict(row) -> dict:
    return {"sum_lo": row[0], "sum_hi": row[1], "count": row[2],
            "min": row[3], "max": row[4]}


def _chunk_payload_width(ch) -> int:
    """Payload width a chunk contributes to its group's unified W: RLE
    decodes to logical codes, FOR/plain scan at their stored width."""
    return ch.code_bits if ch.encoding is Encoding.RLE else ch.width


def _execute_batched(plan: Plan, aggregates, table: EncodedTable,
                     mode) -> dict:
    names = sorted(columns_of(plan) | set(aggregates))
    out = {a: identity_ints(table.columns[a].code_bits)
           for a in aggregates}
    fused_rle = (isinstance(plan, Pred) and aggregates == (plan.column,))
    fused = isinstance(plan, Pred) and len(aggregates) == 1

    rle_cids: list[int] = []
    groups: dict[int, list[int]] = {}
    for ci in range(table.n_chunks):
        chunks = [table.columns[n].chunks[ci] for n in names]
        if any(ch.n_rows == 0 for ch in chunks):
            continue                  # a zero-row chunk is the identity
        if fused_rle and chunks[0].encoding is Encoding.RLE:
            rle_cids.append(ci)       # names == (plan.column,) here
            continue
        W = max(_chunk_payload_width(ch) for ch in chunks)
        groups.setdefault(W, []).append(ci)

    if rle_cids:                      # one launch for every RLE chunk
        col = table.columns[plan.column]
        planes = [(col.chunks[ci].values, col.chunks[ci].lengths)
                  for ci in rle_cids]
        res = np.asarray(rle_ops.rle_scan_aggregate_batched(
            planes, plan.constant, plan.op, col.code_bits, mode=mode))
        obs_metrics.record_batch("rle_scan_aggregate", col.code_bits,
                                 len(rle_cids))
        for k in range(len(rle_cids)):
            _accumulate(out[plan.column],
                        agg_ops.finalize(_row_dict(res[k])))

    tcache: dict = {}
    for W, cids in sorted(groups.items()):
        bound = {n: _bind_group_cached(table.columns[n], cids, W)
                 for n in names}
        tplans = [_translate_cached(
            plan, {n: (bound[n].bases[k], W) for n in names}, tcache)
            for k in range(len(cids))]
        if fused:
            pcol, acol = plan.column, aggregates[0]
            triples = [scan_ops.canonical_pred(tp.op, tp.constant, W)
                       for tp in tplans]
            res = np.asarray(fused_ops.scan_aggregate_batched(
                bound[pcol].words, bound[acol].words, bound[pcol].valid,
                triples, W, mode=mode))
            obs_metrics.record_batch("scan_aggregate", W, len(cids))
            for k in range(len(cids)):
                part = fixup_base(agg_ops.finalize(_row_dict(res[k])),
                                  bound[acol].bases[k],
                                  table.columns[acol].code_bits)
                _accumulate(out[acol], part)
            continue
        mask3 = _batched_mask(tplans, bound, W, mode)
        obs_metrics.record_batch("scan_filter", W, len(cids))
        for acol in aggregates:
            g = bound[acol]
            res = np.asarray(agg_ops.aggregate_batched(g.words, mask3, W,
                                                       mode=mode))
            obs_metrics.record_batch("aggregate", W, len(cids))
            for k in range(len(cids)):
                part = fixup_base(agg_ops.finalize(_row_dict(res[k])),
                                  g.bases[k],
                                  table.columns[acol].code_bits)
                _accumulate(out[acol], part)
    return out


def execute_encoded(plan: Plan, aggregates, table: EncodedTable,
                    mode=None, guard=None, batched: bool = True) -> dict:
    """Run a bound plan over the compressed chunks -> exact host-int
    aggregates, bit-identical to the plain-format engine.

    `batched=True` (default) collapses the per-chunk kernel loop into one
    launch per (column group, encoding); `batched=False` keeps the
    original chunk-at-a-time loop as the in-tree parity oracle.

    `guard` (a resilience.ChunkGuard) makes every chunk read verify its
    checksum first: a corrupt chunk is quarantined and repaired from the
    oracle before its bytes reach a kernel, or the query dies with a
    typed ChunkCorruptionError — corrupt payloads never aggregate. All
    checks run before the first kernel launch, in (chunk, column) order,
    so quarantine/repair order matches the per-chunk loop exactly.
    """
    aggregates = tuple(aggregates)
    names = sorted(columns_of(plan) | set(aggregates))
    if guard is not None:
        for ci in range(table.n_chunks):
            guard.check([(n, ci) for n in names])
    if batched:
        return _execute_batched(plan, aggregates, table, mode)

    out = {a: identity_ints(table.columns[a].code_bits)
           for a in aggregates}
    fused_rle = (isinstance(plan, Pred) and aggregates == (plan.column,))
    tcache: dict = {}
    for ci in range(table.n_chunks):
        chunks = {n: table.columns[n].chunks[ci] for n in names}
        if fused_rle and chunks[plan.column].encoding is Encoding.RLE:
            ch = chunks[plan.column]
            d = rle_ops.rle_scan_aggregate(ch.values, ch.lengths,
                                           plan.constant, plan.op,
                                           ch.code_bits, mode=mode)
            _accumulate(out[plan.column],
                        agg_ops.finalize(agg_ops.fetch(d)))
            continue
        bound = {n: _bind_chunk(table.columns[n], ci) for n in names}
        frames = {n: (b.base, b.slice.code_bits)
                  for n, b in bound.items()}
        tplan = _translate_cached(plan, frames, tcache)
        raw = physical.execute(tplan, aggregates,
                               {n: b.slice for n, b in bound.items()},
                               mode=mode)
        raw = agg_ops.fetch(raw)
        for a in aggregates:
            part = fixup_base(agg_ops.finalize(raw[a]), bound[a].base,
                              table.columns[a].code_bits)
            _accumulate(out[a], part)
    return out


# --------------------------------------------------------------------------
# grouped execution (GroupBy / HashJoin over compressed chunks)
# --------------------------------------------------------------------------

def _grouped_strategy(query, table, names, domain_ok: bool):
    """Pick the kernels/group_aggregate strategy per chunk from its
    EncodingStats: the fused RLE run path when the key chunk is RLE and
    the query is a count-only shape whose predicate the run kernel can
    evaluate, dense accumulator planes while the (FOR-framed) group
    domain stays under DENSE_MAX_GROUPS, the host sort/hash fallback
    otherwise. Zero-row chunks are skipped (the grouped identity)."""
    from repro.query import relational
    kcol = table.columns[query.key]
    kp = relational.key_only_pred(query, kcol.code_bits)
    rle_ok = (not query.aggs) and kp is not False
    rle_cids, dense_cids, fb_cids = [], [], []
    for ci in range(table.n_chunks):
        chunks = [table.columns[n].chunks[ci] for n in names]
        if any(ch.n_rows == 0 for ch in chunks):
            continue
        if rle_ok and domain_ok \
                and kcol.chunks[ci].encoding is Encoding.RLE:
            rle_cids.append(ci)
        elif domain_ok:
            dense_cids.append(ci)
        else:
            fb_cids.append(ci)
    return rle_cids, dense_cids, fb_cids, kp


def execute_grouped_encoded(query, table: EncodedTable, mode=None,
                            guard=None) -> dict:
    """GroupBy/HashJoin over the compressed chunks -> the finalized
    grouped result, bit-identical to relational.execute_grouped_oracle
    on the decoded table.

    Batched like execute_encoded: all RLE-strategy chunks share ONE fused
    run launch, all dense-strategy chunks share ONE accumulator-plane
    launch per value column — `(n_chunks, n_groups, 3)` partials sliced
    host-side with the exact FOR base fix-up (sum += base * count) before
    the partial dicts merge. `guard` semantics match execute_encoded:
    every referenced (column, chunk) verifies before the first launch, in
    (chunk, column) order."""
    from repro.kernels.group_aggregate import ops as gops
    from repro.query import relational
    relational.bind_check(query, table.columns)
    names = sorted(columns_of(query.plan()) | set(query.aggregates))
    if guard is not None:
        for ci in range(table.n_chunks):
            guard.check([(n, ci) for n in names])

    kcol = table.columns[query.key]
    stats = [ch.stats for ch in kcol.chunks if ch.n_rows]
    if not stats:
        return relational.empty_result()
    kmin = min(s.vmin for s in stats)
    kmax = max(s.vmax for s in stats)
    domain = relational.group_domain(query, kmin, kmax)
    domain_ok = relational.dense_ok(domain) and len(domain) > 0
    rle_cids, dense_cids, fb_cids, kp = _grouped_strategy(
        query, table, names, domain_ok)
    part = relational.new_partial()

    if rle_cids:
        planes = [(kcol.chunks[ci].values, kcol.chunks[ci].lengths)
                  for ci in rle_cids]
        pred = None if kp == ("ge", 0, False) else kp
        res = np.asarray(gops.rle_group_accumulate_batched(
            planes, domain, pred=pred, mode=mode))
        obs_metrics.record_batch("rle_group_accumulate", kcol.code_bits,
                                 len(rle_cids))
        # normalized [lo, hi, count] planes are additive in int64:
        # (sum hi << 16) + sum lo == sum((hi << 16) + lo), so all RLE
        # chunks (base 0, shared domain) absorb as one summed plane
        relational.absorb_plane(part, domain,
                                res.astype(np.int64).sum(axis=0), None,
                                count_source=True)

    if dense_cids:
        decoded = {n: [table.columns[n].chunks[ci].decode()
                       for ci in dense_cids] for n in names}
        sels = []
        for k, ci in enumerate(dense_cids):
            cols = {n: decoded[n][k] for n in names}
            sels.append(np.asarray(
                relational.eval_plan_codes(query.plan(), cols), np.int32))
        keys3 = gops.lift_chunks(decoded[query.key])
        sel3 = gops.lift_chunks(sels)
        value_cols = query.aggs if query.aggs else (None,)
        for i, name in enumerate(value_cols):
            if name is None:
                vals3 = jnp.zeros_like(keys3)
                bases = [0] * len(dense_cids)
            else:
                col = table.columns[name]
                bases = [col.chunks[ci].base if col.chunks[ci].encoding
                         is Encoding.FOR else 0 for ci in dense_cids]
                vals3 = gops.lift_chunks(
                    [decoded[name][k].astype(np.int64) - bases[k]
                     for k in range(len(dense_cids))])
            res = np.asarray(gops.group_sum_count_batched(
                keys3, vals3, sel3, domain, mode=mode))
            obs_metrics.record_batch("group_sum_count", len(domain),
                                  len(dense_cids))
            for k in range(len(dense_cids)):
                relational.absorb_plane(part, domain, res[k], name,
                                        base=bases[k],
                                        count_source=(i == 0))

    if fb_cids:
        bk = relational.build_keys(query) \
            if hasattr(query, "build") else None
        obs_metrics.count_launch("group_aggregate_fallback", len(fb_cids))
        for ci in fb_cids:
            cols = {n: table.columns[n].chunks[ci].decode()
                    for n in names}
            sel = np.asarray(
                relational.eval_plan_codes(query.plan(), cols), bool)
            if bk is not None:
                sel = sel & np.isin(cols[query.key], bk)
            relational.absorb_fallback(
                part, cols[query.key],
                {a: cols[a] for a in query.aggs}, sel)
    return relational.finalize(part)
