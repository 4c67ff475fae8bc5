"""Public fused scan+aggregate API, dispatched through
repro.kernels.dispatch.

The full predicate set {lt, le, gt, ge, eq, ne} is composed from the
kernel's {ge, eq} primitives plus an in-kernel complement, by
scan_filter's composition rules (`canonical_pred`); a leaf that selects
nothing (gt at the payload max) makes the answer the empty-selection
identity, and one that selects every valid row (le at or above it) drops
out of the conjunction.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.aggregate import ref as agg_ref
from repro.kernels.scan_aggregate import kernel as K
from repro.kernels.scan_aggregate import ref
from repro.kernels.scan_filter import ops as scan_ops
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS
from repro.obs import metrics as obs_metrics


def scan_conjunction(words: dict, valid_words, preds, aggregates,
                     code_bits: int, mode=None) -> dict:
    """Fused SELECT agg(a) for a in aggregates WHERE p1 AND p2 AND ... in
    one launch -> {a: dict(sum_lo, sum_hi, count, min, max)}; reassemble
    each exact sum with repro.kernels.aggregate.ops.finalize.

    words: {column: (n_words,) packed words}, every column at one code
    width and word count; preds: ((column, op, constant), ...) leaves of
    the conjunction; valid_words: the packed delimiter-bit validity mask
    the columns share (bits set only for real rows), which cancels
    tail-of-word and shard padding.
    """
    # canonical_pred refuses an unknown op in either mode
    leaves = [(col, *scan_ops.canonical_pred(op, c, code_bits))
              for col, op, c in preds]
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_aggregate")
    aggregates = tuple(dict.fromkeys(aggregates))
    if not r.use_pallas:
        return ref.scan_conjunction_ref(words, valid_words, preds,
                                        aggregates, code_bits)
    # degenerate leaves fold in here: one that selects nothing makes the
    # answer the empty-selection identity, one that selects every valid
    # row drops out (and its column is not read)
    if (any(leaf[1:] == scan_ops.SELECT_NONE for leaf in leaves)
            or valid_words.size == 0):    # zero-row grid is undefined
        return {a: agg_ref.identity(code_bits) for a in aggregates}
    leaves = [leaf for leaf in leaves if leaf[1:] != scan_ops.SELECT_ALL]
    names = tuple(dict.fromkeys([leaf[0] for leaf in leaves]
                                + list(aggregates)))
    idx = {n: i for i, n in enumerate(names)}
    out = K.scan_conjunction_packed(
        tuple(_to2d(words[n]) for n in names), _to2d(valid_words),
        leaves=tuple((idx[c], prim, cst, inv)
                     for c, prim, cst, inv in leaves),
        aggs=tuple(idx[a] for a in aggregates), code_bits=code_bits,
        tile_rows=min(DEFAULT_BLOCK_ROWS,
                      agg_ops.sum_bound_block_rows(code_bits)),
        interpret=r.interpret)
    return {a: {"sum_lo": out[k, 0], "sum_hi": out[k, 1],
                "count": out[k, 2], "min": out[k, 3], "max": out[k, 4]}
            for k, a in enumerate(aggregates)}


def _to2d(words):
    """(n_words,) packed words -> (rows, 128) kernel planes, the last row
    zero-filled (zero validity words select nothing)."""
    return scan_ops._to_2d(jnp.asarray(words, jnp.uint32))[0]


def scan_aggregate_batched(pred3, agg3, valid3, triples, code_bits: int,
                           block_rows: int | None = None, mode=None):
    """All chunks of one (pred, agg) column pair in ONE launch.

    pred3/agg3/valid3: (n_chunks, n_words) packed word planes (every
    chunk already repacked to the shared `code_bits`). triples: per-chunk
    canonical (prim, constant, invert) from scan_filter.ops.canonical_pred
    — per-chunk FOR frames translate the constant differently, and the
    batched kernel carries that difference as scalar-prefetched data.
    Returns int32[n_chunks, 5]; each row is bit-identical to
    `scan_conjunction` over that chunk with its one leaf."""
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_aggregate")
    p = jnp.asarray(pred3, jnp.uint32)
    n_chunks = p.shape[0]
    if len(triples) != n_chunks:
        raise ValueError(f"{len(triples)} triples for {n_chunks} chunks")
    if n_chunks == 0 or p.shape[1] == 0:     # empty-selection identities
        vmax = (1 << (code_bits - 1)) - 1
        return jnp.tile(jnp.asarray([[0, 0, 0, vmax, 0]], jnp.int32),
                        (n_chunks, 1))
    if not r.use_pallas:
        consts, flags = scan_ops.packed_triples(triples, code_bits)
        return _fused_batched_ref(p, jnp.asarray(agg3, jnp.uint32),
                                  jnp.asarray(valid3, jnp.uint32),
                                  consts, flags, code_bits)

    consts, flags = scan_ops.packed_triples(triples, code_bits)
    p3 = agg_ops.to3d_words(p)
    a3 = agg_ops.to3d_words(agg3)
    v3 = agg_ops.to3d_words(valid3)
    rows = p3.shape[1]
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    br = min(br, agg_ops.sum_bound_block_rows(code_bits))
    return K.scan_aggregate_batched_packed(
        jnp.asarray(consts), jnp.asarray(flags), p3, a3, v3,
        code_bits=code_bits, block_rows=br, interpret=r.interpret)


@partial(jax.jit, static_argnums=5)
def _fused_batched_ref(p3, a3, v3, consts, flags, code_bits: int):
    """The whole ref fused path as one compiled call — mask planes and
    batched aggregate fuse, and the traced constants mean every query at
    a given plane shape reuses the same executable."""
    mask3 = scan_ops.mask_planes(p3, consts, flags, code_bits) & v3
    return agg_ref.aggregate_batched_ref(a3, mask3, code_bits)


def _example(rng):
    from repro.kernels.scan_filter import ref as scan_ref
    n = 5001                                  # exercises the tail validity
    words = {c: jnp.asarray(scan_ref.pack(rng.integers(0, 128, n), 8))
             for c in "abc"}
    valid = jnp.asarray(scan_ref.valid_mask(words["a"].size, n, 8))
    preds = (("a", "lt", 64), ("b", "ge", 10), ("a", "ne", 7),
             ("c", "le", 127))
    return (words, valid, preds, ("b", "c"), 8), {}


dispatch.register(
    "scan_aggregate", fn=scan_conjunction, ref=ref.scan_conjunction_ref,
    example=_example)
