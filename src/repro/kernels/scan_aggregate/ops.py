"""Public fused scan+aggregate API, dispatched through
repro.kernels.dispatch.

The full predicate set {lt, le, gt, ge, eq, ne} is composed from the
kernel's {ge, eq} primitives plus an in-kernel complement, mirroring
scan_filter's composition rules; the two degenerate compositions (gt at the
payload max, le at/above it) short-circuit to the empty-selection identity
and a plain validity-mask aggregate respectively.
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
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES
from repro.kernels.scan_filter.ref import OPS
from repro.obs import metrics as obs_metrics


def scan_aggregate(pred_words, agg_words, valid_words, constant: int,
                   op: str, code_bits: int, block_rows: int | None = None,
                   mode=None) -> dict:
    """Fused SELECT agg(agg_col) WHERE pred_col <op> constant over packed
    words of one shared code width ->
    dict(sum_lo, sum_hi, count, min, max); reassemble the exact sum with
    repro.kernels.aggregate.ops.finalize.

    valid_words is the packed delimiter-bit validity mask (bits set only
    for real rows); it cancels tail-of-word and shard padding.
    """
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of {OPS}")
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_aggregate")
    if not r.use_pallas:
        return ref.scan_aggregate_ref(pred_words, agg_words, valid_words,
                                      constant, op, code_bits)
    if pred_words.size == 0:          # zero-row grid is undefined
        return agg_ref.identity(code_bits)

    vmax = (1 << (code_bits - 1)) - 1
    c = int(constant)
    if op in ("ge", "eq"):
        prim, cc, inv = op, c, False
    elif op == "lt":
        prim, cc, inv = "ge", c, True
    elif op == "ne":
        prim, cc, inv = "eq", c, True
    elif op == "gt":
        if c >= vmax:                 # nothing exceeds the payload max
            return agg_ref.identity(code_bits)
        prim, cc, inv = "ge", c + 1, False
    else:  # le
        if c >= vmax:                 # everything valid matches
            return agg_ops.aggregate(agg_words, valid_words, code_bits,
                                     mode=mode)
        prim, cc, inv = "ge", c + 1, True

    def to2d(w):
        w = jnp.asarray(w, jnp.uint32)
        return jnp.pad(w, (0, (-w.shape[0]) % LANES)).reshape(-1, LANES)

    p2d, a2d, v2d = to2d(pred_words), to2d(agg_words), to2d(valid_words)
    rows = p2d.shape[0]
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    br = min(br, agg_ops.sum_bound_block_rows(code_bits))
    out = K.scan_aggregate_packed(p2d, a2d, v2d, constant=cc, op=prim,
                                  invert=inv, code_bits=code_bits,
                                  block_rows=br, interpret=r.interpret)
    return {"sum_lo": out[0, 0], "sum_hi": out[0, 1], "count": out[0, 2],
            "min": out[0, 3], "max": out[0, 4]}


def scan_aggregate_batched(pred3, agg3, valid3, triples, code_bits: int,
                           block_rows: int | None = None, mode=None):
    """All chunks of one (pred, agg) column pair in ONE launch.

    pred3/agg3/valid3: (n_chunks, n_words) packed word planes (every
    chunk already repacked to the shared `code_bits`). triples: per-chunk
    canonical (prim, constant, invert) from scan_filter.ops.canonical_pred
    — per-chunk FOR frames translate the constant differently, and the
    batched kernel carries that difference as scalar-prefetched data.
    Returns int32[n_chunks, 5]; each row is bit-identical to the
    per-chunk `scan_aggregate` composition for that chunk."""
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
    pw = scan_ref.pack(rng.integers(0, 128, n), 8)
    aw = scan_ref.pack(rng.integers(0, 128, n), 8)
    valid = scan_ref.valid_mask(pw.size, n, 8)
    return (jnp.asarray(pw), jnp.asarray(aw), jnp.asarray(valid),
            64, "lt", 8), {}


dispatch.register(
    "scan_aggregate", fn=scan_aggregate, ref=ref.scan_aggregate_ref,
    example=_example)
