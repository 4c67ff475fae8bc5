"""Fused BitWeaving scan + masked aggregate Pallas TPU kernels.

"Processing Data Where It Makes Sense" applied inside one chip: predicate
masks never round-trip through HBM. Per grid step the kernels compare
(rows, 128) word tiles of the predicate columns against their constants
with the scan kernel's VPU bit-tricks (GE/EQ primitives, optional
complement for the composed lt/le/ne forms), AND the results with the
validity mask in VREGs (tail/shard padding rows carry zero delimiter
bits), and reduce the aggregate columns' tiles under that mask into SMEM
scalar accumulators (the layout helpers are shared with
aggregate/kernel.py).

- `scan_conjunction_packed`: one pass over one plane per column for a
  conjunction of leaf predicates and any number of aggregate columns at
  one layout — TPC-H Q6 reads its three columns and one validity plane
  once, where the mask path writes and re-reads five masks.
- `scan_aggregate_batched_packed`: one (predicate, aggregate) column pair
  over many chunks, the predicate per chunk carried as data (the encoded
  store's batched launch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.aggregate.kernel import (acc_scratch, field_reduce,
                                            fold_acc, init_acc, out_shape,
                                            out_spec, pad_rows, rows_of,
                                            write_row)
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES
from repro.kernels.scan_filter.ref import field_masks


def _fused_batched_kernel(const_ref, flag_ref, p_ref, a_ref, v_ref, o_ref,
                          acc, *, delim, low, code_bits: int, vmax: int):
    """Grid (n_chunks, inner), one output row per chunk. The per-chunk
    predicate rides in as data — scalar-prefetched planes of packed
    constants and flag words (bit0 = eq primitive, bit1 = invert) indexed
    by the chunk grid coordinate — so chunks whose FOR frames translated
    the constant differently still share one launch. Inner steps iterate
    fastest: reset at inner 0, normalized writeback at the last inner
    step."""
    c_id = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        init_acc(acc, vmax)

    x = p_ref[0]
    h = jnp.uint32(delim)
    # packed constants keep delimiter bits 0, so int32 -> uint32 is safe
    cst = const_ref[c_id].astype(jnp.uint32)
    flags = flag_ref[c_id]
    m_ge = ((x | h) - cst) & h
    m_eq = (~(((x ^ cst) | h) - jnp.uint32(low))) & h
    m = jnp.where((flags & 1) == 1, m_eq, m_ge)
    m = jnp.where((flags & 2) == 2, m ^ h, m)   # m subset-of h: ^h == ~m&h
    m = m & v_ref[0]

    fold_acc(acc, *field_reduce(a_ref[0], m, code_bits=code_bits,
                                vmax=vmax))

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        write_row(o_ref, acc)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "block_rows", "interpret"))
def scan_aggregate_batched_packed(consts, flags, pred3d, agg3d, valid3d, *,
                                  code_bits: int,
                                  block_rows: int = DEFAULT_BLOCK_ROWS,
                                  interpret: bool = True):
    """All chunks of one (pred, agg) column pair in ONE launch.

    consts/flags: (n_chunks,) int32 scalar planes from
    scan_filter.ops.packed_triples (per-chunk packed constant + eq/invert
    flags), scalar-prefetched so the grid's chunk coordinate selects each
    tile's predicate without re-specializing the kernel.
    pred3d/agg3d/valid3d: (n_chunks, rows, 128) packed word planes.
    Returns int32[n_chunks, 5] of [sum_lo, sum_hi, count, min, max] rows.
    Rows are zero-padded to the block multiple; padded validity words
    carry zero delimiter bits so padding contributes to no accumulator."""
    (pred3d, agg3d, valid3d), rows, block_rows = pad_rows(
        [pred3d, agg3d, valid3d], block_rows)
    n_chunks = pred3d.shape[0]
    delim, low, value = field_masks(code_bits)
    kernel = functools.partial(_fused_batched_kernel, delim=int(delim),
                               low=int(low), code_bits=code_bits,
                               vmax=int(value))
    spec = pl.BlockSpec((1, block_rows, LANES), lambda c, i, *_: (c, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_chunks, rows // block_rows),
        in_specs=[spec, spec, spec],
        out_specs=out_spec(lambda c, i, *_: (c, 0, 0)),
        scratch_shapes=[acc_scratch()],
    )
    return rows_of(pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape(n_chunks),
        interpret=interpret,
    )(consts, flags, pred3d, agg3d, valid3d))


# most (tile_rows, 128) reduction tiles one grid step of the conjunction
# kernel reads per plane, and the VMEM its double-buffered blocks may take
CONJUNCTION_MAX_TILES = 4
CONJUNCTION_VMEM_BYTES = 12 << 20


def _conjunction_kernel(*refs, leaves, aggs, tile_rows: int, delim, low,
                        code_bits: int, vmax: int):
    """Grid (steps,): refs are the column blocks, the validity block, one
    output tile per aggregate, then one SMEM accumulator per aggregate.
    Each (tile_rows, 128) tile of a step is masked and reduced on its
    own, so every tile partial stays int32-exact whatever the block."""
    n_cols = len(refs) - 1 - 2 * len(aggs)
    cols, v_ref = refs[:n_cols], refs[n_cols]
    outs = refs[n_cols + 1:n_cols + 1 + len(aggs)]
    accs = refs[n_cols + 1 + len(aggs):]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for acc in accs:
            init_acc(acc, vmax)

    h = jnp.uint32(delim)
    for r0 in range(0, v_ref.shape[0], tile_rows):
        rows = pl.ds(r0, tile_rows)
        tiles = {}

        def tile(c):
            if c not in tiles:
                tiles[c] = cols[c][rows, :]
            return tiles[c]

        m = v_ref[rows, :]
        for c, prim, cst, inv in leaves:
            x = tile(c)
            if prim == "ge":
                lm = ((x | h) - jnp.uint32(cst)) & h
            else:
                lm = ~(((x ^ jnp.uint32(cst)) | h) - jnp.uint32(low)) & h
            # m holds delimiter bits only, so m & ~lm is the complement
            m = m & ~lm if inv else m & lm
        for acc, c in zip(accs, aggs):
            fold_acc(acc, *field_reduce(tile(c), m, code_bits=code_bits,
                                        vmax=vmax))

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        for o_ref, acc in zip(outs, accs):
            write_row(o_ref, acc)


def _tiles_per_step(n_tiles: int, n_planes: int, tile_rows: int) -> int:
    """Reduction tiles per grid step: the largest count up to
    CONJUNCTION_MAX_TILES whose double-buffered blocks of `n_planes`
    planes fit CONJUNCTION_VMEM_BYTES and which divides `n_tiles`, so the
    grid covers the planes exactly (no ragged last block)."""
    cap = CONJUNCTION_VMEM_BYTES // (2 * n_planes * tile_rows * LANES * 4)
    k = max(1, min(CONJUNCTION_MAX_TILES, cap, n_tiles))
    while n_tiles % k:
        k -= 1
    return k


@functools.partial(jax.jit,
                   static_argnames=("leaves", "aggs", "code_bits",
                                    "tile_rows", "interpret"))
def scan_conjunction_packed(cols2d, valid2d, *, leaves, aggs,
                            code_bits: int,
                            tile_rows: int = DEFAULT_BLOCK_ROWS,
                            interpret: bool = True):
    """SELECT agg(c) for c in aggs WHERE leaf AND leaf AND ... in ONE
    pass over the planes.

    cols2d: a tuple of (rows, 128) packed word planes at one code width;
    valid2d: their (shared) validity plane. leaves: ((column index,
    prim, constant, invert), ...) with prim a kernel primitive (ge | eq)
    and constant in [0, vmax]; no leaves selects every valid row.
    aggs: column indices to aggregate. Returns int32[len(aggs), 5] of
    [sum_lo, sum_hi, count, min, max] rows. `tile_rows` bounds the
    reduction tile (ops.py keeps it within the sum bound); a grid step
    reads up to CONJUNCTION_MAX_TILES tiles of every plane. Rows are
    zero-padded to a whole tile; padded validity words select nothing."""
    planes, rows, tile_rows = pad_rows(
        [p[None] for p in (*cols2d, valid2d)], tile_rows)
    planes = [p[0] for p in planes]
    block_rows = tile_rows * _tiles_per_step(
        rows // tile_rows, len(planes), tile_rows)
    delim, low, value = field_masks(code_bits)
    n_fields = 32 // code_bits
    packed = tuple(
        (c, prim, sum((cst & int(value)) << (f * code_bits)
                      for f in range(n_fields)), inv)
        for c, prim, cst, inv in leaves)
    kernel = functools.partial(
        _conjunction_kernel, leaves=packed, aggs=aggs, tile_rows=tile_rows,
        delim=int(delim), low=int(low), code_bits=code_bits,
        vmax=int(value))
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[spec] * len(planes),
        out_specs=[out_spec(lambda i: (0, 0, 0))] * len(aggs),
        out_shape=[out_shape(1)] * len(aggs),
        scratch_shapes=[acc_scratch()] * len(aggs),
        interpret=interpret,
    )(*planes)
    return jnp.concatenate([rows_of(o) for o in outs])
