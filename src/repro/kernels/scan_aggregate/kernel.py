"""Fused BitWeaving scan + masked aggregate Pallas TPU kernel.

"Processing Data Where It Makes Sense" applied inside one chip: the scan's
packed predicate mask never round-trips through HBM. Per grid step a
(block_rows, 128) tile of the predicate column is compared against the
constant with the scan kernel's VPU bit-tricks (GE/EQ primitives, optional
complement for the composed lt/le/ne forms), ANDed with the validity mask
(tail/shard padding rows carry zero delimiter bits), and immediately
reduced against the aggregate column's tile into SMEM scalar accumulators
(the layout helpers are shared with aggregate/kernel.py).

Streams 3 inputs and writes 4 scalars, vs 4 streamed tiles + a full mask
write for the scan->aggregate pipeline — at the paper's ~1 B/instr scan
regime that is a 40% traffic cut for the dominant single-predicate query.
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


@functools.partial(jax.jit,
                   static_argnames=("constant", "op", "invert", "code_bits",
                                    "block_rows", "interpret"))
def scan_aggregate_packed(pred2d, agg2d, valid2d, *, constant: int, op: str,
                          invert: bool, code_bits: int,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = True):
    """(rows, 128) packed predicate/aggregate/validity words -> int32[1, 5]
    = [sum_lo, sum_hi, count, min, max] (sum = sum_hi * 65536 + sum_lo):
    the batched kernel over one chunk. `op` is a kernel primitive
    (ge | eq); the six public predicates are composed in ops.py via
    (op, constant, invert)."""
    if op not in ("ge", "eq"):
        raise ValueError(op)
    vmax = (1 << (code_bits - 1)) - 1
    const_packed = 0
    for f in range(32 // code_bits):
        const_packed |= (int(constant) & vmax) << (f * code_bits)
    flags = (1 if op == "eq" else 0) | (2 if invert else 0)
    return scan_aggregate_batched_packed(
        jnp.full((1,), const_packed, jnp.int32),
        jnp.full((1,), flags, jnp.int32),
        pred2d[None], agg2d[None], valid2d[None], code_bits=code_bits,
        block_rows=block_rows, interpret=interpret)
