"""Scan-over-compressed Pallas TPU kernel: fused predicate + aggregate
directly on RLE runs.

The bandwidth argument, squared: the plain fused kernel already avoids the
mask round-trip; this one avoids touching *rows* at all. Per grid step a
(block_rows, 128) tile of run values is compared against the constant on
the VPU (runs hold decoded codes, so all six predicates are plain int32
compares — no BitWeaving masks needed) and reduced against the matching
run-length tile: a run of length n contributes n to the count and n*value
to the sum, entirely in registers and SMEM scalars. A chunk of r rows in
k runs streams 8k bytes instead of 4*ceil(r/cpw) — on sorted or
low-cardinality columns that is a 10-100x traffic cut at identical
answers.

Exactness: the store bounds chunks at 65536 rows with payloads < 2^15, so
every partial (value*length summed over a chunk) stays below 2^31 and the
int32 tile partial is exact; it folds into the 16/16 sum planes all
aggregate paths share (aggregate/kernel.py holds the layout helpers).
Zero-length runs (pow2 padding) are cancelled by the `lengths > 0` term
of the selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.aggregate.kernel import (acc_scratch, fold_acc, init_acc,
                                            out_shape, out_spec, pad_rows,
                                            rows_of, write_row)
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES


def _rle_batched_kernel(v_ref, l_ref, o_ref, acc, *, op: str, constant: int,
                        vmax: int):
    """Grid (n_chunks, inner); one output row per chunk. The inner
    dimension iterates fastest (TPU grid order), so the per-chunk
    accumulator resets at inner step 0 and writes back normalized at the
    last inner step — chunk c's partial never sees chunk c±1's tiles."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        init_acc(acc, vmax)

    v = v_ref[0]
    l = l_ref[0]
    c = jnp.int32(constant)
    cmp = {"lt": v < c, "le": v <= c, "gt": v > c, "ge": v >= c,
           "eq": v == c, "ne": v != c}[op]
    sel = cmp & (l > 0)
    fold_acc(acc, jnp.sum(jnp.where(sel, v * l, 0)),
             jnp.sum(jnp.where(sel, l, 0)),
             jnp.min(jnp.where(sel, v, vmax)),
             jnp.max(jnp.where(sel, v, 0)))

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        write_row(o_ref, acc)


@functools.partial(jax.jit,
                   static_argnames=("constant", "op", "code_bits",
                                    "block_rows", "interpret"))
def rle_scan_aggregate_batched_packed(values3d, lengths3d, *, constant: int,
                                      op: str, code_bits: int,
                                      block_rows: int = DEFAULT_BLOCK_ROWS,
                                      interpret: bool = True):
    """(n_chunks, rows, 128) int32 run planes -> int32[n_chunks, 5]: one
    [sum_lo, sum_hi, count, min, max] row per chunk, all chunks in ONE
    kernel launch. Rows are zero-padded per chunk to the block multiple
    and across chunks to the widest chunk; padded runs carry length 0 and
    contribute to no accumulator."""
    (values3d, lengths3d), rows, block_rows = pad_rows(
        [values3d, lengths3d], block_rows)
    n_chunks = values3d.shape[0]
    vmax = (1 << (code_bits - 1)) - 1
    kernel = functools.partial(_rle_batched_kernel, op=op,
                               constant=int(constant), vmax=vmax)
    spec = pl.BlockSpec((1, block_rows, LANES), lambda c, i: (c, i, 0))
    return rows_of(pl.pallas_call(
        kernel,
        grid=(n_chunks, rows // block_rows),
        in_specs=[spec, spec],
        out_specs=out_spec(lambda c, i: (c, 0, 0)),
        out_shape=out_shape(n_chunks),
        scratch_shapes=[acc_scratch()],
        interpret=interpret,
    )(values3d, lengths3d))


@functools.partial(jax.jit,
                   static_argnames=("constant", "op", "code_bits",
                                    "block_rows", "interpret"))
def rle_scan_aggregate_packed(values2d, lengths2d, *, constant: int,
                              op: str, code_bits: int,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool = True):
    """(rows, 128) int32 run-value/run-length planes -> int32[1, 5]
    = [sum_lo, sum_hi, count, min, max] over the rows the runs encode:
    the batched kernel over one chunk."""
    return rle_scan_aggregate_batched_packed(
        values2d[None], lengths2d[None], constant=constant, op=op,
        code_bits=code_bits, block_rows=block_rows, interpret=interpret)
