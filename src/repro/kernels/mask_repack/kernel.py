"""Delimiter-bit mask repack between code widths as a Pallas TPU kernel.

Of the two layouts, the one with more codes per word (the narrower code)
is the dense side: a (block_rows, 128) block of it holds the rows of a
(k * block_rows, 128) block of the sparse side, k being the ratio of the
widths. Row q of every k rows of the sparse block maps to one 128/k-lane
stretch of the dense block, so the kernel

1. reads or writes the sparse block as k row-strided (block_rows, 128)
   slices, and
2. moves bits across lanes on the MXU: each word's delimiter bits are
   compacted to a small integer, fed a byte at a time (exact in
   bfloat16) to a constant 0/power-of-two (128, 128) matrix per slice,
   which gathers k lanes into one (narrowing) or copies one lane to k
   (widening); the float32 accumulator holds at most 16 bits, exactly.

The kernel reads and writes each mask word once; its MXU work is 2 *
128 * 128 operations per dense row, slice and byte.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.mask_repack.ref import compact, spread
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES


def lane_maps(from_bits: int, to_bits: int) -> np.ndarray:
    """(k, 128, 128) float32 matrices, one per row slice q of the sparse
    side. Narrowing: source lane l of slice q lands in dense lane
    (128 q + l) // k, shifted up by (l % k) fields of the source. Widening:
    dense lane (128 q + j) // k is copied to sparse lane j."""
    k = max(from_bits, to_bits) // min(from_bits, to_bits)
    lanes = np.arange(LANES)
    out = np.zeros((k, LANES, LANES), np.float32)
    for q in range(k):
        dense = (LANES * q + lanes) // k
        if from_bits > to_bits:
            out[q, lanes, dense] = 2.0 ** (lanes % k * (32 // from_bits))
        else:
            out[q, dense, lanes] = 1.0
    return out


def _to_bf16(v):
    return v.astype(jnp.float32).astype(jnp.bfloat16)


def _narrow_kernel(x_ref, p_ref, o_ref, *, k, from_bits, to_bits,
                   block_rows):
    acc = jnp.zeros((block_rows, LANES), jnp.float32)
    for q in range(k):
        v = compact(x_ref[pl.ds(q, block_rows, stride=k), :], from_bits)
        acc += jnp.dot(_to_bf16(v), p_ref[q],
                       preferred_element_type=jnp.float32)
    o_ref[...] = spread(acc.astype(jnp.int32), to_bits)


def _widen_kernel(x_ref, p_ref, o_ref, *, k, from_bits, to_bits,
                  block_rows):
    c = 32 // to_bits
    v = compact(x_ref[...], from_bits)            # < 2^(32 // from_bits)
    pieces = [(v >> s) & 0xFF for s in range(0, 32 // from_bits, 8)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    shift = (lane & (k - 1)) * c
    for q in range(k):
        y = sum(jnp.dot(_to_bf16(p), p_ref[q],
                        preferred_element_type=jnp.float32) * 256.0 ** i
                for i, p in enumerate(pieces))
        o_ref[pl.ds(q, block_rows, stride=k), :] = spread(
            (y.astype(jnp.int32) >> shift) & ((1 << c) - 1), to_bits)


@functools.partial(jax.jit, static_argnames=("from_bits", "to_bits",
                                             "block_rows", "interpret"))
def repack_mask_packed(mask2d, *, from_bits: int, to_bits: int,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: bool = True):
    """(rows, 128) uint32 mask words at `from_bits` -> the same rows'
    mask at `to_bits`: (rows // k, 128) when narrowing, (rows * k, 128)
    when widening. The dense side's rows must be a multiple of
    `block_rows` (or equal it)."""
    assert mask2d.shape[1] == LANES and from_bits != to_bits, mask2d.shape
    k = max(from_bits, to_bits) // min(from_bits, to_bits)
    narrow = from_bits > to_bits
    rows = mask2d.shape[0]
    dense_rows = rows // k if narrow else rows
    assert dense_rows % block_rows == 0 and (not narrow or rows % k == 0), \
        (rows, block_rows)
    dense = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    sparse = pl.BlockSpec((k * block_rows, LANES), lambda i: (i, 0))
    kernel = functools.partial(_narrow_kernel if narrow else _widen_kernel,
                               k=k, from_bits=from_bits, to_bits=to_bits,
                               block_rows=block_rows)
    out = pl.pallas_call(
        kernel,
        grid=(dense_rows // block_rows,),
        in_specs=[sparse if narrow else dense,
                  pl.BlockSpec((k, LANES, LANES), lambda i: (0, 0, 0))],
        out_specs=dense if narrow else sparse,
        out_shape=jax.ShapeDtypeStruct(
            (dense_rows if narrow else k * rows, LANES), jnp.int32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(mask2d, jnp.int32),
      jnp.asarray(lane_maps(from_bits, to_bits), jnp.bfloat16))
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
