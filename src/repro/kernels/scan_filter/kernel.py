"""BitWeaving-H predicate scan as a Pallas TPU kernel.

TPU adaptation of Li & Patel (SIGMOD'13): codes are packed `codes_per_word`
to an int32 lane with a per-field delimiter MSB kept 0 in the data; a whole
word of codes is compared against a constant with three VPU integer ops
(no per-code unpacking, no warp primitives needed):

  GE:  ((X | H) - C) & H          — the borrow clears the delimiter
  EQ:  ~((X^C | H) - L) & H       — zero-test via low-bit borrow

The grid streams (block_rows, 128)-word VMEM tiles from HBM; arithmetic
intensity is ~3 int-ops per 4 bytes, i.e. the paper's bandwidth-bound scan
regime (this kernel is what `core_perf` measures for the analytic model).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.scan_filter.ref import field_masks
from repro.obs import metrics as obs_metrics

LANES = 128
DEFAULT_BLOCK_ROWS = 256
# words in one default (block_rows, 128) tile: a plane of a multiple of
# this many words reaches the kernels with nothing to pad or slice
TILE_WORDS = LANES * DEFAULT_BLOCK_ROWS


def _scan_kernel(x_ref, o_ref, *, op: str, const_packed, delim, low):
    x = x_ref[...]
    h = jnp.uint32(delim)
    if op == "ge":
        o_ref[...] = ((x | h) - jnp.uint32(const_packed)) & h
    elif op == "eq":
        z = x ^ jnp.uint32(const_packed)
        o_ref[...] = (~((z | h) - jnp.uint32(low))) & h
    else:
        raise ValueError(op)


@functools.partial(jax.jit,
                   static_argnames=("constant", "op", "code_bits",
                                    "block_rows", "interpret"))
def scan_packed(words2d, constant: int, *, op: str, code_bits: int,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = True):
    """words2d: (rows, 128) uint32 packed codes. Returns packed delimiter
    mask words of the same shape. `op` is a kernel primitive: ge | eq.

    Arbitrary row counts are supported: rows are zero-padded up to the next
    block multiple and the pad is sliced off the output."""
    rows = words2d.shape[0]
    assert words2d.shape[1] == LANES, words2d.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        obs_metrics.count("tile_pads")
        words2d = jnp.pad(words2d, ((0, pad), (0, 0)))
    grid_rows = rows + pad
    delim, low, value = field_masks(code_bits)
    c = 32 // code_bits
    const_packed = 0
    for i in range(c):
        const_packed |= (int(constant) & int(value)) << (i * code_bits)

    kernel = functools.partial(_scan_kernel, op=op,
                               const_packed=const_packed,
                               delim=int(delim), low=int(low))
    out = pl.pallas_call(
        kernel,
        grid=(grid_rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid_rows, LANES), jnp.uint32),
        interpret=interpret,
    )(words2d)
    return out[:rows] if pad else out
