"""Fused masked aggregate (sum/count/min/max) Pallas TPU kernel.

One pass over the packed column + packed predicate mask (the scan kernel's
output): per grid step a (block_rows, 128) word tile is unpacked field-wise
in VREGs (static shift loop, no gather), masked, and reduced to scalars
that fold into SMEM scratch accumulators; each chunk's last grid step
writes its 5 scalars. With the scan kernel this forms the paper's
scan+aggregate query plan executing at HBM bandwidth (arithmetic intensity
~= 2 int-ops/byte).

The sum leaves the kernel as two normalized 16-bit planes (sum_hi, sum_lo):
int32 wraps after ~65k selected rows of a 16-bit column and TPUs have no
int64, so each tile's (exact, block-size-bounded) int32 partial is split
16/16 into two accumulators, renormalized after every tile. See
aggregate/ref.py for the bounds; ops.py clamps block_rows so a tile partial
can never wrap.

TPU layout: scalars live in SMEM (Mosaic cannot store scalars to VMEM),
and each chunk's output is one lane-dense (8, 128) int32 VMEM tile whose
lanes 0..4 hold [sum_lo, sum_hi, count, min, max] — a (1, 5) block over
an (n_chunks, 5) array breaks the (8, 128) tiling rule. The jitted entry
points slice the tiles back to the int32[n_chunks, 5] contract. The
scan_aggregate and scan_compressed families share these helpers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES
from repro.obs import metrics as obs_metrics

# one chunk's output tile: the smallest int32 block the (8, 128) rule allows
OUT_TILE = (8, LANES)


def acc_scratch():
    """SMEM scratch for [sum_lo, sum_hi, count, min, max]."""
    return pltpu.SMEM((5,), jnp.int32)


def out_spec(index_map):
    """Per-chunk lane-dense output block over (n_chunks, 8, 128)."""
    return pl.BlockSpec((1,) + OUT_TILE, index_map)


def out_shape(n_chunks: int):
    return jax.ShapeDtypeStruct((n_chunks,) + OUT_TILE, jnp.int32)


def rows_of(tiles):
    """(n_chunks, 8, 128) output tiles -> int32[n_chunks, 5]."""
    return tiles[:, 0, :5]


def init_acc(acc, vmax: int) -> None:
    acc[0] = jnp.int32(0)      # sum_lo (16-bit plane)
    acc[1] = jnp.int32(0)      # sum_hi
    acc[2] = jnp.int32(0)      # count
    acc[3] = jnp.int32(vmax)   # min
    acc[4] = jnp.int32(0)      # max


def fold_acc(acc, s, cnt, mn, mx) -> None:
    """Fold one tile's exact int32 partials in. The sum splits 16/16 and
    the lo plane renormalizes every tile, so neither plane wraps however
    many tiles a chunk has (hi < 2^31 while the sum is < 2^47)."""
    lo = acc[0] + (s & 0xFFFF)
    acc[0] = lo & 0xFFFF
    acc[1] += (s >> 16) + (lo >> 16)
    acc[2] += cnt
    acc[3] = jnp.minimum(acc[3], mn)
    acc[4] = jnp.maximum(acc[4], mx)


def write_row(o_ref, acc) -> None:
    """Write the chunk's 5 scalars to lanes 0..4 of its output tile."""
    vals = tuple(acc[k] for k in range(5))
    lane = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 1)
    tile = jnp.zeros(OUT_TILE, jnp.int32)
    for k, v in enumerate(vals):
        tile = jnp.where(lane == k, v, tile)
    o_ref[0] = tile


def field_reduce(x, m, *, code_bits: int, vmax: int):
    """Masked (sum, count, min, max) of one tile of packed words `x`
    under packed delimiter mask `m`, as exact int32 scalars."""
    c = 32 // code_bits
    value_mask = jnp.uint32((1 << (code_bits - 1)) - 1)
    s = jnp.int32(0)
    cnt = jnp.int32(0)
    mn = jnp.int32(vmax)
    mx = jnp.int32(0)
    for f in range(c):                       # static unroll over fields
        vals = ((x >> jnp.uint32(f * code_bits)) & value_mask).astype(
            jnp.int32)
        bit = ((m >> jnp.uint32(f * code_bits + code_bits - 1))
               & jnp.uint32(1)).astype(jnp.int32)
        sel = bit == 1
        s += jnp.sum(vals * bit)
        cnt += jnp.sum(bit)
        mn = jnp.minimum(mn, jnp.min(jnp.where(sel, vals, vmax)))
        mx = jnp.maximum(mx, jnp.max(jnp.where(sel, vals, 0)))
    return s, cnt, mn, mx


def pad_rows(planes, block_rows: int):
    """Zero-pad (n_chunks, rows, 128) planes to a block multiple ->
    (planes, padded rows, block_rows)."""
    rows = planes[0].shape[1]
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        obs_metrics.count("tile_pads")
        planes = [jnp.pad(p, ((0, 0), (0, pad), (0, 0))) for p in planes]
    return planes, rows + pad, block_rows


def _agg_batched_kernel(x_ref, m_ref, o_ref, acc, *, code_bits: int,
                        vmax: int):
    """Grid (n_chunks, inner), one output row per chunk. Inner steps
    iterate fastest, so the accumulator resets at inner step 0 and is
    written back at the last inner step."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        init_acc(acc, vmax)

    fold_acc(acc, *field_reduce(x_ref[0], m_ref[0], code_bits=code_bits,
                                vmax=vmax))

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        write_row(o_ref, acc)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "block_rows", "interpret"))
def aggregate_batched_packed(words3d, mask3d, *, code_bits: int,
                             block_rows: int = DEFAULT_BLOCK_ROWS,
                             interpret: bool = True):
    """(n_chunks, rows, 128) packed words + packed masks ->
    int32[n_chunks, 5], one [sum_lo, sum_hi, count, min, max] row per
    chunk, all chunks in ONE kernel launch. Padded words carry zero mask
    delimiter bits and contribute nothing."""
    (words3d, mask3d), rows, block_rows = pad_rows([words3d, mask3d],
                                                   block_rows)
    n_chunks = words3d.shape[0]
    vmax = (1 << (code_bits - 1)) - 1
    kernel = functools.partial(_agg_batched_kernel, code_bits=code_bits,
                               vmax=vmax)
    spec = pl.BlockSpec((1, block_rows, LANES), lambda c, i: (c, i, 0))
    return rows_of(pl.pallas_call(
        kernel,
        grid=(n_chunks, rows // block_rows),
        in_specs=[spec, spec],
        out_specs=out_spec(lambda c, i: (c, 0, 0)),
        out_shape=out_shape(n_chunks),
        scratch_shapes=[acc_scratch()],
        interpret=interpret,
    )(words3d, mask3d))


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "block_rows", "interpret"))
def aggregate_packed(words2d, mask2d, *, code_bits: int,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = True):
    """(rows, 128) packed words + packed mask -> int32[1, 5] =
    [sum_lo, sum_hi, count, min, max] (sum = sum_hi * 65536 + sum_lo):
    the batched kernel over one chunk."""
    return aggregate_batched_packed(words2d[None], mask2d[None],
                                    code_bits=code_bits,
                                    block_rows=block_rows,
                                    interpret=interpret)
