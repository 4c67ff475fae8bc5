"""Grouped aggregation Pallas TPU kernels: dense accumulator planes.

Three kernels, all writing int32 `(n_groups, 3)` accumulator planes of
[sum_lo, sum_hi, count] rows (the grouped analogue of aggregate/kernel.py's
5-scalar row):

- `_dense_*`: one pass over (rows, LANES) int32 key/value/select code
  planes. Per grid step each key of a block of group keys is compared
  against the tile in VREGs and reduced into SMEM scalar accumulators —
  a dense accumulator plane instead of a hash table, viable because the
  store's FOR frames bound the key range.
- `_rle_*`: the fused pre-grouped path over RLE run planes: a run
  (value v, length n) contributes n to group v's count and n*v to its
  sum as ONE register accumulation — no scatter, no per-row traffic. An
  optional canonical predicate on the run value is evaluated in-kernel.

Exactness mirrors the aggregate family: ops.py bounds block_rows so each
tile partial stays < 2^31, every tile partial is split 16/16 into two
running planes renormalized per tile, and the final grid step writes the
normalized pair. Group
key blocks are padded with -1 (codes are unsigned, so the sentinel never
matches); padded rows/runs carry zero select/length.

TPU layout: group keys are scalar-prefetched into SMEM, accumulators are
SMEM scalars, and each (chunk, group block) writes one lane-dense (8, 128)
output tile (rows = [sum_lo, sum_hi, count], lanes = groups of the block);
the jitted entry points reshape the tiles to the (n_chunks, G, 3)
contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.aggregate.kernel import OUT_TILE, pad_rows
from repro.kernels.scan_filter.kernel import LANES
from repro.kernels.scan_filter.ref import field_masks

DEFAULT_BLOCK_ROWS = 256
DEFAULT_GROUP_BLOCK = 8
SUBLANES = 8              # rows of one (8, 128) int32 VREG


def _accumulate(acc, gk_ref, base, gb: int, keys, vals, live, weights=None):
    """Reduce one (block_rows, LANES) tile into the (3 * gb,) SMEM scratch:
    per group of the block, a masked (weighted) sum split 16/16 plus a
    (weighted) count. Group keys are SMEM scalars read one at a time."""
    w = weights if weights is not None else jnp.int32(1)
    wv = vals * w
    for j in range(gb):                   # static unroll over the block
        match = live & (keys == gk_ref[base + j])
        s = jnp.sum(jnp.where(match, wv, 0))
        lo = acc[3 * j] + (s & 0xFFFF)       # renormalized every tile
        acc[3 * j] = lo & 0xFFFF
        acc[3 * j + 1] += (s >> 16) + (lo >> 16)
        acc[3 * j + 2] += jnp.sum(jnp.where(match, w, 0))


def _writeback(o_ref, acc, gb: int):
    """Normalized [sum_lo, sum_hi, count] of group j of the block land in
    rows 0..2 of lane j of the block's lane-dense (8, 128) output tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 1)
    tile = jnp.zeros(OUT_TILE, jnp.int32)
    for j in range(gb):
        at = lane == j
        for f in range(3):
            tile = jnp.where(at & (row == f), acc[3 * j + f], tile)
    o_ref[0, 0] = tile


def _step(acc, o_ref, gb: int, body):
    """Grid (n_chunks, n_group_blocks, inner): reset the scratch at inner
    step 0, accumulate, write the block back at the last inner step."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        for k in range(3 * gb):
            acc[k] = jnp.int32(0)

    body(pl.program_id(1) * gb)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        _writeback(o_ref, acc, gb)


def _dense_batched_kernel(gk_ref, k_ref, v_ref, s_ref, o_ref, acc, *,
                          gb: int):
    _step(acc, o_ref, gb, lambda base: _accumulate(
        acc, gk_ref, base, gb, k_ref[0], v_ref[0], s_ref[0] > 0))


def _rle_batched_kernel(gk_ref, v_ref, l_ref, o_ref, acc, *, gb: int,
                        pred):
    v = v_ref[0]
    l = l_ref[0]
    live = l > 0
    if pred is not None:                  # static: baked into the trace
        prim, const, invert = pred
        cmp = (v >= const) if prim == "ge" else (v == const)
        live = live & (cmp ^ invert)
    _step(acc, o_ref, gb, lambda base: _accumulate(
        acc, gk_ref, base, gb, v, v, live, weights=l))


def _pad_groups(group_keys, group_block):
    """(G,) keys -> (G padded to the block multiple,) keys; pads with -1,
    which no unsigned code matches."""
    g = group_keys.shape[0]
    group_block = min(group_block, max(g, 1), LANES)
    pad = (-g) % group_block
    gk = jnp.pad(jnp.asarray(group_keys, jnp.int32), (0, pad),
                 constant_values=-1)
    return gk, group_block, g


def _launch(kernel, gk, gb, planes, rows, block_rows, interpret):
    """One launch over every chunk and group block -> int32[n_chunks,
    G_padded, 3]. Group keys ride in as scalar-prefetched SMEM data."""
    n_chunks = planes[0].shape[0]
    n_gblocks = gk.shape[0] // gb
    spec = pl.BlockSpec((1, block_rows, LANES),
                        lambda c, g, i, *_: (c, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks, n_gblocks, rows // block_rows),
        in_specs=[spec] * len(planes),
        out_specs=pl.BlockSpec((1, 1) + OUT_TILE,
                               lambda c, g, i, *_: (c, g, 0, 0)),
        scratch_shapes=[pltpu.SMEM((3 * gb,), jnp.int32)],
    )
    tiles = pl.pallas_call(
        functools.partial(kernel, gb=gb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_chunks, n_gblocks) + OUT_TILE,
                                       jnp.int32),
        interpret=interpret,
    )(gk, *planes)
    # (n_chunks, n_gblocks, 3, gb) -> (n_chunks, n_gblocks * gb, 3)
    planes3 = jnp.swapaxes(tiles[:, :, :3, :gb], 2, 3)
    return planes3.reshape(n_chunks, n_gblocks * gb, 3)


@functools.partial(jax.jit, static_argnames=("block_rows", "group_block",
                                             "interpret"))
def group_sum_count_batched_planes(keys3, vals3, sel3, group_keys, *,
                                   block_rows: int = DEFAULT_BLOCK_ROWS,
                                   group_block: int = DEFAULT_GROUP_BLOCK,
                                   interpret: bool = True):
    """(n_chunks, rows, LANES) int32 key/value/select planes + (G,) group
    keys -> int32[n_chunks, G, 3] accumulator planes, all chunks and all
    group blocks in ONE kernel launch."""
    planes = [jnp.asarray(p, jnp.int32) for p in (keys3, vals3, sel3)]
    planes, rows, block_rows = pad_rows(planes, block_rows)
    gk, gb, g = _pad_groups(group_keys, group_block)
    out = _launch(_dense_batched_kernel, gk, gb, planes, rows, block_rows,
                  interpret)
    return out[:, :g]


@functools.partial(jax.jit, static_argnames=("pred", "block_rows",
                                             "group_block", "interpret"))
def rle_group_accumulate_batched_planes(vals3, lens3, group_keys, *,
                                        pred=None,
                                        block_rows: int = DEFAULT_BLOCK_ROWS,
                                        group_block: int = DEFAULT_GROUP_BLOCK,
                                        interpret: bool = True):
    """(n_chunks, runs, LANES) RLE value/length planes + (G,) group keys
    -> int32[n_chunks, G, 3]: the fused pre-grouped accumulation, one
    register update per (run, group block) with zero scatter traffic."""
    planes = [jnp.asarray(p, jnp.int32) for p in (vals3, lens3)]
    planes, runs, block_rows = pad_rows(planes, block_rows)
    gk, gb, g = _pad_groups(group_keys, group_block)
    kernel = functools.partial(_rle_batched_kernel, pred=pred)
    out = _launch(kernel, gk, gb, planes, runs, block_rows, interpret)
    return out[:, :g]


def _packed_kernel(gk_ref, k_ref, m_ref, *refs, gb: int, n_vals: int,
                   code_bits: int, block_rows: int):
    """Grid (n_group_blocks, inner). Each step walks its (block_rows, 128)
    word tile one VREG of rows at a time, keeping one (8, 128) count and
    one sum vector per group and value column in registers; the step's
    partials fold into the VMEM scratch split 16/16, and the last inner
    step reduces the scratch to the block's output tile."""
    v_refs, o_ref, acc = refs[:n_vals], refs[n_vals], refs[n_vals + 1]
    c = 32 // code_bits
    full = jnp.uint32((1 << code_bits) - 1)
    value = jnp.uint32((1 << (code_bits - 1)) - 1)
    delim = jnp.uint32(field_masks(code_bits)[0])
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.int32)

    base = pl.program_id(0) * gb
    gks = [gk_ref[base + j] for j in range(gb)]

    def body(r, carry):
        rows = pl.ds(pl.multiple_of(r * SUBLANES, SUBLANES), SUBLANES)
        # an unselected row's key field gains its delimiter bit, which
        # puts it past every code: it then matches no group
        off = jax.lax.bitcast_convert_type(~m_ref[rows, :], jnp.uint32)
        kx = k_ref[rows, :] | (off & delim)
        words = [v[rows, :] for v in v_refs]
        carry = list(carry)
        for f in range(c):                    # static unroll over fields
            s = jnp.uint32(f * code_bits)
            key = ((kx >> s) & full).astype(jnp.int32)
            vals = [((w >> s) & value).astype(jnp.int32) for w in words]
            for j in range(gb):
                hit = key == gks[j]
                carry[j] += jnp.where(hit, 1, 0)
                for v, x in enumerate(vals):
                    carry[gb + j * n_vals + v] += jnp.where(hit, x, 0)
        return tuple(carry)

    zero = jnp.zeros((SUBLANES, LANES), jnp.int32)
    part = jax.lax.fori_loop(0, block_rows // SUBLANES, body,
                             (zero,) * (gb * (1 + n_vals)))
    # scratch rows: gb counts, then (lo, hi) per (group, value column)
    for j in range(gb):
        acc[j] += part[j]
        for v in range(n_vals):
            s = part[gb + j * n_vals + v]
            at = gb + 2 * (j * n_vals + v)
            lo = acc[at] + (s & 0xFFFF)       # renormalized every step
            acc[at] = lo & 0xFFFF
            acc[at + 1] += (s >> 16) + (lo >> 16)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 1)
        counts = [jnp.sum(acc[j]) for j in range(gb)]
        for p in range(max(n_vals, 1)):
            tile = jnp.zeros(OUT_TILE, jnp.int32)
            for j in range(gb):
                if n_vals:
                    at = gb + 2 * (j * n_vals + p)
                    lo = jnp.sum(acc[at])         # < 1024 * 2^16
                    fields = (lo & 0xFFFF, jnp.sum(acc[at + 1]) + (lo >> 16),
                              counts[j])
                else:
                    fields = (0, 0, counts[j])
                for f, x in enumerate(fields):
                    tile = jnp.where((lane == j) & (row == f), x, tile)
            o_ref[0, p] = tile


@functools.partial(jax.jit, static_argnames=("code_bits", "block_rows",
                                             "interpret"))
def group_sum_count_packed(key2d, mask2d, vals2d, group_keys, *,
                           code_bits: int, block_rows: int,
                           interpret: bool = True):
    """(rows, LANES) uint32 key words, packed delimiter mask in the key's
    layout, and a tuple of k value columns' words at the key's width, +
    (G,) group keys -> int32[max(k, 1), G, 3]: one accumulator plane per
    value column (a count-only plane when k is 0), all group blocks in ONE
    launch. `rows` must be a multiple of `block_rows`, itself a multiple
    of SUBLANES. Group keys outside the code range match nothing."""
    vals2d = tuple(vals2d)
    rows = key2d.shape[0]
    assert rows % block_rows == 0 and block_rows % SUBLANES == 0, \
        (rows, block_rows)
    gk, gb, g = _pad_groups(group_keys, DEFAULT_GROUP_BLOCK)
    gk = jnp.where((gk >= 0) & (gk < (1 << (code_bits - 1))), gk, -1)
    n_gblocks, n_planes = gk.shape[0] // gb, max(len(vals2d), 1)
    spec = pl.BlockSpec((block_rows, LANES), lambda g_, i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_gblocks, rows // block_rows),
        in_specs=[spec] * (2 + len(vals2d)),
        out_specs=pl.BlockSpec((1, n_planes) + OUT_TILE,
                               lambda g_, i, *_: (g_, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM(
            (gb * (1 + 2 * len(vals2d)), SUBLANES, LANES), jnp.int32)],
    )
    tiles = pl.pallas_call(
        functools.partial(_packed_kernel, gb=gb, n_vals=len(vals2d),
                          code_bits=code_bits, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_gblocks, n_planes) + OUT_TILE,
                                       jnp.int32),
        interpret=interpret,
    )(gk, key2d, jax.lax.bitcast_convert_type(mask2d, jnp.int32), *vals2d)
    # (n_gblocks, P, 3, gb) -> (P, n_gblocks * gb, 3)
    planes = jnp.transpose(tiles[:, :, :3, :gb], (1, 0, 3, 2))
    return planes.reshape(n_planes, n_gblocks * gb, 3)[:, :g]
