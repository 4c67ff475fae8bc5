"""Public grouped-aggregation API, dispatched through
repro.kernels.dispatch.

`group_sum_count[_batched]` is the dense-accumulator-plane strategy:
SELECT key, count(*), sum(val) GROUP BY key over int32 code planes, with
the group domain handed in explicitly (an arange when a FOR frame bounds
the key range, the sorted distinct build keys for a hash join).
`group_sum_count_packed` is the same strategy over packed words, for a
key and value columns that share one code width: no plane is unpacked,
and one launch reduces every value column.
`rle_group_accumulate[_batched]` is the fused pre-grouped strategy over
RLE run planes — a run of length n contributes n to one group's count and
n*value to its sum in registers, no scatter. The sort/hash fallback for
plain high-cardinality chunks lives host-side in repro.query.relational
(it is a numpy path, not a kernel).

All paths return int32 `(G, 3)` (or batched `(n_chunks, G, 3)`) planes of
normalized [sum_lo, sum_hi, count] rows; `finalize_grouped` reassembles
exact host ints including the FOR base fix-up sum += base * count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.group_aggregate import kernel as K
from repro.kernels.group_aggregate import ref
from repro.kernels.scan_filter.kernel import LANES
from repro.obs import metrics as obs_metrics

# dense strategy cutoff: above this many groups the accumulator plane
# (and its (group_block, block_rows, LANES) compare tiles) stops paying
# for itself and chunks fall back to the host sort/hash path
DENSE_MAX_GROUPS = 1024

# a (block_rows, LANES) tile of 16-bit codes must sum < 2^31 so the
# per-tile partial is exact before the 16/16 split (cf. aggregate/ops.py)
_MAX_BLOCK_ROWS = (2**31 - 1) // (LANES * ((1 << 15) - 1))


def _params(rows: int, groups: int, block_rows: int | None,
            group_block: int | None):
    br = min(K.DEFAULT_BLOCK_ROWS, rows) if block_rows is None \
        else block_rows
    gb = min(K.DEFAULT_GROUP_BLOCK, groups) if group_block is None \
        else group_block
    br = max(1, min(int(br), rows, _MAX_BLOCK_ROWS))
    gb = max(1, min(int(gb), groups))
    return br, gb


def _to_plane(x):
    x = jnp.asarray(x, jnp.int32).reshape(-1)
    return jnp.pad(x, (0, (-x.shape[0]) % LANES)).reshape(-1, LANES)


def lift_chunks(chunks):
    """Ragged per-chunk 1-D arrays -> one (n_chunks, rows, LANES) stack.

    Host inputs pad/stack in numpy and cross to the device once —
    O(n_chunks) un-jitted jnp dispatches would otherwise dominate every
    encoded grouped query. Traced inputs (the sharded per-shard closure)
    keep the jnp path."""
    if not any(isinstance(c, jax.core.Tracer) for c in chunks):
        arrs = [np.asarray(c, np.int32).reshape(-1) for c in chunks]
        rows = max(max((-(-a.size // LANES) for a in arrs), default=0), 1)
        out = np.zeros((len(arrs), rows * LANES), np.int32)
        for i, a in enumerate(arrs):
            out[i, : a.size] = a
        return jnp.asarray(out.reshape(len(arrs), rows, LANES))
    planes = [_to_plane(c) for c in chunks]
    rows = max(max((p.shape[0] for p in planes), default=0), 1)
    return jnp.stack([jnp.pad(p, ((0, rows - p.shape[0]), (0, 0)))
                      for p in planes])


def group_sum_count_batched(keys3, vals3, sel3, group_keys, *, mode=None,
                            block_rows: int | None = None,
                            group_block: int | None = None):
    """Dense grouped aggregate, all chunks in ONE launch.

    keys3/vals3/sel3: (n_chunks, rows, LANES) int32 code planes (padded
    rows carry sel=0); group_keys: sorted (G,) int32. Returns
    int32[n_chunks, G, 3] of normalized [sum_lo, sum_hi, count] rows.
    """
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("group_aggregate")
    keys3 = jnp.asarray(keys3, jnp.int32)
    gk = jnp.asarray(group_keys, jnp.int32)
    n_chunks, rows = keys3.shape[0], keys3.shape[1]
    g = gk.shape[0]
    if n_chunks == 0 or rows == 0 or g == 0:
        return jnp.zeros((n_chunks, g, 3), jnp.int32)
    if not r.use_pallas:
        return ref.group_sum_count_batched_ref(keys3, vals3, sel3, gk)
    br, gb = _params(rows, g, block_rows, group_block)
    return K.group_sum_count_batched_planes(
        keys3, jnp.asarray(vals3, jnp.int32), jnp.asarray(sel3, jnp.int32),
        gk, block_rows=br, group_block=gb, interpret=r.interpret)


def group_sum_count(keys, vals, sel, group_keys, *, mode=None,
                    block_rows: int | None = None,
                    group_block: int | None = None):
    """One-chunk dense grouped aggregate over 1-D int32 code arrays ->
    int32[G, 3]; thin wrapper over the batched launch."""
    out = group_sum_count_batched(
        lift_chunks([keys]), lift_chunks([vals]), lift_chunks([sel]),
        group_keys, mode=mode,
        block_rows=block_rows, group_block=group_block)
    return out[0]


# largest tile of the packed kernel, and the VMEM its double-buffered
# key, mask and value blocks may take (a v5e kernel has about 16 MiB)
PACKED_BLOCK_ROWS = 1024
PACKED_VMEM_BYTES = 12 << 20


def packed_block_rows(rows: int, code_bits: int, n_planes: int) -> int:
    """Rows of words per packed-kernel step over `n_planes` (rows, LANES)
    planes: the largest power of two up to PACKED_BLOCK_ROWS that keeps
    every per-lane partial of a step int32-exact (block_rows / SUBLANES
    rows of codes per word at the width's maximum) and the blocks within
    PACKED_VMEM_BYTES; halved further, down to the default block, until
    it divides `rows`, so a shard at whole tiles needs no pad."""
    cpw, vmax = 32 // code_bits, (1 << (code_bits - 1)) - 1
    cap = min(K.SUBLANES * ((2**31 - 1) // (cpw * vmax)),
              PACKED_VMEM_BYTES // (2 * n_planes * LANES * 4))
    br = PACKED_BLOCK_ROWS
    while br > max(cap, K.SUBLANES):
        br //= 2
    while br > K.DEFAULT_BLOCK_ROWS and rows % br:
        br //= 2
    return min(br, -(-rows // K.SUBLANES) * K.SUBLANES)


def group_sum_count_packed(key_words, mask_words, value_words, group_keys,
                           *, code_bits: int, mode=None):
    """Dense grouped aggregate over packed words, every value column in
    ONE launch.

    key_words: (n_words,) uint32 packed key codes; mask_words: the packed
    selection mask in the key's layout (delimiter bit set per selected
    row, so rows past the table must carry none); value_words: a sequence
    of k (n_words,) packed columns at the key's code width; group_keys:
    sorted (G,) int32. Returns int32[max(k, 1), G, 3] of normalized
    [sum_lo, sum_hi, count] rows, one plane per value column (sums 0 in
    the count-only plane when k is 0).
    """
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("group_aggregate_packed")
    value_words = tuple(jnp.asarray(w, jnp.uint32) for w in value_words)
    key_words = jnp.asarray(key_words, jnp.uint32)
    mask_words = jnp.asarray(mask_words, jnp.uint32)
    gk = jnp.asarray(group_keys, jnp.int32)
    n_planes, g = max(len(value_words), 1), gk.shape[0]
    if key_words.shape[0] == 0 or g == 0:
        return jnp.zeros((n_planes, g, 3), jnp.int32)
    if not r.use_pallas:
        return ref.group_sum_count_packed_ref(
            key_words, mask_words, value_words, gk, code_bits=code_bits)
    rows = -(-key_words.shape[0] // LANES)
    br = packed_block_rows(rows, code_bits, 2 + len(value_words))
    n = -(-rows // br) * br * LANES
    if n != key_words.shape[0]:
        # zero words carry no mask bit: padded rows select nothing
        obs_metrics.count("tile_pads")
    planes = [jnp.pad(w, (0, n - w.shape[0])).reshape(-1, LANES)
              for w in (key_words, mask_words) + value_words]
    return K.group_sum_count_packed(
        planes[0], planes[1], tuple(planes[2:]), gk, code_bits=code_bits,
        block_rows=br, interpret=r.interpret)


def rle_group_accumulate_batched(run_planes, group_keys, *, pred=None,
                                 mode=None, block_rows: int | None = None,
                                 group_block: int | None = None):
    """Fused pre-grouped accumulation over RLE runs, all chunks in ONE
    launch: run (v, n) adds n to group v's count and n*v to its sum —
    register accumulation only, no scatter.

    run_planes: sequence of (values, lengths) run-plane pairs, one per
    chunk (ragged run counts padded with zero-length runs, which are
    inert). `pred` is an optional canonical (prim, const, invert) triple
    evaluated on the run value in-kernel. Returns int32[n_chunks, G, 3].
    """
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("group_aggregate_rle")
    gk = jnp.asarray(group_keys, jnp.int32)
    n_chunks, g = len(run_planes), gk.shape[0]
    if n_chunks == 0 or g == 0:
        return jnp.zeros((n_chunks, g, 3), jnp.int32)
    if pred is not None:
        pred = (str(pred[0]), int(pred[1]), bool(pred[2]))
    v3 = lift_chunks([v for v, _ in run_planes])
    l3 = lift_chunks([l for _, l in run_planes])
    if not r.use_pallas:
        return ref.rle_group_accumulate_batched_ref(v3, l3, gk, pred)
    br, gb = _params(v3.shape[1], g, block_rows, group_block)
    return K.rle_group_accumulate_batched_planes(
        v3, l3, gk, pred=pred, block_rows=br, group_block=gb,
        interpret=r.interpret)


def rle_group_accumulate(values, lengths, group_keys, *, pred=None,
                         mode=None, block_rows: int | None = None,
                         group_block: int | None = None):
    """One chunk of RLE runs -> int32[G, 3]."""
    out = rle_group_accumulate_batched([(values, lengths)], group_keys,
                                       pred=pred, mode=mode,
                                       block_rows=block_rows,
                                       group_block=group_block)
    return out[0]


def finalize_grouped(group_keys, plane, base: int = 0):
    """One (G, 3) accumulator plane -> exact host int64 (keys, sums,
    counts) with the FOR base fix-up: the kernel summed deltas, so the
    logical sum is delta_sum + base * count, exact in Python/host ints."""
    p = np.asarray(plane, np.int64)
    keys = np.asarray(group_keys, np.int64)
    counts = p[:, 2]
    sums = (p[:, 1] << 16) + p[:, 0] + int(base) * counts
    return keys, sums, counts


def _batched_ref(keys3, vals3, sel3, group_keys, *,
                 block_rows=None, group_block=None):
    return ref.group_sum_count_batched_ref(keys3, vals3, sel3, group_keys)


def _example(rng):
    n_chunks, rows = 3, 1000            # non-pow2: exercises lane padding
    keys = rng.integers(0, 7, (n_chunks, rows))
    vals = rng.integers(0, 128, (n_chunks, rows))
    sel = rng.integers(0, 2, (n_chunks, rows))
    gk = jnp.arange(7, dtype=jnp.int32)
    return ((lift_chunks(list(keys)), lift_chunks(list(vals)),
             lift_chunks(list(sel)), gk),
            {})


def _packed_example(rng):
    from repro.kernels.scan_filter.ref import pack
    rows, bits = 5000, 8                 # ragged: a part word, padding
    keys = rng.integers(0, 7, rows)
    vals = [rng.integers(0, 128, rows) for _ in range(3)]
    sel = rng.integers(0, 2, rows) << (bits - 1)
    return ((jnp.asarray(pack(keys, bits)), jnp.asarray(pack(sel, bits)),
             tuple(jnp.asarray(pack(v, bits)) for v in vals),
             jnp.arange(7, dtype=jnp.int32)),
            {"code_bits": bits})


dispatch.register(
    "group_aggregate", fn=group_sum_count_batched, ref=_batched_ref,
    example=_example)

dispatch.register(
    "group_aggregate_packed", fn=group_sum_count_packed,
    ref=ref.group_sum_count_packed_ref, example=_packed_example)
