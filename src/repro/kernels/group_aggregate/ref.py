"""Pure-jnp oracle for grouped aggregation over int32 code planes.

The grouped analogue of aggregate/ref.py: per group the sum leaves as two
normalized 16-bit planes (sum_hi << 16 | sum_lo) plus a count, stacked as
an int32 `(n_groups, 3)` accumulator plane — exact for any input the
kernels accept, psum/all-gather safe across shards, reassembled host-side
by `ops.finalize_grouped`.

Exactness staging mirrors aggregate/ref.split_sum: rows are reduced in
<= _STAGE-element segments (each segment partial < 2^27, int32-exact for
any code width), then the staged partials are split 16/16 and summed —
so the oracle stays bit-exact even when one shard holds far more than
2^16 rows of a 16-bit column, matching the kernels' per-tile split.

`group_keys` must be sorted ascending (the dense domain is an arange and
join build keys are sorted before dispatch); the oracle maps codes to
group slots with a searchsorted instead of materializing the
(groups x rows) compare plane the kernel builds tile by tile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.scan_filter.ref import unpack, unpack_mask

_STAGE = 4096        # segment partials stay < 2^27: exact in int32


def _staged_group_sums(idx, vals, sel, n_groups: int):
    """Segment-reduce (values, selected) into per-group (sum_lo, sum_hi,
    count) planes, staging the sums so no int32 partial ever wraps.

    idx:  (n,) int32 group slot per element (n_groups = out-of-domain)
    vals: (n,) int32 non-negative codes < 2^16
    sel:  (n,) bool
    """
    n = idx.shape[0]
    pad = (-n) % _STAGE
    if pad:
        idx = jnp.pad(idx, (0, pad), constant_values=n_groups)
        vals = jnp.pad(vals, (0, pad))
        sel = jnp.pad(sel, (0, pad))
        n += pad
    n_stages = n // _STAGE
    # one flat segment id per (stage, group); the +1 slot absorbs
    # out-of-domain codes and padding
    stage = jnp.repeat(jnp.arange(n_stages, dtype=jnp.int32), _STAGE)
    seg = stage * (n_groups + 1) + idx
    v = jnp.where(sel, vals, 0)
    c = sel.astype(jnp.int32)
    part = jax.ops.segment_sum(v, seg, num_segments=n_stages * (n_groups + 1))
    cnt = jax.ops.segment_sum(c, seg, num_segments=n_stages * (n_groups + 1))
    part = part.reshape(n_stages, n_groups + 1)[:, :n_groups]
    cnt = cnt.reshape(n_stages, n_groups + 1)[:, :n_groups]
    lo = jnp.sum(part & 0xFFFF, axis=0)
    hi = jnp.sum(part >> 16, axis=0)
    return jnp.stack([lo & 0xFFFF, hi + (lo >> 16), jnp.sum(cnt, axis=0)],
                     axis=1)


def _slots(keys, group_keys):
    """Map codes to sorted-group-key slots; non-members -> n_groups."""
    g = group_keys.shape[0]
    idx = jnp.searchsorted(group_keys, keys).astype(jnp.int32)
    hit = group_keys[jnp.clip(idx, 0, g - 1)] == keys
    return jnp.where(hit, idx, g)


def group_sum_count_ref(keys, vals, sel, group_keys):
    """(rows, LANES) int32 key/value/select planes + sorted (G,) group
    keys -> int32 (G, 3) of [sum_lo, sum_hi, count] rows."""
    k = jnp.asarray(keys, jnp.int32).reshape(-1)
    v = jnp.asarray(vals, jnp.int32).reshape(-1)
    s = jnp.asarray(sel, jnp.int32).reshape(-1) > 0
    gk = jnp.asarray(group_keys, jnp.int32)
    return _staged_group_sums(_slots(k, gk), v, s, gk.shape[0])


@jax.jit
def group_sum_count_batched_ref(keys3, vals3, sel3, group_keys):
    """Batched oracle: (n_chunks, rows, LANES) planes -> (n_chunks, G, 3),
    one accumulator plane per chunk, bit-identical to per-chunk calls.
    Jitted: the eager vmap would re-trace its segment_sums every call,
    which dominates any grouped query that dispatches through it."""
    k = jnp.asarray(keys3, jnp.int32)
    v = jnp.asarray(vals3, jnp.int32)
    s = jnp.asarray(sel3, jnp.int32)
    gk = jnp.asarray(group_keys, jnp.int32)
    fn = jax.vmap(lambda kc, vc, sc: _staged_group_sums(
        _slots(kc.reshape(-1), gk), vc.reshape(-1),
        sc.reshape(-1) > 0, gk.shape[0]))
    return fn(k, v, s)


@partial(jax.jit, static_argnames=("code_bits",))
def group_sum_count_packed_ref(key_words, mask_words, value_words,
                               group_keys, *, code_bits: int):
    """Packed oracle: unpack the key, the mask's delimiter bits and each
    value column's payload to rows, then the batched plane oracle, one
    chunk per value column (one count-only chunk when there is none) ->
    int32[max(k, 1), G, 3]."""
    keys = unpack(key_words, code_bits).astype(jnp.int32)
    sel = unpack_mask(mask_words, code_bits).astype(jnp.int32)
    payload = (1 << (code_bits - 1)) - 1
    vals = [unpack(w, code_bits).astype(jnp.int32) & payload
            for w in value_words] or [jnp.zeros_like(keys)]
    n = len(vals)
    return group_sum_count_batched_ref(
        jnp.broadcast_to(keys, (n,) + keys.shape), jnp.stack(vals),
        jnp.broadcast_to(sel, (n,) + sel.shape), group_keys)


def _rle_one(vals, lens, group_keys, pred):
    g = group_keys.shape[0]
    v = jnp.asarray(vals, jnp.int32).reshape(-1)
    l = jnp.asarray(lens, jnp.int32).reshape(-1)
    live = l > 0
    if pred is not None:
        prim, const, invert = pred
        cmp = (v >= const) if prim == "ge" else (v == const)
        live = live & (cmp ^ invert)
    idx = _slots(v, group_keys)
    idx = jnp.where(live, idx, g)
    # run sums: a run of length n contributes n * value; n * v < 2^31
    # per run and per-chunk totals stay < 2^31 (MAX_CHUNK_ROWS * vmax)
    s = jax.ops.segment_sum(l * v, idx, num_segments=g + 1)[:g]
    c = jax.ops.segment_sum(l, idx, num_segments=g + 1)[:g]
    return jnp.stack([s & 0xFFFF, s >> 16, c], axis=1)


def rle_group_accumulate_ref(vals, lens, group_keys, pred=None):
    """RLE run planes -> (G, 3): run (v, n) contributes n to group v's
    count and n*v to its sum (the pre-grouped path's oracle). `pred` is an
    optional canonical (prim, const, invert) triple on the run value."""
    gk = jnp.asarray(group_keys, jnp.int32)
    return _rle_one(vals, lens, gk, pred)


@partial(jax.jit, static_argnames=("pred",))
def rle_group_accumulate_batched_ref(vals3, lens3, group_keys, pred=None):
    """(n_chunks, runs, LANES) run planes -> (n_chunks, G, 3). Jitted
    (pred static: a canonical triple or None) for the same reason as the
    dense batched oracle."""
    v = jnp.asarray(vals3, jnp.int32)
    l = jnp.asarray(lens3, jnp.int32)
    gk = jnp.asarray(group_keys, jnp.int32)
    return jax.vmap(lambda vc, lc: _rle_one(vc, lc, gk, pred))(v, l)
