"""Public fused scan+aggregate API, dispatched through
repro.kernels.dispatch.

Aggregates carry the sum as two normalized 16-bit planes (sum_hi, sum_lo)
— exact in int32 where a single int32 sum wraps after ~65k selected rows
of a 16-bit column, and safe to psum across shards. `finalize` reassembles
the exact Python int host-side; `sum_bound_block_rows` bounds the tile so
per-tile partials stay exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.aggregate import kernel as K
from repro.kernels.aggregate import ref
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES
from repro.obs import metrics as obs_metrics


def sum_bound_block_rows(code_bits: int) -> int:
    """Largest block_rows whose per-tile sum partial is int32-exact:
    block_rows * LANES words * codes/word * vmax < 2^31."""
    cpw = 32 // code_bits
    vmax = (1 << (code_bits - 1)) - 1
    return max(1, (2**31 - 1) // (LANES * cpw * vmax))


def fetch(tree):
    """One blocking device-to-host read of a pytree of device arrays,
    counted once: every copy starts before the first is awaited."""
    obs_metrics.count("d2h_fetches")
    return jax.device_get(tree)


def finalize(d: dict) -> dict:
    """Aggregate dict -> exact host ints, planes reassembled (the only
    step that may exceed int32, hence Python ints). Device values are
    read one by one: `fetch` them first on a served path."""
    return {"sum": (int(d["sum_hi"]) << 16) + int(d["sum_lo"]),
            "count": int(d["count"]), "min": int(d["min"]),
            "max": int(d["max"])}


def aggregate(words, mask_words, code_bits: int,
              block_rows: int | None = None, mode=None):
    """words/mask_words: (n_words,) uint32 ->
    dict(sum_lo, sum_hi, count, min, max) of int32 scalars.

    Codes in padded tail words have mask delimiter bits 0 and are ignored.
    """
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("aggregate")
    if not r.use_pallas:
        return ref.aggregate_ref(words, mask_words, code_bits)
    if words.size == 0:              # zero-row grid is undefined
        return ref.identity(code_bits)
    w = jnp.asarray(words, jnp.uint32)
    m = jnp.asarray(mask_words, jnp.uint32)
    pad = (-w.shape[0]) % LANES
    if pad:
        obs_metrics.count("tile_pads")
        w, m = jnp.pad(w, (0, pad)), jnp.pad(m, (0, pad))
    w, m = w.reshape(-1, LANES), m.reshape(-1, LANES)
    rows = w.shape[0]
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    br = min(br, sum_bound_block_rows(code_bits))
    out = K.aggregate_packed(w, m, code_bits=code_bits, block_rows=br,
                             interpret=r.interpret)
    return {"sum_lo": out[0, 0], "sum_hi": out[0, 1], "count": out[0, 2],
            "min": out[0, 3], "max": out[0, 4]}


def to3d_words(words3, lanes: int = LANES):
    """(n_chunks, n_words) packed planes -> (n_chunks, rows, lanes) kernel
    tiles (lane-padded with zero words, which no mask ever selects)."""
    w = jnp.asarray(words3, jnp.uint32)
    n_chunks, n_words = w.shape
    pad = (-n_words) % lanes
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return w.reshape(n_chunks, -1, lanes)


def aggregate_batched(words3, mask3, code_bits: int,
                      block_rows: int | None = None, mode=None):
    """All chunks of one column in ONE launch: (n_chunks, n_words) packed
    words + packed masks -> int32[n_chunks, 5], each row bit-identical to
    the per-chunk `aggregate` at that chunk's words/mask."""
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("aggregate")
    w = jnp.asarray(words3, jnp.uint32)
    if w.shape[0] == 0 or w.shape[1] == 0:   # empty-selection identities
        vmax = (1 << (code_bits - 1)) - 1
        return jnp.tile(jnp.asarray([[0, 0, 0, vmax, 0]], jnp.int32),
                        (w.shape[0], 1))
    if not r.use_pallas:
        return _batched_ref_jit(jnp.asarray(words3, jnp.uint32),
                                jnp.asarray(mask3, jnp.uint32), code_bits)
    w3 = to3d_words(words3)
    m3 = to3d_words(mask3)
    rows = w3.shape[1]
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    br = min(br, sum_bound_block_rows(code_bits))
    return K.aggregate_batched_packed(w3, m3, code_bits=code_bits,
                                      block_rows=br, interpret=r.interpret)


# the ref oracle compiled once per plane shape: word planes and masks are
# traced, so a warm trace replay of any query mix never retraces
_batched_ref_jit = jax.jit(ref.aggregate_batched_ref, static_argnums=2)


def _example(rng):
    from repro.kernels.scan_filter import ref as scan_ref
    codes = rng.integers(0, 128, 6000)
    packed = scan_ref.pack(codes, 8)
    mask = scan_ref.scan_ref(packed, 64, "lt", 8)
    return (jnp.asarray(packed), mask, 8), {}


dispatch.register(
    "aggregate", fn=aggregate, ref=ref.aggregate_ref, example=_example)
