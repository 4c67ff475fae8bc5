"""Public mask-repack API, dispatched through repro.kernels.dispatch.

`repack_mask` is how a predicate mask formed at one column's code width
reaches an operator at another's. Each repack between two widths counts
one `mask_repack` launch and one `mask_repacks` (per trace, as launches
are); a mask already at the wanted width is only cut or zero-extended to
the wanted number of words, and counts neither.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.mask_repack import kernel as K
from repro.kernels.mask_repack import ref
from repro.kernels.scan_filter.kernel import LANES
from repro.kernels.scan_filter.ref import pack
from repro.obs import metrics as obs_metrics


def repack_mask(mask_words, from_bits: int, to_bits: int, to_words: int,
                mode=None):
    """(n,) uint32 delimiter-bit mask at `from_bits` -> (to_words,) uint32
    mask of the same rows at `to_bits`, for widths in {2, 4, 8, 16}."""
    words = jnp.asarray(mask_words, jnp.uint32)
    if from_bits == to_bits:
        return ref.fit(words, to_words)
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("mask_repack")
    obs_metrics.count("mask_repacks")
    if not r.use_pallas:
        return ref.repack_ref(words, from_bits, to_bits, to_words)
    if words.shape[0] == 0:           # zero-row grid is undefined
        return jnp.zeros((to_words,), jnp.uint32)
    k = max(from_bits, to_bits) // min(from_bits, to_bits)
    narrow = from_bits > to_bits
    # rows of the dense side (the narrower code's), in whole blocks
    dense_rows = -(-words.shape[0] // (LANES * (k if narrow else 1)))
    block_rows = min(K.DEFAULT_BLOCK_ROWS, dense_rows)
    dense_rows = -(-dense_rows // block_rows) * block_rows
    n = dense_rows * LANES * (k if narrow else 1)
    if n != words.shape[0]:
        obs_metrics.count("tile_pads")
        words = ref.fit(words, n)
    out = K.repack_mask_packed(words.reshape(-1, LANES),
                               from_bits=from_bits, to_bits=to_bits,
                               block_rows=block_rows,
                               interpret=r.interpret)
    return ref.fit(out.reshape(-1), to_words)


def _example(rng):
    rows = 3000
    mask = jnp.asarray(pack(rng.integers(0, 2, rows) << 15, 16))
    return (mask, 16, 8, -(-rows // 4)), {}


dispatch.register("mask_repack", fn=repack_mask, ref=ref.repack_ref,
                  example=_example)
