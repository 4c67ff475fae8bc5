"""Pure-jnp oracle for repacking a delimiter-bit mask between code widths.

A mask at code width b has one bit per row, the delimiter (top) bit of
the row's b-bit field: row r is field r % (32 // b) of word r // (32 // b).
Between widths b1 and b2, with k = max(b1, b2) // min(b1, b2):

- narrowing (b1 > b2) gathers the rows of k adjacent source words into
  one target word;
- widening (b1 < b2) spreads the rows of one source word over k adjacent
  target words.

Every intermediate keeps rows of whole 128-word lanes: a plane is viewed
as (rows, 128 * k) or (rows, 128) words, never as (words, codes per word),
whose small minor dimension the TPU pads to 128 lanes.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

LANES = 128


def compact(words, code_bits: int):
    """The delimiter bit of field f of each word, moved to bit f."""
    out = jnp.zeros_like(words)
    for f in range(32 // code_bits):
        out |= ((words >> (f * code_bits + code_bits - 1)) & 1) << f
    return out


def spread(bits, code_bits: int):
    """Inverse of `compact`: bit f of each word to field f's delimiter."""
    out = jnp.zeros_like(bits)
    for f in range(32 // code_bits):
        out |= ((bits >> f) & 1) << (f * code_bits + code_bits - 1)
    return out


def fit(words, n_words: int):
    """`words` cut or zero-extended to `n_words`; returned as is when it
    already has that many."""
    n = words.shape[0]
    if n == n_words:
        return words
    return words[:n_words] if n > n_words else jnp.pad(words,
                                                       (0, n_words - n))


def repack_ref(mask_words, from_bits: int, to_bits: int, to_words: int):
    """(n,) uint32 mask at `from_bits` -> (to_words,) uint32 mask of the
    same rows at `to_bits`. Rows past either plane's end are padding and
    carry zero bits, so cutting or zero-extending to `to_words` is
    exact."""
    words = jnp.asarray(mask_words, jnp.uint32)
    if from_bits == to_bits:
        return fit(words, to_words)
    if from_bits > to_bits:
        k = from_bits // to_bits
        c = 32 // from_bits
        v = compact(fit(words, -(-words.shape[0] // (LANES * k)) * LANES
                        * k).reshape(-1, LANES * k), from_bits)
        out = v[:, ::k]
        for t in range(1, k):
            out |= v[:, t::k] << (t * c)
    else:
        k = to_bits // from_bits
        c = 32 // to_bits
        lane = np.arange(LANES * k)
        v = compact(fit(words, -(-words.shape[0] // LANES) * LANES)
                    .reshape(-1, LANES), from_bits)
        out = (jnp.take(v, lane // k, axis=1)
               >> (lane % k * c).astype(np.uint32)) & ((1 << c) - 1)
    return fit(spread(out, to_bits).reshape(-1), to_words)
