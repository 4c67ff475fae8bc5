"""Pure-jnp oracle for the BitWeaving-H predicate scan.

Layout: `code_bits`-wide codes packed little-endian into int32 words, one
delimiter (MSB of each field) kept 0 in the data. codes_per_word =
32 // code_bits. A scan produces a packed mask word per data word with the
delimiter bit of each matching field set.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

OPS = ("lt", "le", "gt", "ge", "eq", "ne")


def codes_per_word(code_bits: int) -> int:
    return 32 // code_bits


def field_masks(code_bits: int):
    """(delimiter_mask, low_mask, value_mask) as uint32 scalars."""
    c = codes_per_word(code_bits)
    delim = 0
    low = 0
    for i in range(c):
        delim |= 1 << (i * code_bits + code_bits - 1)
        low |= 1 << (i * code_bits)
    value = (1 << (code_bits - 1)) - 1   # payload bits per field
    return np.uint32(delim), np.uint32(low), np.uint32(value)


def pack(codes, code_bits: int):
    """codes: (N,) ints in [0, 2^(bits-1)) -> packed uint32 words
    (N padded to a multiple of codes_per_word). Works field by field over
    strided views, so the only temporaries are word-sized."""
    codes = np.asarray(codes)
    c = codes_per_word(code_bits)
    out = np.zeros(-(-len(codes) // c), np.uint32)
    for i in range(c):
        f = codes[i::c].astype(np.uint32)
        f <<= np.uint32(i * code_bits)
        out[:len(f)] |= f
    return out


def unpack(words, code_bits: int):
    words = jnp.asarray(words, jnp.uint32)
    c = codes_per_word(code_bits)
    shifts = jnp.arange(c, dtype=jnp.uint32) * code_bits
    vals = (words[:, None] >> shifts[None, :]) & jnp.uint32(
        (1 << code_bits) - 1)
    return vals.reshape(-1)


def code_range(words, code_bits: int, n_rows: int) -> tuple[int, int]:
    """(min, max) of the first `n_rows` codes of packed host words, field
    by field (no per-row temporaries); (0, -1) when there are none."""
    c = codes_per_word(code_bits)
    words = np.asarray(words, np.uint32)
    lo, hi = None, None
    for i in range(min(c, max(n_rows, 0))):
        f = (words[:-(-(n_rows - i) // c)] >> np.uint32(i * code_bits)) \
            & np.uint32((1 << code_bits) - 1)
        lo = int(f.min()) if lo is None else min(lo, int(f.min()))
        hi = int(f.max()) if hi is None else max(hi, int(f.max()))
    return (0, -1) if lo is None else (lo, hi)


def valid_mask(n_words: int, n_rows: int, code_bits: int):
    """Packed delimiter-bit mask over `n_words` words with a bit set for
    exactly the first `n_rows` codes: the validity plane that cancels
    tail-of-word and shard padding. Built word-wise (no per-row
    temporaries), so it stays cheap at billions of rows."""
    c = codes_per_word(code_bits)
    n_rows = max(n_rows, 0)
    out = np.zeros(n_words, np.uint32)
    full = min(n_rows // c, n_words)
    out[:full] = field_masks(code_bits)[0]
    if full < n_words:
        for i in range(n_rows - full * c):
            out[full] |= np.uint32(1 << (i * code_bits + code_bits - 1))
    return out


def unpack_mask(mask_words, code_bits: int):
    """Packed delimiter-bit mask -> boolean per code."""
    c = codes_per_word(code_bits)
    words = jnp.asarray(mask_words, jnp.uint32)
    shifts = (jnp.arange(c, dtype=jnp.uint32) * code_bits + code_bits - 1)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1).astype(bool)


def scan_ref(words, constant: int, op: str, code_bits: int):
    """Oracle: unpack -> compare -> repack delimiter-bit mask."""
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of "
                         f"{OPS}")
    vals = unpack(words, code_bits)
    fn = {"lt": jnp.less, "le": jnp.less_equal, "gt": jnp.greater,
          "ge": jnp.greater_equal, "eq": jnp.equal,
          "ne": jnp.not_equal}[op]
    hits = fn(vals, jnp.uint32(constant))
    c = codes_per_word(code_bits)
    hits = hits.reshape(-1, c)
    shifts = (jnp.arange(c, dtype=jnp.uint32) * code_bits + code_bits - 1)
    return jnp.bitwise_or.reduce(
        jnp.where(hits, jnp.uint32(1) << shifts[None, :], jnp.uint32(0)),
        axis=1)
