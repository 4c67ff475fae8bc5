"""Pure-jnp oracle for the fused scan+aggregate: each leaf scanned to a
mask, the masks ANDed with validity, every aggregate column reduced."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.aggregate.ref import aggregate_ref
from repro.kernels.scan_filter.ref import scan_ref


def scan_conjunction_ref(words: dict, valid_words, preds, aggregates,
                         code_bits: int) -> dict:
    """{agg column: aggregate dict} over the rows where every
    (column, op, constant) of `preds` holds. valid_words is a packed
    delimiter-bit mask with bits set only for real (non-padding) rows,
    so tail/shard padding never matches."""
    mask = jnp.asarray(valid_words, jnp.uint32)
    for col, op, constant in preds:
        mask = mask & scan_ref(words[col], constant, op, code_bits)
    return {a: aggregate_ref(words[a], mask, code_bits) for a in aggregates}
