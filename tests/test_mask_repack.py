"""The mask repack (repro.kernels.mask_repack) against the boolean-row
oracle: its jnp reference and its Pallas kernel (interpret mode) for
every ordered pair of widths, on ragged planes, on planes cut or
zero-extended to the wanted words, and on planes of whole kernel tiles
and below one tile."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from repro.kernels.mask_repack import ops, ref
from repro.kernels.scan_filter.kernel import TILE_WORDS
from repro.kernels.scan_filter.ref import unpack_mask
from repro.obs.metrics import MetricsRegistry, scoped

WIDTHS = (2, 4, 8, 16)
PAIRS = [p for p in itertools.product(WIDTHS, WIDTHS) if p[0] != p[1]]


def oracle(mask, from_bits: int, to_bits: int, to_words: int):
    """Boolean rows of the mask, cut or zero-padded to `to_words` words'
    rows, packed at `to_bits`: the repack by definition (small sizes
    only: it holds a (words, codes per word) array)."""
    sel = np.asarray(unpack_mask(mask, from_bits))
    c = 32 // to_bits
    sel = np.resize(sel, to_words * c) * (np.arange(to_words * c)
                                          < sel.size)
    shifts = (np.arange(c) * to_bits + to_bits - 1).astype(np.uint64)
    return (sel.reshape(-1, c).astype(np.uint64) << shifts).sum(
        1).astype(np.uint32)


def random_mask(rng, n_words: int, code_bits: int):
    c = 32 // code_bits
    bits = rng.integers(0, 2, (n_words, c)).astype(np.uint64)
    shifts = (np.arange(c) * code_bits + code_bits - 1).astype(np.uint64)
    return (bits << shifts).sum(1).astype(np.uint32)


def _dense_words(from_bits, to_bits, dense: int) -> int:
    """Source words whose rows fill `dense` words of the narrower code."""
    return dense * max(1, from_bits // to_bits)


# (source words, target words) from the pair's widths
CASES = {
    "ragged": lambda f, t: (1000, -(-1000 * f // t)),
    "zero_extend": lambda f, t: (1000, -(-1000 * f // t) + 300),
    "truncate": lambda f, t: (1000, 1000 * f // t // 3),
    "below_one_tile": lambda f, t: (_dense_words(f, t, 333),
                                    _dense_words(f, t, 333) * f // t),
    "whole_tiles": lambda f, t: (_dense_words(f, t, 2 * TILE_WORDS),
                                 _dense_words(f, t, 2 * TILE_WORDS)
                                 * f // t),
}


@pytest.mark.parametrize("impl", ("ref", "pallas"))
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("from_bits,to_bits", PAIRS,
                         ids=[f"{a}to{b}" for a, b in PAIRS])
def test_repack_matches_oracle(from_bits, to_bits, case, impl):
    n, to_words = CASES[case](from_bits, to_bits)
    rng = np.random.default_rng((from_bits, to_bits, n))
    mask = random_mask(rng, n, from_bits)
    want = oracle(mask, from_bits, to_bits, to_words)
    reg = MetricsRegistry("repack")
    with scoped(reg):
        if impl == "ref":
            got = ref.repack_ref(mask, from_bits, to_bits, to_words)
        else:
            got = ops.repack_mask(mask, from_bits, to_bits, to_words,
                                  mode="pallas")
    np.testing.assert_array_equal(np.asarray(got), want)
    if impl == "pallas":
        assert reg.counter("mask_repacks").value == 1
        # planes of whole tiles reach the kernel with nothing to pad
        assert (reg.counter("tile_pads").value == 0) == (
            case == "whole_tiles")
