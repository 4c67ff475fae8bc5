"""The packed grouped kernel (group_aggregate.group_sum_count_packed)
against numpy over the decoded rows: its Pallas kernel (interpret mode)
and its jnp reference, at every code width, with none, one and three
value columns, over dense, padded and gapped group domains, a ragged last
word, an empty mask and values at each width's maximum."""
from __future__ import annotations

import numpy as np
import pytest

from repro.kernels.group_aggregate import kernel as K
from repro.kernels.group_aggregate import ops
from repro.kernels.scan_filter.ref import pack, valid_mask

WIDTHS = (2, 4, 8, 16)
IMPLS = ("pallas", "xla_ref")


def oracle(keys, sel, vals, domain):
    """int64 [max(k, 1), G, 3] normalized planes by definition."""
    out = np.zeros((max(len(vals), 1), len(domain), 3), np.int64)
    for p, v in enumerate(vals or [np.zeros_like(keys)]):
        for j, g in enumerate(domain):
            m = sel & (keys == g)
            s = int(v[m].sum())
            out[p, j] = (s & 0xFFFF, s >> 16, int(m.sum()))
    return out


def run(keys, sel, vals, domain, bits, impl, mask=None):
    if mask is None:
        mask = pack(sel.astype(np.int64) << (bits - 1), bits)
    got = ops.group_sum_count_packed(
        pack(keys, bits), mask, [pack(v, bits) for v in vals],
        np.asarray(domain, np.int32), code_bits=bits, mode=impl)
    return np.asarray(got)


def table(rng, rows, bits, k, n_keys):
    vmax = (1 << (bits - 1)) - 1
    keys = rng.integers(0, min(n_keys, vmax + 1), rows)
    vals = [rng.integers(0, vmax + 1, rows) for _ in range(k)]
    return keys, rng.integers(0, 2, rows).astype(bool), vals


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("k", (0, 1, 3))
@pytest.mark.parametrize("bits", WIDTHS)
def test_packed_matches_numpy(bits, k, impl):
    rng = np.random.default_rng(bits * 10 + k)
    keys, sel, vals = table(rng, 3001, bits, k, 13)
    domain = np.arange(min(13, 1 << (bits - 1)))
    got = run(keys, sel, vals, domain, bits, impl)
    assert got.shape == (max(k, 1), len(domain), 3)
    np.testing.assert_array_equal(got, oracle(keys, sel, vals, domain))


DOMAINS = {
    "one": [3],
    "four": [0, 1, 2, 3],
    "padded_block": list(range(13)),              # 8 + 5 of a second block
    "gapped_join": [1, 5, 6, 40, 99, 127],
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("domain", sorted(DOMAINS))
def test_packed_group_domains(domain, impl):
    rng = np.random.default_rng(7)
    keys, sel, vals = table(rng, 4099, 8, 3, 128)
    want = oracle(keys, sel, vals, DOMAINS[domain])
    np.testing.assert_array_equal(
        run(keys, sel, vals, DOMAINS[domain], 8, impl), want)
    if domain == "gapped_join":        # keys outside the domain drop out
        assert want[0, :, 2].sum() < sel.sum()


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("bits", WIDTHS)
def test_ragged_last_word_cancelled_by_validity(bits, impl):
    """The part of the last word past the table packs zero codes, which
    key 0 would match: the validity-masked mask drops them."""
    rng = np.random.default_rng(bits)
    cpw = 32 // bits
    rows = 40 * cpw + cpw // 2 + 1
    keys, _, vals = table(rng, rows, bits, 1, 3)
    n_words = -(-rows // cpw)
    mask = valid_mask(n_words, rows, bits)
    got = run(keys, None, vals, [0, 1, 2], bits, impl, mask=mask)
    np.testing.assert_array_equal(
        got, oracle(keys, np.ones(rows, bool), vals, [0, 1, 2]))


@pytest.mark.parametrize("impl", IMPLS)
def test_all_zero_mask_selects_nothing(impl):
    rng = np.random.default_rng(5)
    keys, _, vals = table(rng, 2048, 8, 3, 4)
    got = run(keys, np.zeros(2048, bool), vals, [0, 1, 2, 3], 8, impl)
    assert got.shape == (3, 4, 3) and not got.any()


@pytest.mark.parametrize("bits", WIDTHS)
def test_values_at_the_width_maximum_stay_exact(bits):
    """Every row selected, one group, every value at the width's maximum,
    over several kernel steps: the sums pass 2^16 (2^31 at 16 bits) and
    stay exact, and the chosen block keeps each lane's partial of a step
    int32-exact however large the plane."""
    cpw, vmax = 32 // bits, (1 << (bits - 1)) - 1
    for plane_rows in (256, 1 << 20, 3 * 256):
        br = ops.packed_block_rows(plane_rows, bits, 5)
        assert br // K.SUBLANES * cpw * vmax < 2**31
    rows = 3 * 256 * 128 * cpw          # three steps of 256 word rows
    keys = np.zeros(rows, np.int64)
    vals = [np.full(rows, vmax)]
    got = run(keys, np.ones(rows, bool), vals, [0], bits, "pallas")
    total = rows * vmax
    assert (bits < 16) or total >= 2**31
    np.testing.assert_array_equal(
        got, [[[total & 0xFFFF, total >> 16, rows]]])
