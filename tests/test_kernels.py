"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles.

Every kernel is swept over shapes/dtypes and assert_allclose'd against its
ref.py (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.aggregate import ref as agg_ref
from repro.kernels.decode_attention import ops as dec_ops
from repro.kernels.decode_attention import ref as dec_ref
from repro.kernels.flash_attention import kernel as flash_kernel
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels.scan_filter import ops as scan_ops
from repro.kernels.scan_filter import ref as scan_ref

RNG = np.random.default_rng(42)


# --------------------------------------------------------------------------
# scan_filter
# --------------------------------------------------------------------------
@pytest.mark.parametrize("code_bits", [4, 8, 16])
@pytest.mark.parametrize("op", scan_ref.OPS)
def test_scan_filter_matches_ref(code_bits, op):
    vmax = (1 << (code_bits - 1)) - 1
    codes = RNG.integers(0, vmax + 1, 4096)
    packed = scan_ref.pack(codes, code_bits)
    for const in (0, 1, vmax // 3, vmax - 1, vmax):
        got = scan_ops.scan_filter(packed, const, op, code_bits)
        want = scan_ref.scan_ref(packed, const, op, code_bits)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{code_bits}b {op} c={const}")


@pytest.mark.parametrize("n", [128, 129, 1000, 8192])
def test_scan_filter_ragged_lengths(n):
    code_bits = 8
    codes = RNG.integers(0, 128, n)
    packed = scan_ref.pack(codes, code_bits)
    got = scan_ops.scan_filter(packed, 64, "lt", code_bits)
    want = scan_ref.scan_ref(packed, 64, "lt", code_bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scan_filter_semantics_vs_plain_numpy():
    code_bits = 8
    codes = RNG.integers(0, 128, 2048)
    packed = scan_ref.pack(codes, code_bits)
    mask = scan_ops.scan_filter(packed, 40, "lt", code_bits)
    sel = np.asarray(scan_ref.unpack_mask(mask, code_bits))[:len(codes)]
    np.testing.assert_array_equal(sel, codes < 40)


@pytest.mark.parametrize("code_bits", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [0, 1, 7, 129, 1000])
def test_pack_and_validity_planes_vs_plain_numpy(code_bits, n):
    """Host packing helpers against per-row definitions: pack round-trips
    through unpack, valid_mask sets exactly the first n delimiter bits
    (also over extra padding words), code_range is the codes' (min, max)."""
    codes = RNG.integers(0, 1 << (code_bits - 1), n)
    words = scan_ref.pack(codes, code_bits)
    cpw = 32 // code_bits
    assert words.size == -(-n // cpw)
    np.testing.assert_array_equal(
        np.asarray(scan_ref.unpack(words, code_bits))[:n], codes)
    for n_words in (words.size, words.size + 3):
        valid = scan_ref.valid_mask(n_words, n, code_bits)
        sel = np.asarray(scan_ref.unpack_mask(valid, code_bits))
        np.testing.assert_array_equal(sel, np.arange(n_words * cpw) < n)
    want = (int(codes.min()), int(codes.max())) if n else (0, -1)
    assert scan_ref.code_range(words, code_bits, n) == want


# --------------------------------------------------------------------------
# aggregate
# --------------------------------------------------------------------------
@pytest.mark.parametrize("code_bits", [4, 8, 16])
@pytest.mark.parametrize("selectivity", [0.0, 0.3, 1.0])
def test_aggregate_matches_ref(code_bits, selectivity):
    vmax = (1 << (code_bits - 1)) - 1
    codes = RNG.integers(0, vmax + 1, 6000)
    packed = scan_ref.pack(codes, code_bits)
    const = int(vmax * selectivity)
    mask = scan_ref.scan_ref(packed, const, "lt", code_bits)
    got = agg_ops.aggregate(packed, mask, code_bits)
    want = agg_ref.aggregate_ref(packed, mask, code_bits)
    for key in ("sum_lo", "sum_hi", "count", "min", "max"):
        assert int(got[key]) == int(want[key]), (key, code_bits, selectivity)
    # cross-check against plain numpy on the unpacked values
    sel = codes < const
    fin = agg_ops.finalize(got)
    assert fin["count"] == int(sel.sum())
    assert fin["sum"] == int(codes[sel].sum())


def test_aggregate_sum_exact_beyond_int32():
    """300k selected rows of a 16-bit column sum past 2^31; the 16-bit
    sum planes must stay exact where a single int32 accumulator wraps."""
    n = 300_000
    codes = RNG.integers(0, 1 << 15, n)
    packed = scan_ref.pack(codes, 16)
    mask = scan_ref.scan_ref(packed, 0, "ge", 16)    # select everything
    want = int(codes.astype(np.int64).sum())
    assert want > 2**31                              # the case that wrapped
    for mode in ("pallas", "xla_ref"):
        fin = agg_ops.finalize(agg_ops.aggregate(packed, mask, 16,
                                                 mode=mode))
        assert fin["sum"] == want, mode
        assert fin["count"] == n


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kvh,g,sq,skv,d", [
    (1, 1, 1, 128, 128, 128),
    (2, 2, 4, 128, 256, 128),     # GQA group 4, rectangular
    (1, 2, 1, 256, 256, 64),
    (2, 1, 2, 384, 384, 128),
])
def test_flash_matches_ref(dtype, b, kvh, g, sq, skv, d):
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, kvh, g, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, kvh, skv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv_, (b, kvh, skv, d), jnp.float32).astype(dtype)
    got = flash_kernel.flash_attention_fwd(q, k, v, interpret=True)
    want = flash_ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [32, 128, 1024])
def test_flash_sliding_window(window):
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 2, 2, 256, 64), jnp.float32)
    k = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    got = flash_kernel.flash_attention_fwd(q, k, v, window=window,
                                           interpret=True)
    want = flash_ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fit_clamps_to_divisor():
    assert flash_ops.fit(1024, 4096) == 1024
    assert flash_ops.fit(96, 64) == 48
    assert flash_ops.fit(7, 4) == 1


def test_flash_block_shape_independence():
    """Different BlockSpec tilings must give the same answer."""
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, 1, 2, 256, 128), jnp.float32)
    k = jax.random.normal(key, (1, 1, 256, 128), jnp.float32)
    v = jax.random.normal(key, (1, 1, 256, 128), jnp.float32)
    a = flash_kernel.flash_attention_fwd(q, k, v, bq=128, bk=128,
                                         interpret=True)
    b = flash_kernel.flash_attention_fwd(q, k, v, bq=64, bk=256,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients_flow():
    """custom_vjp: kernel forward + reference backward."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 1, 1, 128, 64), jnp.float32)
    k = jax.random.normal(key, (1, 1, 128, 64), jnp.float32)
    v = jax.random.normal(key, (1, 1, 128, 64), jnp.float32)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_ops.flash5(q, k, v, 0) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_ref.attention_ref(q, k, v) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kvh,g,s,d", [
    (2, 2, 2, 512, 128),
    (1, 1, 8, 1024, 64),
    (4, 2, 1, 2048, 128),
])
def test_decode_matches_ref(dtype, b, kvh, g, s, d):
    key = jax.random.PRNGKey(0)
    kq, kk, kv_, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, kvh, g, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, kvh, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv_, (b, kvh, s, d), jnp.float32).astype(dtype)
    fill = int(0.75 * s)
    kv_pos = jnp.where(jnp.arange(s)[None, :] < fill,
                       jnp.arange(s)[None, :], 1 << 30)
    kv_pos = jnp.broadcast_to(kv_pos, (b, s))
    q_pos = jnp.full((b,), fill, jnp.int32)
    got = dec_ops.decode_attention(q, k, v, q_pos, kv_pos)
    want = dec_ref.decode_ref(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [64, 512])
def test_decode_sliding_window_ring(window):
    """Ring-buffer semantics: positions wrap, window masks stale slots."""
    b, kvh, g, s, d = 1, 1, 2, 256, 64
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (b, kvh, g, d), jnp.float32)
    k = jax.random.normal(key, (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(key, (b, kvh, s, d), jnp.float32)
    # cache holds positions 300-555 in ring layout (wrapped)
    abs_pos = jnp.arange(300, 300 + s)
    slots = abs_pos % s
    kv_pos = jnp.zeros((b, s), jnp.int32).at[0, slots].set(abs_pos)
    q_pos = jnp.full((b,), 556, jnp.int32)
    got = dec_ops.decode_attention(q, k, v, q_pos, kv_pos, window=window)
    want = dec_ref.decode_ref(q, k, v, q_pos, kv_pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_split_sizes_agree():
    b, kvh, g, s, d = 1, 2, 2, 1024, 128
    key = jax.random.PRNGKey(6)
    q = jax.random.normal(key, (b, kvh, g, d), jnp.float32)
    k = jax.random.normal(key, (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(key, (b, kvh, s, d), jnp.float32)
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q_pos = jnp.full((b,), s - 1, jnp.int32)
    a = dec_ops.decode_attention(q, k, v, q_pos, kv_pos, bk=256)
    c = dec_ops.decode_attention(q, k, v, q_pos, kv_pos, bk=1024)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-5)
