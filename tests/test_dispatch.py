"""Dispatch layer tests.

- mode resolution: PALLAS (the default) runs interpret-mode Pallas
  off-TPU, XLA_REF runs the jnp oracle.
- every registered kernel family stays bit/tolerance-parity with its
  ref.py oracle under every mode.
- each scan and aggregate family answers its ref.py oracle at its default
  block size over row counts that leave a ragged last tile.
- the KV cache pytree is stored in the kernel-native layout so the decode
  step never transposes the ring (the zero-copy contract).
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch

RNG_SEED = 1234


# --------------------------------------------------------------------------
# mode resolution
# --------------------------------------------------------------------------
def test_auto_resolves_to_interpret_pallas_off_tpu():
    """No mode named resolves to PALLAS: the kernel, interpreted off the
    TPU and compiled on it."""
    r = dispatch.resolve(None)
    if jax.default_backend() == "tpu":
        assert r.use_pallas and not r.interpret
    else:
        assert r.use_pallas and r.interpret


def test_pallas_mode_is_untuned_pallas():
    """PALLAS is the default and the one kernel mode; its decision holds
    nothing but the kernel and the interpret flag."""
    assert list(dispatch.KernelMode) == [dispatch.KernelMode.PALLAS,
                                         dispatch.KernelMode.XLA_REF]
    r = dispatch.resolve(dispatch.KernelMode.PALLAS)
    assert r.use_pallas and r == dispatch.resolve(None)
    assert set(vars(r)) == {"use_pallas", "interpret"}


def test_xla_ref_and_legacy_use_kernel_flag():
    assert not dispatch.resolve("xla_ref").use_pallas
    assert dispatch.resolve(None).use_pallas
    # the oracle is reached by naming its mode, and by nothing else
    assert list(inspect.signature(dispatch.resolve).parameters) == ["mode"]


def test_registry_has_all_families():
    assert set(dispatch.registered()) == {
        "scan_filter", "aggregate", "scan_aggregate", "scan_compressed",
        "group_aggregate", "group_aggregate_packed", "mask_repack",
        "flash_attention",
        "decode_attention", "ssd_chunk"}


# --------------------------------------------------------------------------
# parity: every registered op vs its oracle under all modes
# --------------------------------------------------------------------------
def _assert_close(got, want):
    got_l = jax.tree_util.tree_leaves(got)
    want_l = jax.tree_util.tree_leaves(want)
    assert len(got_l) == len(want_l)
    for g, w in zip(got_l, want_l):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.dtype.kind in "ui" and w.dtype.kind in "ui":
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", sorted(dispatch.registered()))
@pytest.mark.parametrize("mode", ["pallas", "xla_ref"])
def test_registered_op_parity(name, mode):
    op = dispatch.get(name)
    args, kwargs = op.example(np.random.default_rng(RNG_SEED))
    got = op.fn(*args, mode=mode, **kwargs)
    want = op.ref(*args, **kwargs)
    _assert_close(got, want)


# --------------------------------------------------------------------------
# compile cache
# --------------------------------------------------------------------------
@pytest.mark.parametrize("env_dir", [None, "/some/shared/jax_cache"])
def test_compile_cache_honours_env_else_fixed_repo_dir(monkeypatch,
                                                       env_dir):
    from repro.launch import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(compile_cache.DEFAULT_DIR)
            assert want.endswith("artifacts/jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            want = env_dir
        assert compile_cache.enable() == want
        # with the variable set, JAX reads it and nothing is set in code
        assert jax.config.jax_compilation_cache_dir == (
            want if env_dir is None else None)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# --------------------------------------------------------------------------
# ragged shapes: each family pads its last tile instead of asserting
# --------------------------------------------------------------------------
# `rows` counts (rows, 128) word tiles. Below the default block (256) a
# family runs one block of all rows, past it the last block is padded; the
# packed grouped kernel also pads to whole (8, 128) tiles.

def _ragged_scan_filter(rows, rng):
    from repro.kernels.scan_filter import kernel as K
    from repro.kernels.scan_filter import ref as scan_ref
    packed = scan_ref.pack(rng.integers(0, 128, rows * 128 * 4), 8)
    w2d = jnp.asarray(packed).reshape(rows, K.LANES)
    out = K.scan_packed(w2d, 64, op="ge", code_bits=8, block_rows=32,
                        interpret=True)
    assert out.shape == w2d.shape
    return out.reshape(-1), scan_ref.scan_ref(packed, 64, "ge", 8)


def _ragged_aggregate(rows, rng):
    from repro.kernels.aggregate import ops, ref
    from repro.kernels.scan_filter import ref as scan_ref
    packed = scan_ref.pack(rng.integers(0, 128, rows * 128 * 4), 8)
    mask = scan_ref.scan_ref(packed, 64, "lt", 8)
    return (ops.aggregate(packed, mask, 8, mode="pallas"),
            ref.aggregate_ref(packed, mask, 8))


def _ragged_scan_aggregate(rows, rng):
    from repro.kernels.scan_aggregate import ops, ref
    from repro.kernels.scan_filter import ref as scan_ref
    n = rows * 128 * 4 - 3                    # a part word at the end
    pw = scan_ref.pack(rng.integers(0, 128, n), 8)
    aw = scan_ref.pack(rng.integers(0, 128, n), 8)
    valid = scan_ref.valid_mask(pw.size, n, 8)
    words = {"p": pw, "a": aw}
    return (ops.scan_conjunction(words, valid, (("p", "lt", 64),), ("a",),
                                 8, mode="pallas"),
            ref.scan_conjunction_ref(words, valid, (("p", "lt", 64),),
                                     ("a",), 8))


def _ragged_scan_compressed(rows, rng):
    from repro.kernels.scan_compressed import ops, ref
    values = rng.integers(0, 128, rows * 128).astype(np.int32)
    lengths = rng.integers(1, 9, rows * 128).astype(np.int32)
    return (ops.rle_scan_aggregate(values, lengths, 64, "lt", 8,
                                   mode="pallas"),
            ref.rle_scan_aggregate_ref(values, lengths, 64, "lt", 8))


def _ragged_group_sum_count_packed(rows, rng):
    from repro.kernels.group_aggregate import ops, ref
    from repro.kernels.scan_filter import ref as scan_ref
    n = rows * 128 * 4
    keys = scan_ref.pack(rng.integers(0, 7, n), 8)
    mask = scan_ref.pack(rng.integers(0, 2, n) << 7, 8)
    vals = tuple(jnp.asarray(scan_ref.pack(rng.integers(0, 128, n), 8))
                 for _ in range(3))
    gk = jnp.arange(7, dtype=jnp.int32)
    return (ops.group_sum_count_packed(keys, mask, vals, gk, code_bits=8,
                                       mode="pallas"),
            ref.group_sum_count_packed_ref(jnp.asarray(keys),
                                           jnp.asarray(mask), vals, gk,
                                           code_bits=8))


RAGGED = {"scan_filter": _ragged_scan_filter,
          "aggregate": _ragged_aggregate,
          "scan_aggregate": _ragged_scan_aggregate,
          "scan_compressed": _ragged_scan_compressed,
          "group_sum_count_packed": _ragged_group_sum_count_packed}
# tests/test_store.py runs RLE at one tile and
# tests/test_group_aggregate_packed.py the packed kernel at one and three:
# those families take row counts past the default block there instead
RAGGED_ROWS = {"scan_compressed": (300, 3, 37, 130),
               "group_sum_count_packed": (300, 515, 37, 130)}


@pytest.mark.parametrize("family,rows", [
    (f, r) for f in RAGGED for r in RAGGED_ROWS.get(f, (1, 3, 37, 130))])
def test_scan_packed_arbitrary_rows(family, rows):
    got, want = RAGGED[family](rows, np.random.default_rng(rows))
    got_l = jax.tree_util.tree_leaves(got)
    want_l = jax.tree_util.tree_leaves(want)
    assert len(got_l) == len(want_l)
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --------------------------------------------------------------------------
# zero-copy decode contract
# --------------------------------------------------------------------------
def test_kv_cache_is_kernel_native_layout():
    """The ring cache pytree must already be in the decode kernel's
    (B, KVH, S, D) layout — no swapaxes/reshape on the decode hot path."""
    from repro.configs import get_config
    from repro.models import attention

    cfg = get_config("internlm2-1.8b").reduced(dtype="float32",
                                               num_layers=2)
    b, s = 3, 32
    cache = attention.init_cache(cfg, b, s, jnp.float32)
    hd = cfg.resolved_head_dim
    assert cache["k"].shape == (b, cfg.num_kv_heads, s, hd)
    assert cache["v"].shape == (b, cfg.num_kv_heads, s, hd)
    assert cache["pos"].shape == (b, s)
    assert attention.CACHE_AXES["k"] == ("batch", "kv_heads", "kv_seq",
                                         "head_dim")
    # and the kernel consumes it without transposing: the reshape in
    # decode_attention_fwd merges leading axes only (a view), asserted by
    # feeding the cache layout straight through the public op.
    from repro.kernels.decode_attention import ops as dec_ops
    from repro.kernels.decode_attention import ref as dec_ref
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, cfg.num_kv_heads,
                                cfg.num_heads // cfg.num_kv_heads, hd))
    kv_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q_pos = jnp.full((b,), s - 1, jnp.int32)
    k = jax.random.normal(key, cache["k"].shape)
    v = jax.random.normal(key, cache["v"].shape)
    got = dec_ops.decode_attention(q, k, v, q_pos, kv_pos)
    want = dec_ref.decode_ref(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_no_private_interpret_probes_remain():
    """Dispatch is the only module allowed to probe the backend."""
    import pathlib

    import repro.kernels as kernels_pkg
    root = pathlib.Path(kernels_pkg.__file__).parent
    offenders = [p for p in root.rglob("*.py")
                 if p.name != "dispatch.py" and "_interpret" in p.read_text()]
    assert offenders == [], offenders
