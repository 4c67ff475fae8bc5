"""Dispatch + autotune layer tests.

- mode resolution: AUTO/PALLAS run interpret-mode Pallas off-TPU, XLA_REF
  (and the legacy use_kernel=False) run the jnp oracle.
- every registered kernel family stays bit/tolerance-parity with its
  ref.py oracle under every mode.
- the tune cache round-trips through JSON, is hit (no re-timing) on the
  second call, and feeds ops' block-size choices.
- the KV cache pytree is stored in the kernel-native layout so the decode
  step never transposes the ring (the zero-copy contract).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, tune

RNG_SEED = 1234


# --------------------------------------------------------------------------
# mode resolution
# --------------------------------------------------------------------------
def test_auto_resolves_to_interpret_pallas_off_tpu():
    r = dispatch.resolve("auto")
    if jax.default_backend() == "tpu":
        assert r.use_pallas and not r.interpret
    else:
        assert r.use_pallas and r.interpret
    assert r.tuned


def test_pallas_mode_is_untuned_pallas():
    r = dispatch.resolve(dispatch.KernelMode.PALLAS)
    assert r.use_pallas and not r.tuned


def test_xla_ref_and_legacy_use_kernel_flag():
    assert not dispatch.resolve("xla_ref").use_pallas
    assert not dispatch.resolve(None, use_kernel=False).use_pallas
    assert dispatch.resolve(None).use_pallas


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
@pytest.mark.parametrize("mode", ["pallas", "xla_ref", "auto"])
def test_registered_op_parity(name, mode):
    op = dispatch.get(name)
    args, kwargs = op.example(np.random.default_rng(RNG_SEED))
    got = op.fn(*args, mode=mode, **kwargs)
    want = op.ref(*args, **kwargs)
    _assert_close(got, want)


# --------------------------------------------------------------------------
# tune cache
# --------------------------------------------------------------------------
def test_tune_cache_json_roundtrip_and_second_call_hit(tmp_path):
    tune.set_cache_path(tmp_path / "tune.json")
    try:
        calls = []

        def bench(params):
            calls.append(params["block_rows"])
            return {64: 0.9, 128: 0.1, 256: 0.5}[params["block_rows"]]

        # autotune times every candidate once (plus warmup) and persists
        entry = tune.autotune("fake_op", "rows=1024",
                              {"block_rows": (64, 128, 256)}, bench,
                              repeat=1)
        assert entry["params"]["block_rows"] in (64, 128, 256)
        assert len(entry["sweep"]) == 3
        n_first = len(calls)
        assert n_first == 6          # 3 candidates x (warm + 1 timed)

        # on-disk JSON, keyed by op|backend|shape
        raw = json.loads((tmp_path / "tune.json").read_text())
        key = f"fake_op|{jax.default_backend()}|rows=1024"
        assert raw[key]["params"] == entry["params"]

        # second call is a pure cache hit: no bench invocations
        again = tune.autotune("fake_op", "rows=1024",
                              {"block_rows": (64, 128, 256)}, bench)
        assert again["params"] == entry["params"]
        assert len(calls) == n_first

        # a fresh TuneCache instance reads the same file (JSON round-trip)
        tune.set_cache_path(tmp_path / "tune.json")
        assert tune.best_params("fake_op", "rows=1024",
                                {"block_rows": 999}) == entry["params"]
    finally:
        tune.set_cache_path(None)    # back to the default cache file


def test_autotune_keeps_refused_candidates_with_their_error(tmp_path):
    """A candidate the compiler refuses stays in the sweep with its error
    text and never wins; when every candidate is refused the error names
    each refusal."""
    tune.set_cache_path(tmp_path / "tune.json")
    try:
        def bench(params):
            if params["block_rows"] == 128:
                raise ValueError("Mosaic refused block (128, 128)")

        entry = tune.autotune("refusing_op", "rows=512",
                              {"block_rows": (64, 128)}, bench, repeat=1)
        assert entry["params"] == {"block_rows": 64}
        refused = [r for r in entry["sweep"] if "refused" in r]
        assert refused == [{"params": {"block_rows": 128},
                            "refused": "ValueError: Mosaic refused block "
                                       "(128, 128)"}]

        def never(params):
            raise RuntimeError(f"no tile {params['block_rows']}")

        with pytest.raises(ValueError, match="no tile 32"):
            tune.autotune("refusing_op", "rows=64",
                          {"block_rows": (32,)}, never, repeat=1)
    finally:
        tune.set_cache_path(None)


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


def test_ops_consult_tuned_block_sizes(tmp_path):
    """A cached winner changes the block size scan_filter actually uses."""
    from repro.kernels.scan_filter import kernel as K
    from repro.kernels.scan_filter import ops as scan_ops
    from repro.kernels.scan_filter import ref as scan_ref

    cache = tune.set_cache_path(tmp_path / "tune.json")
    try:
        codes = np.random.default_rng(0).integers(0, 128, 4096)
        packed = jnp.asarray(scan_ref.pack(codes, 8))
        rows = -(-packed.shape[0] // K.LANES)
        cache.store("scan_filter", tune.shape_key(rows=rows, bits=8),
                    {"params": {"block_rows": 4}, "us": 1.0})
        got = scan_ops.scan_filter(packed, 64, "lt", 8, mode="auto")
        want = scan_ref.scan_ref(packed, 64, "lt", 8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert scan_ops._block_rows(rows, 8, tuned=True) == 4
        # PALLAS mode ignores the tune cache
        assert scan_ops._block_rows(rows, 8, tuned=False) \
            == min(K.DEFAULT_BLOCK_ROWS, rows)
    finally:
        tune.set_cache_path(None)


def test_tune_fit_clamps_to_divisor():
    assert tune.fit(1024, 4096) == 1024
    assert tune.fit(96, 64) == 48
    assert tune.fit(7, 4) == 1


def test_corrupt_cache_file_is_a_miss_not_a_crash(tmp_path):
    """A truncated/garbage cache file (e.g. a killed bench run under the
    old non-atomic writer) must behave like an empty cache: best_params
    falls back to defaults, autotune re-sweeps and rewrites valid JSON."""
    path = tmp_path / "tune.json"
    path.write_text('{"scan_filter|cpu|rows=8": {"params": {"block')
    tune.set_cache_path(path)
    try:
        assert tune.best_params("scan_filter", "rows=8",
                                {"block_rows": 77}) == {"block_rows": 77}
        entry = tune.autotune("fake_op", "rows=8", {"block_rows": (4, 8)},
                              lambda p: None, repeat=1)
        assert entry["params"]["block_rows"] in (4, 8)
        raw = json.loads(path.read_text())      # valid JSON again
        assert f"fake_op|{jax.default_backend()}|rows=8" in raw
    finally:
        tune.set_cache_path(None)


def test_store_leaves_no_temp_files(tmp_path):
    """Atomic write discipline: after store() only the cache file remains
    (unique temp + os.replace, so concurrent writers can't interleave)."""
    cache = tune.set_cache_path(tmp_path / "tune.json")
    try:
        cache.store("op", "rows=1", {"params": {"b": 1}, "us": 1.0})
        assert [p.name for p in tmp_path.iterdir()] == ["tune.json"]
    finally:
        tune.set_cache_path(None)


def test_repro_tune_cache_env_override_roundtrip(tmp_path, monkeypatch):
    """REPRO_TUNE_CACHE redirects the cache file: entries stored under the
    override land at that path and are read back by a fresh cache object
    (the documented TPU-retune workflow)."""
    override = tmp_path / "elsewhere" / "tpu_tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(override))
    try:
        assert tune.cache_path() == override
        cache = tune.set_cache_path(None)       # re-resolve from the env
        assert cache.path == override
        cache.store("op", "rows=2", {"params": {"b": 2}, "us": 1.0})
        assert override.exists()
        fresh = tune.TuneCache()                # new object, same env
        assert fresh.lookup("op", "rows=2")["params"] == {"b": 2}
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        assert tune.cache_path() != override    # back to the default
    finally:
        tune.set_cache_path(None)


# --------------------------------------------------------------------------
# ragged shapes: the scan/aggregate kernels pad instead of asserting
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [1, 3, 37, 130])
def test_scan_packed_arbitrary_rows(rows):
    from repro.kernels.scan_filter import kernel as K
    from repro.kernels.scan_filter import ref as scan_ref

    codes = np.random.default_rng(rows).integers(0, 128, rows * 128 * 4)
    packed = scan_ref.pack(codes, 8)
    w2d = jnp.asarray(packed).reshape(rows, K.LANES)
    out = K.scan_packed(w2d, 64, op="ge", code_bits=8, block_rows=32,
                        interpret=True)
    assert out.shape == w2d.shape
    want = scan_ref.scan_ref(packed, 64, "ge", 8)
    np.testing.assert_array_equal(np.asarray(out).reshape(-1),
                                  np.asarray(want))


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
