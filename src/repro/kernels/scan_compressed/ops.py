"""Public scan-over-compressed API, dispatched through
repro.kernels.dispatch.

`rle_scan_aggregate` is the fused SELECT agg(col) WHERE col <op> const
over one RLE-encoded chunk: runs stream instead of rows, so effective
bandwidth multiplies by rows/runs. FOR-encoded chunks need no kernel of
their own — a FOR plane *is* a plain BitWeaving plane at the delta width,
so repro.store.exec routes them through the existing scan_filter /
aggregate / scan_aggregate families at the narrower width with a
translated constant and an exact host-side base fix-up.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.aggregate import ref as agg_ref
from repro.kernels.scan_compressed import kernel as K
from repro.kernels.scan_compressed import ref
from repro.kernels.scan_filter.kernel import DEFAULT_BLOCK_ROWS, LANES
from repro.kernels.scan_filter.ref import OPS
from repro.obs import metrics as obs_metrics


def rle_scan_aggregate(values, lengths, constant: int, op: str,
                       code_bits: int, block_rows: int | None = None,
                       mode=None) -> dict:
    """Fused predicate + aggregate over RLE run planes ->
    dict(sum_lo, sum_hi, count, min, max); reassemble the exact sum with
    repro.kernels.aggregate.ops.finalize.

    values/lengths are the (n_runs_padded,) int32 planes of one store
    chunk (repro.store.encode); zero-length runs are inert padding.
    """
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of "
                         f"{OPS}")
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_compressed")
    if not r.use_pallas:
        return ref.rle_scan_aggregate_ref(values, lengths, constant, op,
                                          code_bits)
    v = jnp.asarray(values, jnp.int32)
    l = jnp.asarray(lengths, jnp.int32)
    if v.size == 0:                   # zero-run grid is undefined
        return agg_ref.identity(code_bits)

    def to2d(x):
        return jnp.pad(x, (0, (-x.shape[0]) % LANES)).reshape(-1, LANES)

    v2d, l2d = to2d(v), to2d(l)
    rows = v2d.shape[0]
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    out = K.rle_scan_aggregate_packed(v2d, l2d, constant=int(constant),
                                      op=op, code_bits=code_bits,
                                      block_rows=br, interpret=r.interpret)
    return {"sum_lo": out[0, 0], "sum_hi": out[0, 1], "count": out[0, 2],
            "min": out[0, 3], "max": out[0, 4]}


def rle_scan_aggregate_batched(planes, constant: int, op: str,
                               code_bits: int,
                               block_rows: int | None = None, mode=None):
    """All RLE chunks of a column in ONE launch.

    planes: sequence of (values, lengths) run-plane pairs, one per chunk
    (ragged run counts allowed). Returns int32[n_chunks, 5] — one
    [sum_lo, sum_hi, count, min, max] row per chunk, bit-identical to
    calling `rle_scan_aggregate` per chunk: ragged chunks are padded to
    the widest with zero-length runs, which select nothing.
    """
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of "
                         f"{OPS}")
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_compressed")
    n_chunks = len(planes)
    if n_chunks == 0:
        return jnp.zeros((0, 5), jnp.int32)

    def to2d(x):
        x = jnp.asarray(x, jnp.int32)
        return jnp.pad(x, (0, (-x.shape[0]) % LANES)).reshape(-1, LANES)

    pairs = [(to2d(v), to2d(l)) for v, l in planes]
    rows = max(max(v.shape[0] for v, _ in pairs), 1)

    def lift(x):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))

    v3 = jnp.stack([lift(v) for v, _ in pairs])
    l3 = jnp.stack([lift(l) for _, l in pairs])
    if not r.use_pallas:
        return ref.rle_scan_aggregate_batched_ref(v3, l3, constant, op,
                                                  code_bits)
    br = block_rows or min(DEFAULT_BLOCK_ROWS, rows)
    return K.rle_scan_aggregate_batched_packed(
        v3, l3, constant=int(constant), op=op, code_bits=code_bits,
        block_rows=br, interpret=r.interpret)


def _example(rng):
    n = 2000                           # non-pow2: exercises lane padding
    values = jnp.asarray(rng.integers(0, 128, n), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, 9, n), jnp.int32)
    return (values, lengths, 64, "lt", 8), {}


dispatch.register(
    "scan_compressed", fn=rle_scan_aggregate,
    ref=ref.rle_scan_aggregate_ref,
    example=_example)
