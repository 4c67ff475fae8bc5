"""Public scan-filter API: all six predicates composed from the kernel's
{ge, eq} primitives, dispatched through repro.kernels.dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.scan_filter import kernel as K
from repro.kernels.scan_filter import ref
from repro.kernels.scan_filter.ref import OPS, field_masks
from repro.obs import metrics as obs_metrics


def _to_2d(words):
    n = words.shape[0]
    pad = (-n) % K.LANES
    if pad:
        obs_metrics.count("tile_pads")
        words = jnp.pad(words, (0, pad))
    return words.reshape(-1, K.LANES), n


def scan_filter(words, constant: int, op: str, code_bits: int,
                block_rows: int | None = None, mode=None):
    """words: (n_words,) uint32 packed codes -> (n_words,) packed mask.

    Composition rules (payload max = 2^(bits-1) - 1):
      lt = ~ge(C);  le = lt(C+1) | all-if-C==max;  gt = ge(C+1, 0-if-max);
      ne = ~eq.
    """
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of "
                         f"{OPS}")
    r = dispatch.resolve(mode)
    obs_metrics.count_launch("scan_filter")
    if not r.use_pallas:
        return ref.scan_ref(words, constant, op, code_bits)
    if words.shape[0] == 0:           # zero-row grid is undefined
        return jnp.zeros((0,), jnp.uint32)

    delim, _, value = field_masks(code_bits)
    vmax = int(value)
    w2d, n = _to_2d(jnp.asarray(words, jnp.uint32))
    br = block_rows or min(K.DEFAULT_BLOCK_ROWS, w2d.shape[0])
    run = lambda c, o: K.scan_packed(w2d, c, op=o, code_bits=code_bits,
                                     block_rows=br, interpret=r.interpret)
    dm = jnp.uint32(delim)
    c = int(constant)
    if op == "ge":
        out = run(c, "ge")
    elif op == "lt":
        out = ~run(c, "ge") & dm
    elif op == "gt":
        out = run(c + 1, "ge") if c < vmax else jnp.zeros_like(w2d)
    elif op == "le":
        out = (~run(c + 1, "ge") & dm if c < vmax
               else jnp.full_like(w2d, dm))
    elif op == "eq":
        out = run(c, "eq")
    else:  # ne
        out = ~run(c, "eq") & dm

    return out.reshape(-1)[:n]


# --------------------------------------------------------------------------
# batched (multi-chunk) path
# --------------------------------------------------------------------------
# One column's chunks differ only by their translated constant (each FOR
# chunk subtracts its own base), so a batched launch carries the per-chunk
# predicate as data, not code: canonical (prim, constant, invert) triples
# packed into per-chunk scalar planes (the SMEM scalar-prefetch idiom).

def canonical_pred(op: str, constant: int, code_bits: int):
    """Reduce any of the six predicates at any integer constant to the
    kernel-primitive triple (prim in {ge, eq}, constant in [0, vmax],
    invert) with tautologies folded: (ge, 0, False) selects every valid
    row, (ge, 0, True) selects none. Mirrors scan_filter's composition
    rules exactly (payload codes are unsigned, <= vmax)."""
    if op not in OPS:
        raise ValueError(f"unknown predicate op {op!r}; expected one of "
                         f"{OPS}")
    vmax = (1 << (code_bits - 1)) - 1
    c = int(constant)
    all_, none = ("ge", 0, False), ("ge", 0, True)
    if op == "ge":
        return all_ if c <= 0 else (none if c > vmax else ("ge", c, False))
    if op == "gt":
        return all_ if c < 0 else (none if c >= vmax else ("ge", c + 1,
                                                           False))
    if op == "lt":
        return none if c <= 0 else (all_ if c > vmax else ("ge", c, True))
    if op == "le":
        return none if c < 0 else (all_ if c >= vmax else ("ge", c + 1,
                                                           True))
    if op == "eq":
        return none if not 0 <= c <= vmax else ("eq", c, False)
    return all_ if not 0 <= c <= vmax else ("eq", c, True)   # ne


def packed_triples(triples, code_bits: int):
    """Canonical triples -> (consts, flags) int32 numpy planes for a
    batched launch: consts[k] is chunk k's constant replicated into every
    field of a packed word; flags bit0 = eq-primitive, bit1 = invert."""
    import numpy as np
    _, _, value = field_masks(code_bits)
    vmax = int(value)
    n_fields = 32 // code_bits
    consts = np.zeros(len(triples), np.int32)
    flags = np.zeros(len(triples), np.int32)
    for k, (prim, c, inv) in enumerate(triples):
        pc = 0
        for f in range(n_fields):
            pc |= (int(c) & vmax) << (f * code_bits)
        consts[k] = pc                 # delimiter bits stay 0: int32-safe
        flags[k] = (1 if prim == "eq" else 0) | (2 if inv else 0)
    return consts, flags


@partial(jax.jit, static_argnums=3)
def mask_planes(words3, consts, flags, code_bits: int):
    """Compiled core of the batched mask: per-chunk constants and flags
    enter as *traced* planes, so one compilation serves every predicate
    constant at a given (n_chunks, n_words, code_bits) — a warm trace
    replay never retraces, whatever the query mix."""
    delim, low, _ = field_masks(code_bits)
    x = jnp.asarray(words3, jnp.uint32)
    h = jnp.uint32(delim)
    C = jnp.asarray(consts).astype(jnp.uint32)[:, None]
    m_ge = ((x | h) - C) & h
    m_eq = (~(((x ^ C) | h) - jnp.uint32(low))) & h
    is_eq = (jnp.asarray(flags) & 1) == 1
    inv = (jnp.asarray(flags) & 2) == 2
    m = jnp.where(is_eq[:, None], m_eq, m_ge)
    return jnp.where(inv[:, None], m ^ h, m)  # m subset-of h: ^h == ~m & h


def mask_batched(words3, triples, code_bits: int):
    """Pure mask math for the batched scan: (n_chunks, n_words) packed
    codes + per-chunk canonical triples -> (n_chunks, n_words) packed
    masks, one compiled elementwise expression (the kernel's GE/EQ
    bit-tricks with the constant broadcast per chunk). No launch is
    counted here — callers that expose it as a dispatch wrap it."""
    consts, flags = packed_triples(triples, code_bits)
    return mask_planes(jnp.asarray(words3, jnp.uint32), consts, flags,
                       code_bits)


def scan_filter_batched(words3, triples, code_bits: int, mode=None):
    """(n_chunks, n_words) packed codes + per-chunk canonical triples ->
    (n_chunks, n_words) packed masks in ONE dispatch.

    The per-word math is elementwise (no accumulator), so PALLAS and
    XLA_REF share the jnp form — the Pallas win lives in the
    fused/aggregate stages that consume the mask.
    """
    dispatch.resolve(mode)            # validates the mode string
    obs_metrics.count_launch("scan_filter")
    return mask_batched(words3, triples, code_bits)


def _example(rng):
    codes = rng.integers(0, 128, 4096)
    return (jnp.asarray(ref.pack(codes, 8)), 64, "lt", 8), {}


dispatch.register(
    "scan_filter", fn=scan_filter, ref=ref.scan_ref, example=_example)
