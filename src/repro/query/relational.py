"""Relational operators: grouped aggregation & hash join execution.

The compile target for `plan.GroupBy` / `plan.HashJoin`: this module owns
the pieces every execution surface (plain tables here, the compressed
store in store/exec.py, the mesh in query/sharded.py, degraded re-runs in
resilience/recover.py) shares —

- bind/validation with actionable errors (unknown column, aggregate over
  the key, join-key width mismatch naming both columns and widths),
- the group-domain choice: a dense arange when the observed/FOR-framed
  key span stays under `DENSE_MAX_GROUPS`, the sorted distinct build
  keys for a join, or the host sort/hash fallback above the cutoff,
- predicate-tree evaluation over int32 code planes (numpy or jnp — the
  unpacked analogue of physical.eval_mask),
- the host-partial algebra: per-chunk/per-shard `(G, 3)` accumulator
  planes become exact Python-int partial dicts (FOR base fix-up applied
  per plane), merged associatively and finalized into
  `{"groups": {key: {"count", "sums"}}, "count": total}`.

Every path — PALLAS kernel, XLA_REF oracle, numpy fallback, sharded
all-gather — lands in the same partial algebra, which is how bit-exact
parity across all four is kept a structural property instead of a test
hope.
"""
from __future__ import annotations

import operator

import jax.numpy as jnp
import numpy as np

from repro.kernels.group_aggregate import ops as gops
from repro.kernels.group_aggregate.ops import DENSE_MAX_GROUPS
from repro.kernels.scan_filter import ref as packref
from repro.obs import metrics as obs_metrics
from repro.query import physical
from repro.query.plan import And, GroupBy, HashJoin, Or, Pred, is_grouped

_OPS = {"lt": operator.lt, "le": operator.le, "gt": operator.gt,
        "ge": operator.ge, "eq": operator.eq, "ne": operator.ne}


# --------------------------------------------------------------------------
# bind / validation
# --------------------------------------------------------------------------

def bind_check(query, columns) -> None:
    """Validate a GroupBy/HashJoin against a table's columns before any
    work: unknown columns (key, aggregates, plan) and join-key width
    mismatches raise actionable ValueErrors."""
    physical.bind_check(query.plan(), query.aggregates, columns)
    if isinstance(query, HashJoin):
        probe_bits = columns[query.probe].code_bits
        build_bits = query.build.columns[query.on].code_bits
        if probe_bits != build_bits:
            raise ValueError(
                f"HashJoin key width mismatch: probe column "
                f"{query.probe!r} is {probe_bits}-bit but build column "
                f"{query.on!r} is {build_bits}-bit; join keys compare "
                f"dictionary codes, so both sides must share one code "
                f"width — re-encode the narrower side")


def build_keys(join: HashJoin) -> np.ndarray:
    """Sorted distinct dictionary codes of the build side's join column —
    the hash table this join broadcasts (a sorted array: membership and
    group slots resolve by binary search, not scatter)."""
    col = join.build.columns[join.on]
    codes = np.asarray(packref.unpack(col.words, col.code_bits))
    codes = codes[:col.num_rows]
    return np.unique(codes).astype(np.int64)


def group_domain(query, kmin: int, kmax: int) -> np.ndarray:
    """Candidate group keys given the observed (or FOR-framed) key code
    range [kmin, kmax] — dense arange for GroupBy, the build side's
    distinct keys (clipped to the observable range) for HashJoin."""
    if isinstance(query, HashJoin):
        bk = build_keys(query)
        return bk[(bk >= kmin) & (bk <= kmax)]
    if kmax < kmin:                      # zero-row table
        return np.zeros(0, np.int64)
    return np.arange(kmin, kmax + 1, dtype=np.int64)


def dense_ok(domain: np.ndarray) -> bool:
    return len(domain) <= DENSE_MAX_GROUPS


# --------------------------------------------------------------------------
# predicate trees over code planes
# --------------------------------------------------------------------------

def eval_plan_codes(plan, cols: dict):
    """Evaluate a Pred/And/Or tree over unpacked int32 code arrays
    (numpy in, numpy out; jnp in, jnp out) -> boolean selection."""
    if isinstance(plan, Pred):
        return _OPS[plan.op](cols[plan.column], plan.constant)
    parts = [eval_plan_codes(c, cols) for c in plan.children]
    out = parts[0]
    for p in parts[1:]:
        out = (out & p) if isinstance(plan, And) else (out | p)
    return out


def key_only_pred(query, code_bits: int):
    """If the query's plan is a single Pred on the group key (the
    tautology included), return its canonical (prim, const, invert)
    triple — what the fused RLE kernel evaluates on run values in
    registers; return False for any other plan shape."""
    from repro.kernels.scan_filter.ops import canonical_pred
    plan = query.plan()
    if not isinstance(plan, Pred) or plan.column != query.key:
        return False
    return canonical_pred(plan.op, plan.constant, code_bits)


# --------------------------------------------------------------------------
# host-partial algebra (exact Python ints)
# --------------------------------------------------------------------------

def new_partial() -> dict:
    return {}


def absorb_plane(partial: dict, domain, plane, col: str | None,
                 base: int = 0, key_base: int = 0,
                 count_source: bool = False) -> dict:
    """Fold one (G, 3) accumulator plane into a host partial.

    domain: the plane's group keys (kernel domain); key_base shifts them
    back to logical codes (FOR delta keys), base is the value column's
    FOR base fix-up (sum += base * count, exact). Counts are added only
    when count_source (one plane per chunk carries them — every value
    column's launch returns identical counts)."""
    keys, sums, counts = gops.finalize_grouped(domain, plane, base)
    for k, s, c in zip(keys, sums, counts):
        if c == 0:
            continue
        entry = partial.setdefault(int(k) + key_base, [0, {}])
        if count_source:
            entry[0] += int(c)
        if col is not None:
            entry[1][col] = entry[1].get(col, 0) + int(s)
    return partial


def absorb_fallback(partial: dict, key_codes, val_cols: dict,
                    sel) -> dict:
    """The sort/hash strategy: numpy bincount/add.at over one chunk's
    decoded codes — exact in int64, no kernel launch."""
    k = np.asarray(key_codes)[np.asarray(sel)]
    if k.size == 0:
        return partial
    uniq, inv = np.unique(k, return_inverse=True)
    counts = np.bincount(inv, minlength=len(uniq)).astype(np.int64)
    sums = {}
    for name, v in val_cols.items():
        acc = np.zeros(len(uniq), np.int64)
        np.add.at(acc, inv, np.asarray(v, np.int64)[np.asarray(sel)])
        sums[name] = acc
    for i, key in enumerate(uniq):
        entry = partial.setdefault(int(key), [0, {}])
        entry[0] += int(counts[i])
        for name in val_cols:
            entry[1][name] = entry[1].get(name, 0) + int(sums[name][i])
    return partial


def combine(a: dict, b: dict) -> dict:
    """Merge two host partials (associative, commutative, exact)."""
    for k, (c, sums) in b.items():
        entry = a.setdefault(k, [0, {}])
        entry[0] += c
        for name, s in sums.items():
            entry[1][name] = entry[1].get(name, 0) + s
    return a


def restrict(partial: dict, keys) -> dict:
    """Keep only groups whose key is in `keys` (join semantics when a
    fallback chunk grouped every key it saw)."""
    allowed = set(int(k) for k in keys)
    return {k: v for k, v in partial.items() if k in allowed}


def finalize(partial: dict) -> dict:
    """Host partial -> the engine's grouped result: groups sorted by key,
    zero-count groups dropped, `count` the total selected rows."""
    groups = {}
    total = 0
    for k in sorted(partial):
        c, sums = partial[k]
        if c == 0:
            continue
        groups[k] = {"count": c, "sums": dict(sorted(sums.items()))}
        total += c
    return {"groups": groups, "count": total}


def empty_result() -> dict:
    return {"groups": {}, "count": 0}


# --------------------------------------------------------------------------
# plain-table execution (the numpy-backed BitPackedColumn path)
# --------------------------------------------------------------------------

def _codes(col) -> np.ndarray:
    vals = np.asarray(packref.unpack(col.words, col.code_bits))
    return vals[: col.num_rows].astype(np.int64)


def execute_grouped_oracle(query, table) -> dict:
    """The numpy oracle: decode, select, group with add.at — the ground
    truth every kernel/sharded/degraded path must match bit-exactly."""
    bind_check(query, table.columns)
    cols = {n: _codes(c) for n, c in table.columns.items()
            if n in set(query.aggregates) | physical.columns_of(
                query.plan())}
    n = table.num_rows
    sel = np.asarray(eval_plan_codes(query.plan(), cols)) \
        if n else np.zeros(0, bool)
    if isinstance(query, HashJoin):
        bk = build_keys(query)
        sel = sel & np.isin(cols[query.key], bk)
    part = absorb_fallback(new_partial(), cols[query.key],
                           {a: cols[a] for a in query.aggs}, sel)
    return finalize(part)


def execute_grouped(query, table, mode=None) -> dict:
    """GroupBy/HashJoin over a plain bit-packed table through the
    group_aggregate kernel family (dense strategy; host fallback above
    the dense cutoff). Returns the finalized grouped result."""
    bind_check(query, table.columns)
    n = table.num_rows
    if n == 0:
        return empty_result()
    need = set(query.aggregates) | physical.columns_of(query.plan())
    # columns of different widths unpack to different padded lengths;
    # truncating to the logical rows puts every plane on one row axis
    planes = {name: jnp.asarray(packref.unpack(
        table.columns[name].words, table.columns[name].code_bits),
        jnp.int32)[:n] for name in need}
    sel = eval_plan_codes(query.plan(), planes)
    kmin, kmax = (int(jnp.min(planes[query.key])),
                  int(jnp.max(planes[query.key])))
    domain = group_domain(query, kmin, kmax)
    part = new_partial()
    if not dense_ok(domain):
        obs_metrics.count_launch("group_aggregate_fallback")
        cols = {name: np.asarray(p)[:n] for name, p in planes.items()}
        sel_np = np.asarray(sel)[:n]
        if isinstance(query, HashJoin):
            sel_np = sel_np & np.isin(cols[query.key], build_keys(query))
        absorb_fallback(part, cols[query.key],
                        {a: cols[a] for a in query.aggs}, sel_np)
        if isinstance(query, HashJoin):
            part = restrict(part, build_keys(query))
        return finalize(part)
    if len(domain) == 0:
        return empty_result()
    sel_i = sel.astype(jnp.int32)
    value_cols = query.aggs if query.aggs else (None,)
    for i, name in enumerate(value_cols):
        vals = planes[name] if name is not None \
            else jnp.zeros_like(planes[query.key])
        plane = gops.group_sum_count(planes[query.key], vals, sel_i,
                                     domain, mode=mode)
        absorb_plane(part, domain, np.asarray(plane), name,
                     count_source=(i == 0))
    return finalize(part)
