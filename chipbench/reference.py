"""The plain reference: a query template's exact answer, in numpy.

Imports nothing of the program. Works from the generated codes and the
template as the traffic file states it, slab by slab on a thread pool, in
int64. Answers come in the engine's result format:

- a scan: {column: {"sum", "count", "min", "max"}} over the selected rows;
- a group-by: {"groups": {key: {"count", "sums": {column: sum}}},
  "count": selected rows}, groups with no selected row left out.

`precision="float32"` is the control: the same answer with every sum and
count accumulated sequentially in float32, the precision below the exact
integers the configuration guarantees.
"""
from __future__ import annotations

import numpy as np

from chipbench.tpch import slab_map

CMP = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
       "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}


def selection(where, part: dict) -> np.ndarray:
    """The conjunction of `[column, op, constant]` predicates."""
    sel = np.ones(len(next(iter(part.values()))), bool)
    for col, op, const in where:
        sel &= CMP[op](part[col], const)
    return sel


def _total(x, precision: str):
    if precision == "float32":
        return np.cumsum(x, dtype=np.float32)[-1] if x.size \
            else np.float32(0)
    return int(x.sum(dtype=np.int64))


def _combine(parts, precision: str):
    if precision == "float32":
        return int(_total(np.asarray(parts, np.float32), precision))
    return sum(parts)


def answer(template: dict, codes: dict, precision: str = "int64") -> dict:
    """The exact (or, under the control's precision, float32) answer."""
    where = template.get("where", [])
    aggs = template["aggregates"]
    key = template.get("group_by")
    n_rows = len(next(iter(codes.values())))

    def slab(_, lo, hi):
        part = {c: codes[c][lo:hi] for c in codes}
        sel = selection(where, part)
        if key is None:
            out = {}
            for a in aggs:
                v = part[a][sel]
                out[a] = (_total(v, precision),
                          _total(np.ones(v.size, np.uint8), precision),
                          int(v.min()) if v.size else None,
                          int(v.max()) if v.size else None)
            return out
        k = part[key][sel]
        vals = {a: part[a][sel] for a in aggs}
        groups = {}
        for g in np.unique(k):
            hit = k == g
            groups[int(g)] = (
                _total(np.ones(int(hit.sum()), np.uint8), precision),
                {a: _total(vals[a][hit], precision) for a in aggs})
        return groups

    parts = slab_map(slab, n_rows)
    if key is None:
        out = {}
        for a in aggs:
            mins = [p[a][2] for p in parts if p[a][2] is not None]
            maxs = [p[a][3] for p in parts if p[a][3] is not None]
            out[a] = {"sum": _combine([p[a][0] for p in parts], precision),
                      "count": _combine([p[a][1] for p in parts], precision),
                      "min": min(mins) if mins else None,
                      "max": max(maxs) if maxs else None}
        return out
    keys = sorted({g for p in parts for g in p})
    groups = {}
    for g in keys:
        here = [p[g] for p in parts if g in p]
        groups[g] = {"count": _combine([h[0] for h in here], precision),
                     "sums": {a: _combine([h[1][a] for h in here],
                                          precision) for a in aggs}}
    return {"groups": groups,
            "count": sum(v["count"] for v in groups.values())}


def _leaves(x, path=()):
    if isinstance(x, dict):
        for k, v in x.items():
            yield from _leaves(v, path + (str(k),))
    else:
        yield path, x


def gap(got, want) -> int:
    """The largest absolute difference between two answers, field by
    field. A field that only one of them has counts as 0 in the other,
    and at least 1: an answer that never came gaps by the reference's
    largest field."""
    a, b = dict(_leaves(got or {})), dict(_leaves(want))
    worst = 0
    for k in a.keys() | b.keys():
        x, y = a.get(k), b.get(k)
        if x is None or y is None:
            worst = max(worst, abs(int(x or 0)) + abs(int(y or 0)),
                        int(x is not y))
        else:
            worst = max(worst, abs(int(x) - int(y)))
    return worst
