"""Traffic files into the engine's queries, and the order they are sent in.

A traffic file (`chipbench/traffic/<name>.json`) holds:

- `loop`: "closed", and `clients`: 1, one client sending its next query
  when the last answer is back (the TPC-H power test);
- `queries`: templates, each with a `name`, a `weight`, a `where` list of
  `[column, op, constant]` predicates (their conjunction, in the order
  written), `aggregates`, and for a grouped query `group_by`.

The order of templates in a run is drawn from the seed by weight; a file
with one template sends it every time.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from chipbench.tpch import needed_bytes

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
LOOPS = ("closed",)


def load_traffic(name: str) -> dict:
    traffic = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    if traffic["loop"] not in LOOPS or traffic["clients"] != 1:
        raise ValueError(f"traffic {name!r}: loop {traffic['loop']!r} with "
                         f"{traffic['clients']} clients; only a closed loop "
                         f"of one client is generated")
    if not traffic["queries"]:
        raise ValueError(f"traffic {name!r} has no query templates")
    return traffic


def build(template: dict):
    """The engine's query object for one template."""
    from repro.query import And, GroupBy, Pred, Query
    preds = [Pred(c, op, int(k)) for c, op, k in template["where"]]
    where = preds[0] if len(preds) == 1 else And.of(*preds)
    if "group_by" in template:
        return GroupBy(template["group_by"], tuple(template["aggregates"]),
                       where=where)
    return Query(where, tuple(template["aggregates"]))


def referenced(template: dict) -> set[str]:
    cols = {c for c, _, _ in template["where"]} | set(
        template["aggregates"])
    if "group_by" in template:
        cols.add(template["group_by"])
    return cols


def template_bytes(template: dict, config: dict) -> int:
    return needed_bytes(config["columns"], config["rows"],
                        referenced(template))


def sequence(traffic: dict, seed: int):
    """Endless template indices drawn by weight from the seed."""
    w = np.array([q["weight"] for q in traffic["queries"]], float)
    rng = np.random.default_rng((seed, 7))
    while True:
        yield from rng.choice(len(w), 1024, p=w / w.sum()).tolist()
