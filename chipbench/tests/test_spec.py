"""BENCHMARK.json against the files the harness finds by name: every
configuration, traffic mix and metric it names has its file."""
from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_names_are_unique_and_plain():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    assert data["guarantees"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    configs = {c["name"]: c for c in SPEC["configs"]}
    data = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    assert data["chips"] == cell["chips"]
    assert (ROOT / "chipbench" / "traffic" /
            f"{cell['traffic']}.json").exists()
    e2e = [m for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    assert callable(mod.read)
    moves = {m["name"] for m in SPEC["end_to_end"]}
    if metric in SPEC["per_layer"]:
        assert metric["moves"] in moves
        for w in metric.get("workloads", []):
            assert w in {c["name"] for c in SPEC["workloads"]}
