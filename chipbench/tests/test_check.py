"""The check that decides `correct`, driven through a whole run on the CPU
with the chip look skipped: sound runs pass, and each fault a cell can
have, planted under the timed path, turns `correct` false. The control
(the reference in float32) fails it too."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import queries, reference, run, tpch

ROOT = Path(__file__).resolve().parents[2]
ROWS = 20000


def plant(fault: str | None, monkeypatch) -> None:
    """Break the timed path underneath the engine."""
    import repro.kernels.aggregate.ops as agg_ops
    import repro.kernels.scan_filter.ref as packref
    import repro.query.physical as physical
    import repro.query.relational as relational
    if fault == "answer_altered":
        fin, gfin = agg_ops.finalize, relational.finalize

        def bumped(d):
            out = fin(d)
            return dict(out, sum=out["sum"] + 1)

        def bumped_groups(part):
            out = gfin(part)
            k = min(out["groups"])
            out["groups"][k]["count"] += 1
            return out

        monkeypatch.setattr(agg_ops, "finalize", bumped)
        monkeypatch.setattr(relational, "finalize", bumped_groups)
    elif fault == "half_rows":
        vm = packref.valid_mask
        monkeypatch.setattr(packref, "valid_mask",
                            lambda w, n, b: vm(w, n // 2, b))
    elif fault == "no_exchange":
        monkeypatch.setattr(physical, "_psum_aggs", lambda d, axis: d)


def run_small(cell_name: str, devices, rows: int = ROWS) -> dict:
    spec, cell, config, traffic = run.load_cell(cell_name)
    return run.run_cell(spec, cell, dict(config, rows=rows), traffic,
                        2**33 + 5, 0.2, False, devices)


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(run, "device_peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("fault", [None, "answer_altered", "half_rows"])
@pytest.mark.parametrize("cell", ["q6_power", "q1_power"])
def test_one_chip_faults_fail_the_check(cell, fault, monkeypatch,
                                        cpu_peaks):
    import jax
    plant(fault, monkeypatch)
    out = run_small(cell, jax.devices()[:1])
    assert out["attempted"] > 0
    assert out["correct"] is (fault is None), out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) >= {"p50_ms", "scan_gb_s", "setup_s"}


FOUR_DEVICES = """
import json, sys
import pytest
from chipbench import run
from chipbench.tests import test_check as t
import jax
out = {}
for fault in [None, "answer_altered", "half_rows", "no_exchange"]:
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "device_peaks", lambda kind: {"hbm_bytes_per_s": 1.0})
    t.plant(fault, mp)
    out[str(fault)] = t.run_small("q6_power_4chip", jax.devices()[:4])
    mp.undo()
print(json.dumps({k: v["correct"] for k, v in out.items()}))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["None", "answer_altered", "half_rows",
                                   "no_exchange"])
def test_four_chip_faults_fail_the_check(four_device_runs, fault):
    assert four_device_runs[fault] is (fault == "None")


def test_float32_control_fails_the_check():
    """Sums of Q1 past 2^24 lose their low bits when accumulated in
    float32: the control answers, put in the program's place, fail."""
    spec, cell, config, traffic = run.load_cell("q1_power")
    codes = tpch.generate(dict(config, rows=3 << 20), 11)
    t = traffic["queries"][0]
    control = reference.answer(t, codes, precision="float32")
    rec = {"template": [0, 0], "answers": [control, control]}
    numbers = run.check(rec, traffic, codes)
    assert numbers["wrong_answers"] == 2 and numbers["max_gap"] > 0
    exact = dict(rec, answers=[reference.answer(t, codes)] * 2)
    assert run.check(exact, traffic, codes) == {"wrong_answers": 0,
                                                "max_gap": 0}


def test_no_tpu_exits_before_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        "q6_power", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-m", "chipbench.run", "--workload",
                        "q6_power", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_template_builds_and_counts_bytes():
    for name in ("q6_power", "q1_power"):
        traffic = queries.load_traffic(name)
        spec, cell, config, _ = run.load_cell(name)
        for t in traffic["queries"]:
            assert queries.build(t) is not None
            assert queries.template_bytes(t, config) > 0
