"""chip_smoke.py off the chip: it refuses the CPU, and its phases (seeded
data, engine queries, numpy oracle) agree at a small size in interpret
mode, so API drift shows up here before it costs chip time."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.require_tpu(1)
    assert e.value.code == 2
    assert capsys.readouterr().out == ""          # no result line


def test_generated_codes_are_seeded_and_in_range(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "SLAB_ROWS", 1 << 10)
    for bits in (8, 16):
        a = smoke.uniform_codes(5, 0, 5000, bits)
        assert np.array_equal(a, smoke.uniform_codes(5, 0, 5000, bits))
        assert not np.array_equal(a, smoke.uniform_codes(6, 0, 5000, bits))
        assert a.max() < 1 << (bits - 1)


def test_phases_match_the_oracle_in_interpret_mode(smoke, monkeypatch):
    from repro.query import sharded
    monkeypatch.setattr(smoke, "plan_rows", lambda n, devs: 20000 + 48)
    monkeypatch.setattr(smoke, "SLAB_ROWS", 1 << 12)
    monkeypatch.setattr(smoke, "ENC_ROWS", 1 << 17)
    monkeypatch.setattr(sharded, "GROUP_SLAB_ROWS", 1 << 12)
    devs = jax.devices()
    # each phase raises SystemExit on any answer that differs
    flat = smoke.run_sharded(1, 3, devs)
    enc = smoke.run_encoded(3, devs)
    seen = set(flat.metrics.launch_counts()) \
        | set(enc.metrics.launch_counts())
    assert seen == smoke.PALLAS_FAMILIES
