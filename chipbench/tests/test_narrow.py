"""The narrow-width Q6 cell rehearsed on the CPU, and the reader of its
mask-repack metric."""
from __future__ import annotations

import pytest

from chipbench.metrics import repack_ms_per_query
from chipbench.tests.test_check import cpu_peaks, plant, run_small  # noqa: F401


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_narrow_cell_check(fault, monkeypatch, cpu_peaks):  # noqa: F811
    import jax
    plant(fault, monkeypatch)
    out = run_small("q6_power_narrow", jax.devices()[:1])
    assert out["attempted"] > 0
    assert out["correct"] is (fault is None), out["check"]
    assert set(out["metrics"]) >= {"p50_ms", "scan_gb_s", "setup_s"}


@pytest.mark.parametrize("ops,want", [
    ([["scan_packed", 2.0], ["repack_mask_packed", 0.3]], 3.0),
    ([["scan_packed", 2.0]], None),
])
def test_repack_reader(ops, want):
    rec = {"queries": 100, "trace": {"device_ops": ops}}
    assert repack_ms_per_query.read(rec) == (None if want is None
                                             else pytest.approx(want))
    assert repack_ms_per_query.read(dict(rec, trace=None)) is None
