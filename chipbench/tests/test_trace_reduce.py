"""The reduction from trace events to per-layer numbers, on a hand-made
two-chip trace whose answers are counted by hand, and the readers that
turn it into metrics."""
from __future__ import annotations

import importlib

import pytest

from chipbench import trace_reduce

NS = 1e-9
HOST = {"s": [(0, 10), (100, 110)], "r": [(10, 90), (110, 190)]}
DEVICE = {
    "/device:TPU:0": [("custom-call.1", 20, 30),   # [20, 50]
                      ("fusion.2", 40, 20),        # [40, 60] overlaps
                      ("all-reduce.3", 70, 10),    # [70, 80]
                      ("custom-call.1", 120, 50),  # [120, 170]
                      # a loop around the last kernel: not counted twice
                      ("%while.7 = s32[] while(s32[] %x)", 115, 60)],
    "/device:TPU:1": [("copy.4", -5, 10),           # clipped to [0, 5]
                      ("custom-call.1", 30, 20),   # [30, 50]
                      ("all-reduce.3", 60, 30),    # [60, 90]
                      ("custom-call.1", 150, 10),  # [150, 160]
                      ("fusion.2", 200, 5)],       # after the window
}


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_events(DEVICE, HOST, "s", "r")


def test_window_and_busy_union(red):
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(190 * NS)
    # chip 0: 40 + 10 + 50; chip 1: 5 + 20 + 30 + 10
    assert red["busy_s"] == pytest.approx((100 + 65) / 2 * NS)
    assert red["busy_in_run_s"] == pytest.approx((100 + 60) / 2 * NS)
    assert red["run_span_s"] == pytest.approx(160 * NS)


def test_kernel_and_collective_sums(red):
    assert red["kernel_s"] == pytest.approx((80 + 30) / 2 * NS)
    assert red["kernel_n"] == 2
    assert red["collective_s"] == pytest.approx((10 + 30) / 2 * NS)
    assert red["collective_n"] == 1


def test_idle_gaps_by_host_activity(red):
    idle = dict(red["idle_gaps"])
    assert idle["run"] == pytest.approx((50 + 125) / 2 * NS)
    assert idle["submit"] == pytest.approx(40 / 2 * NS)
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])


def test_device_ops_ranked(red):
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "custom-call" and "while" not in names
    assert dict(red["device_ops"])["custom-call"] == pytest.approx(
        (80 + 30) / 2 * NS)


@pytest.mark.parametrize("text,want", [
    ("%copy.79 = u32[1,128]{1,0} copy(u32[1,128]{1,0} %reshape.396)",
     "copy"),
    ("%group_sum_count_batched_planes.48 = s32[1,1,8,128] custom-call(...)",
     "group_sum_count_batched_planes"),
    ("fusion", "fusion")])
def test_short_name(text, want):
    assert trace_reduce.short_name(text) == want


def test_no_window_raises():
    with pytest.raises(ValueError, match="no window"):
        trace_reduce.reduce_events(DEVICE, {"s": [], "r": []}, "s", "r")


@pytest.mark.parametrize("kind,name", [
    ("kernel", "custom-call.12"), ("collective", "all-reduce.1"),
    ("collective", "all-gather-start"), ("other", "fusion.3")])
def test_op_kind(kind, name):
    assert trace_reduce.op_kind(name) == kind


def reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}").read


@pytest.mark.parametrize("name,want", [
    ("device_idle_share", 100 * (1 - 82.5 / 190)),
    ("host_ms_per_query", (160 - 80) * NS / 2 * 1e3),
    ("kernel_ms_per_query", 55 * NS / 2 * 1e3),
    ("kernel_launches_per_query", 1.0),
    ("collective_ms_per_query", 20 * NS / 2 * 1e3),
    # 2 * 819 bytes per chip take 2 ns at 819 GB/s, against 82.5 ns busy
    ("scan_roofline", 100 * 2 / 82.5),
])
def test_trace_readers(red, name, want):
    rec = {"trace": red, "queries": 2, "needed_bytes": 4 * 819,
           "chips": 2, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert reader(name)(rec) == pytest.approx(want)


def test_readers_find_nothing_without_a_trace():
    rec = {"trace": None, "queries": 2}
    for name in ("device_idle_share", "host_ms_per_query", "scan_roofline",
                 "kernel_ms_per_query", "collective_ms_per_query"):
        assert reader(name)(rec) is None


def test_host_clock_readers():
    rec = {"latency_s": [0.010, 0.020, 0.030, 0.040], "submit_s": [1e-5] * 4,
           "queries": 4, "needed_bytes": 8e9, "window_s": 2.0,
           "setup_s": 30.5, "compiles": 0}
    assert reader("p50_ms")(rec) == pytest.approx(25.0)
    assert reader("p95_ms")(rec) == pytest.approx(38.5)
    assert reader("scan_gb_s")(rec) == pytest.approx(4.0)
    assert reader("setup_s")(rec) == 30.5
    assert reader("submit_us")(rec) == pytest.approx(10.0)
    assert reader("compiles_in_window")(rec) == 0


def test_recorded_q6_trace():
    """Four Q6 queries as a v5e chip ran them (short names kept): five
    predicate scans and two aggregates per query, all on the device, with
    the host's finalize and dispatch between queries."""
    import json
    from pathlib import Path
    d = json.loads((Path(__file__).parent / "data" /
                    "q6_power_trace.json").read_text())
    ops = {p: [tuple(o) for o in v] for p, v in d["device_ops"].items()}
    red = trace_reduce.reduce_events(ops, d["host_spans"],
                                     "chipbench.submit", "chipbench.run")
    n = len(d["host_spans"]["chipbench.run"])
    assert n == 4 and red["chips"] == 1
    assert red["kernel_n"] == 7 * n
    assert 0 < red["kernel_s"] < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["busy_in_run_s"] <= red["busy_s"]
    names = dict(red["device_ops"])
    assert names["custom-call:scan_packed"] > 0
    assert names["custom-call:aggregate_batched_packed"] > 0
