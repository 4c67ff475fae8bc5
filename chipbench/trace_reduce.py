"""A profiler trace of the measured window, reduced to the numbers the
per-layer metrics read.

The window is the span from the first `submit` span's start to the last
`run` span's end, both host spans the benchmark writes into the trace
(`jax.profiler.TraceAnnotation`). On each TPU plane the device operations
are the events of its `XLA Ops` line, less those that enclose others (a
`while` loop's event spans its body's). Per chip:

- busy: the length of the union of the operation intervals inside the
  window; idle is the rest of the window;
- kernels: the Pallas kernels, which reach the device as Mosaic custom
  calls (`custom-call` operations), their summed time and count;
- collectives: all-reduce, all-gather, reduce-scatter and permute
  operations, their summed time and count;
- busy inside `run` spans: what of the engine's execute time the device
  was working.

Each is averaged over the chips. Idle gaps are put down to what the host
was doing at their middle: `submit`, `run`, or `loop` (the benchmark's
own code between the two).
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
KERNEL = re.compile(r"custom-call|custom_call")


def find_xplane(trace_dir) -> str:
    found = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(a, b) -> float:
    """Length of the overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def inside(spans, starts, t: float) -> bool:
    """Whether t lies in one of `spans` (sorted, disjoint; `starts` their
    start times)."""
    k = bisect.bisect_right(starts, t) - 1
    return k >= 0 and t < spans[k][1]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(text: str) -> str:
    """`%fusion.12 = ...HLO...` -> `fusion`: the instruction's name without
    its number, stable across compiles."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def leaves(ops):
    """Drop the operations that enclose others (a `while` around its body)
    so that no time is counted twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] + nxt[2] > op[1] + op[2]]


def op_kind(name: str) -> str:
    if COLLECTIVE.search(name):
        return "collective"
    if KERNEL.search(name):
        return "kernel"
    return "other"


def reduce_events(device_ops: dict, host_spans: dict, submit: str,
                  run: str) -> dict:
    """The reduction on plain data: `device_ops` maps each chip to a list
    of (name, start_ns, dur_ns); `host_spans` maps a span name to its
    (start_ns, end_ns) list."""
    subs, runs = sorted(host_spans.get(submit, [])), sorted(
        host_spans.get(run, []))
    if not subs or not runs or not device_ops:
        raise ValueError("the trace holds no window: no submit/run spans "
                         "or no TPU operations")
    lo, hi = subs[0][0], runs[-1][1]
    sub0, run0 = [a for a, _ in subs], [a for a, _ in runs]
    n = len(device_ops)
    acc = defaultdict(float)
    by_op = defaultdict(float)
    idle_by = defaultdict(float)
    for ops in device_ops.values():
        held = [(name, max(s, lo), min(s + d, hi))
                for name, s, d in leaves(ops) if s + d > lo and s < hi]
        merged = union((s, e) for _, s, e in held)
        acc["busy"] += sum(e - s for s, e in merged)
        acc["busy_in_run"] += covered(merged, runs)
        for name, s, e in held:
            kind = op_kind(name)
            if kind != "other":
                acc[f"{kind}_ns"] += e - s
                acc[f"{kind}_n"] += 1
            by_op[short_name(name)] += e - s
        for s, e in gaps(merged, lo, hi):
            mid = (s + e) / 2
            what = ("submit" if inside(subs, sub0, mid) else
                    "run" if inside(runs, run0, mid) else "loop")
            idle_by[what] += e - s
    ns = 1e-9 / n                               # mean over chips, seconds
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": acc["busy"] * ns,
        "busy_in_run_s": acc["busy_in_run"] * ns,
        "run_span_s": sum(b - a for a, b in runs) * 1e-9,
        "kernel_s": acc["kernel_ns"] * ns,
        "kernel_n": acc["kernel_n"] / n,
        "collective_s": acc["collective_ns"] * ns,
        "collective_n": acc["collective_n"] / n,
        "device_ops": [[k, v * ns] for k, v in top],
        "idle_gaps": sorted(([k, v * ns] for k, v in idle_by.items()),
                            key=lambda kv: -kv[1]),
    }


def load(path: str, submit: str, run: str):
    """(device_ops, host_spans) from an .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, host = {}, defaultdict(list)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            device_ops[plane.name] = [
                (e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (submit, run):
                        host[e.name].append((e.start_ns,
                                             e.start_ns + e.duration_ns))
    return device_ops, host


def reduce(path: str, submit: str, run: str) -> dict:
    return reduce_events(*load(path, submit, run), submit, run)
