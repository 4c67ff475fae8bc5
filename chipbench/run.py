"""Chip benchmark of the analytic query engine: one run of one cell.

    python3 -m chipbench.run --workload q6_power --seed 7 --seconds 20 \
        --trace 0

Run from the root of a checkout. The cell, its configuration and its
traffic are looked up by name from BENCHMARK.json; nothing here knows a
cell. A run:

1. turns on JAX's persistent compile cache (`launch/compile_cache`) and
   refuses to go on unless JAX sees a TPU with as many chips as the cell
   asks for;
2. generates the configuration's lineitem from `--seed`, packs it, and
   places it as a ShardedTable row-sharded over a (chips,) mesh;
3. warms the traffic's own queries, each twice;
4. sends them through `QueryEngine.submit` -> `run` in mode pallas for
   `--seconds` (a closed loop: the next query goes when the last answer
   is on the host), under the profiler with `--trace 1`;
5. reads peak HBM, frees the table, and compares every answer of the
   window with the numpy reference.

Lines before the last on stdout are set-up readings (host clock). The
last stdout line is the result, one JSON object; the numbers the check
compared, each beside its limit, are the last lines on stderr. With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics; each metric is read by
`chipbench/metrics/<name>.py` from the run's record.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import queries, reference, tpch, trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SPAN_SUBMIT, SPAN_RUN = "chipbench.submit", "chipbench.run"
# lowering a jaxpr to a module happens once for every program built,
# whether the backend compile then hits the persistent cache or not
PROGRAM_BUILD_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
WARM_RUNS = 2
LIMITS = {"wrong_answers": 0, "max_gap": 0}


def say(*parts) -> None:
    print("chipbench:", *parts, flush=True)


def load_cell(name: str, root: Path = ROOT):
    """(benchmark spec, cell, configuration, traffic) for a cell name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no cell {name!r}; cells are "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    return spec, cell, config, queries.load_traffic(cell["traffic"])


def device_peaks(kind: str) -> dict:
    """The chip's published peaks; a chip missing from the table is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU (jax sees {devs[0].platform}); refusing "
              f"to run", file=sys.stderr, flush=True)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chips, jax sees "
              f"{len(devs)}", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return devs[:chips]


def place(config: dict, seed: int, chips: int):
    """(codes, ShardedTable): the lineitem generated, packed into the
    engine's columns and placed row-sharded on the first `chips`
    devices."""
    from repro.db.columnar import BitPackedColumn, Table
    from repro.kernels.scan_filter.ref import pack
    from repro.launch.mesh import make_mesh
    from repro.query import ShardedTable
    rows = config["rows"]
    t = time.perf_counter()
    codes = tpch.generate(config, seed)
    say(f"generated {rows} rows of {config['name']} from seed {seed} in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    table = Table(config["name"])
    for name, bits in config["columns"].items():
        c, cpw = codes[name], 32 // bits
        words = np.empty(-(-rows // cpw), np.uint32)

        def pack_slab(_, lo, hi, c=c, words=words, cpw=cpw, bits=bits):
            words[lo // cpw:-(-hi // cpw)] = pack(c[lo:hi], bits)

        tpch.slab_map(pack_slab, rows)
        table.add(BitPackedColumn(name, bits, rows, words))
    say(f"packed {table.nbytes} bytes of words in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    st = ShardedTable.shard(table, make_mesh((chips,), ("data",)))
    for s in st.slices.values():
        s.words.block_until_ready()
        s.valid.block_until_ready()
    resident = sum(int(s.words.size + s.valid.size) * 4
                   for s in st.slices.values())
    say(f"placed {resident} bytes (words and validity) on {chips} "
        f"device(s) in {time.perf_counter() - t:.3f} s")
    return codes, st


class ProgramBuilds:
    """Counts programs JAX builds while `on` is set."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == PROGRAM_BUILD_EVENT:
            self.n += 1


def serve_window(eng, built: list, seq, seconds: float, trace_dir):
    """The measured window: a closed loop of submit -> run for `seconds`.
    Returns the record of every query sent."""
    import jax
    span = (jax.profiler.TraceAnnotation if trace_dir is not None
            else lambda _name: contextlib.nullcontext())
    rec = {"template": [], "answers": [], "latency_s": [], "submit_s": [],
           "failed": 0}
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    t_start = time.perf_counter()
    t_end = t_start + seconds
    t_done = t_start
    while True:
        t_due = time.perf_counter()
        if t_due >= t_end:
            break
        i = next(seq)
        with span(SPAN_SUBMIT):
            qid = eng.submit(built[i])
        t_sub = time.perf_counter()
        with span(SPAN_RUN):
            out = eng.run() if qid is not None else []
        t_done = time.perf_counter()
        rec["template"].append(i)
        rec["submit_s"].append(t_sub - t_due)
        rec["latency_s"].append(t_done - t_due)
        ok = len(out) == 1 and not out[0].degraded
        rec["failed"] += not ok
        rec["answers"].append(out[0].aggregates if ok else None)
    rec["window_s"] = t_done - t_start
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return rec


def check(rec: dict, traffic: dict, codes: dict) -> dict:
    """Every answer of the window against the reference: how many differ,
    and the widest gap of any field."""
    want = {i: reference.answer(traffic["queries"][i], codes)
            for i in sorted(set(rec["template"]))}
    gaps = [reference.gap(got, want[i])
            for i, got in zip(rec["template"], rec["answers"])]
    return {"wrong_answers": sum(g > 0 for g in gaps),
            "max_gap": max(gaps, default=0)}


def read_metrics(spec: dict, cell: dict, trace: bool, rec: dict) -> dict:
    """The cell's end-to-end (`trace` off) or per-layer (`trace` on)
    metrics, each read by its own module; a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = importlib.import_module(
            f"chipbench.metrics.{m['name']}").read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, devices) -> dict:
    """One run of a cell on `devices`; returns the result line's object."""
    from repro.query import QueryEngine
    builds = ProgramBuilds()
    chips = cell["chips"]
    codes, st = place(config, seed, chips)
    eng = QueryEngine(st, mode="pallas")
    built = [queries.build(q) for q in traffic["queries"]]
    for q in built:
        for _ in range(WARM_RUNS):
            eng.submit(q)
            eng.run()
    setup_s = time.perf_counter() - T0
    say(f"set-up {setup_s:.3f} s; warm {WARM_RUNS} x {len(built)} "
        f"query template(s)")
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        builds.on = True
        rec = serve_window(eng, built, queries.sequence(traffic, seed),
                           seconds, Path(tmp) if trace else None)
        builds.on = False
        reduced = (trace_reduce.reduce(trace_reduce.find_xplane(tmp),
                                       SPAN_SUBMIT, SPAN_RUN)
                   if trace else None)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)
    say(f"peak_bytes_in_use on the fullest chip: {peak}")
    del eng, st
    gc.collect()
    t = time.perf_counter()
    numbers = check(rec, traffic, codes)
    say(f"reference check of {len(rec['answers'])} answers in "
        f"{time.perf_counter() - t:.3f} s")
    rec.update(
        queries=len(rec["latency_s"]), setup_s=setup_s, chips=chips,
        compiles=builds.n, trace=reduced,
        needed_bytes=sum(queries.template_bytes(traffic["queries"][i],
                                                config)
                         for i in rec["template"]),
        peaks=device_peaks(devices[0].device_kind))
    correct = (rec["queries"] > 0 and rec["failed"] == 0
               and all(numbers[k] <= LIMITS[k] for k in LIMITS))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": rec["queries"],
           "failed": rec["failed"],
           "metrics": read_metrics(spec, cell, trace, rec),
           "device": device}
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["check"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                    for k in LIMITS}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, config, traffic = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    import jax
    say(f"compile cache {compile_cache.enable()}")
    # every program, however quick to compile, is read back from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = require_tpu(cell["chips"])
    out = run_cell(spec, cell, config, traffic, args.seed, args.seconds,
                   bool(args.trace), devices)
    for k, v in out["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
