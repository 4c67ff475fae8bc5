"""Bring-up smoke run of the analytic query engine on a TPU.

Drives the served path, `QueryEngine.submit` -> `run` in kernel mode
PALLAS (compiled Mosaic kernels, never the interpreter), over a table made
from `--seed` at a size users would call real, and checks every answer
exactly against a plain numpy computation on the generated codes.

    python chip_smoke.py [--seed N]      # one chip (the default)
    python chip_smoke.py --chips 4       # the sharded engine on 4 chips

One chip: the `{a: 8, b: 8, c: 16}`-bit schema of
benchmarks/queries_bench.py at 2^30 rows (4 GiB of packed words, 8 GiB on
the chip with the validity planes) as a ShardedTable over a 1-device mesh,
then a smaller EncodedTable (sorted low-cardinality RLE key, FOR-framed
value column) so the batched RLE and FOR kernels run. `--chips 4` runs only
the sharded phase at 2^31 rows over a 4-device mesh, and also checks the
psum'd answers against the per-shard partials merged on the host.

Lines before the last are smoke readings (host clock, one run each), not
benchmark numbers. The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Any failed phase exits non-zero before that line is printed. The script
runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCHEMA = {"a": 8, "b": 8, "c": 16}
TARGET_ROWS_LOG2 = {1: 30, 4: 31}
ENC_ROWS = 1 << 24              # the encoded table: 256 chunks of 65536
ENC_CHUNK_ROWS = 1 << 16
SLAB_ROWS = 1 << 24             # rows per generation / oracle step
# host bytes per row at peak: the codes (4), their packed words (4), one
# column's validity plane and pack temporaries (4), oracle masks (4)
HOST_BYTES_PER_ROW = 16
# device bytes per row: packed words + validity planes of the schema
DEVICE_BYTES_PER_ROW = 8
# the Pallas kernel families of the served path; anything else launched
# (or a host fallback) fails the run
PALLAS_FAMILIES = {"scan_filter", "aggregate", "scan_aggregate",
                   "scan_compressed", "group_aggregate",
                   "group_aggregate_packed", "group_aggregate_rle",
                   "mask_repack"}


def say(*parts) -> None:
    print("smoke:", *parts, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


# --------------------------------------------------------------------------
# device, memory budget, data
# --------------------------------------------------------------------------

def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {devs[0].platform}); "
              f"refusing to run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < n_chips:
        print(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
              f"jax sees {len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    from repro.kernels import dispatch
    r = dispatch.resolve("pallas")
    check(r.use_pallas and not r.interpret,
          f"mode pallas resolved to {r} on a TPU")
    return devs


def host_available_bytes() -> int:
    for line in Path("/proc/meminfo").read_text().splitlines():
        if line.startswith("MemAvailable:"):
            return int(line.split()[1]) * 1024
    fail("cannot read MemAvailable from /proc/meminfo")


def plan_rows(n_chips: int, devs) -> int:
    """The target row count, halved while host memory or half of each
    chip's HBM cannot hold it; every cut is printed with its reason."""
    rows = 1 << TARGET_ROWS_LOG2[n_chips]
    device_limit = devs[0].memory_stats()["bytes_limit"]
    host = host_available_bytes()
    while rows * HOST_BYTES_PER_ROW > 0.8 * host:
        say(f"cut: {rows} rows need ~{rows * HOST_BYTES_PER_ROW} host "
            f"bytes, {host} available; halving")
        rows //= 2
    while rows * DEVICE_BYTES_PER_ROW / n_chips > 0.55 * device_limit:
        say(f"cut: {rows} rows need {rows * DEVICE_BYTES_PER_ROW // n_chips}"
            f" device bytes per chip of {device_limit}; halving")
        rows //= 2
    return rows


def slab_map(fn, n_rows: int) -> list:
    """fn(lo, hi) over the SLAB_ROWS slabs of [0, n_rows) on a thread pool
    (numpy releases the GIL in these loops); results in slab order."""
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        return list(pool.map(lambda lo: fn(lo, min(n_rows, lo + SLAB_ROWS)),
                             range(0, n_rows, SLAB_ROWS)))


def uniform_codes(seed: int, col: int, rows: int, bits: int) -> np.ndarray:
    """Uniform codes over the payload range [0, 2^(bits-1)) in the
    narrowest numpy dtype: random bytes masked to the payload bits
    (uniform, since the range is a power of two), one generator per
    (seed, column, slab) so slabs fill in parallel and reproducibly."""
    dt = np.dtype(np.uint8 if bits <= 8 else np.uint16)
    payload = dt.type((1 << (bits - 1)) - 1)
    out = np.empty(rows, dt)

    def fill(lo, hi):
        rng = np.random.default_rng((seed, col, lo // SLAB_ROWS))
        np.bitwise_and(np.frombuffer(rng.bytes((hi - lo) * dt.itemsize),
                                     dt), payload, out=out[lo:hi])

    slab_map(fill, rows)
    return out


def make_table(rows: int, seed: int):
    from repro.db.columnar import BitPackedColumn, Table
    from repro.kernels.scan_filter.ref import pack
    codes, table = {}, Table("smoke")
    for i, (name, bits) in enumerate(SCHEMA.items()):
        c = codes[name] = uniform_codes(seed, i, rows, bits)
        cpw = 32 // bits
        words = np.empty(-(-rows // cpw), np.uint32)

        def pack_slab(lo, hi, c=c, words=words, cpw=cpw, bits=bits):
            words[lo // cpw:-(-hi // cpw)] = pack(c[lo:hi], bits)

        slab_map(pack_slab, rows)
        table.add(BitPackedColumn(name, bits, rows, words))
    return codes, table


def make_encoded(seed: int):
    """Sorted low-cardinality key k (RLE) and a value column v whose
    chunks each span < 128 codes (FOR at 8-bit deltas)."""
    from repro.store.encode import EncodedColumn, EncodedTable
    rng = np.random.default_rng(seed + 1)
    n_chunks = ENC_ROWS // ENC_CHUNK_ROWS
    k = np.sort(rng.integers(0, 128, ENC_ROWS, dtype=np.uint8))
    base = rng.integers(0, (1 << 15) - 128, n_chunks).astype(np.uint16)
    v = (np.repeat(base, ENC_CHUNK_ROWS)
         + rng.integers(0, 128, ENC_ROWS, dtype=np.uint16))
    codes = {"k": k, "v": v}
    t = EncodedTable("smoke_enc", ENC_CHUNK_ROWS)
    t.columns["k"] = EncodedColumn.from_values("k", k, 8, ENC_CHUNK_ROWS)
    t.columns["v"] = EncodedColumn.from_values("v", v, 16, ENC_CHUNK_ROWS)
    return codes, t


# --------------------------------------------------------------------------
# the numpy oracle (independent of the engine's code)
# --------------------------------------------------------------------------

_CMP = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
        "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}


def select(plan, codes) -> np.ndarray:
    from repro.query.plan import And, Pred
    if isinstance(plan, Pred):
        return _CMP[plan.op](codes[plan.column], plan.constant)
    parts = [select(c, codes) for c in plan.children]
    out = parts[0]
    for p in parts[1:]:
        out = (out & p) if isinstance(plan, And) else (out | p)
    return out


def oracle(query, codes, bits: dict) -> dict:
    """The query's exact answer in the engine's result format, computed
    slab by slab (in parallel) in int64 so the temporaries stay small."""
    from repro.query.plan import is_grouped
    grouped = is_grouped(query)
    n_keys = 1 << (bits[query.key] - 1) if grouped else 0

    def slab(lo, hi):
        part = {c: x[lo:hi] for c, x in codes.items()}
        sel = select(query.plan(), part)
        if grouped:
            k = part[query.key][sel]
            # float64 bincount is exact per slab: partial sums are
            # integers below 2^24 * 2^15 < 2^53
            return (np.bincount(k, minlength=n_keys),
                    {a: np.bincount(k, weights=part[a][sel],
                                    minlength=n_keys).astype(np.int64)
                     for a in query.aggs})
        n = int(np.count_nonzero(sel))
        out = {}
        for a in query.aggregates:
            v = part[a]
            hit = v * sel                       # v where selected, else 0
            vmax = v.dtype.type((1 << (bits[a] - 1)) - 1)
            out[a] = (int(hit.sum(dtype=np.int64)), n,
                      int(np.maximum(v, ~sel * vmax).min()), int(hit.max()))
        return out

    parts = slab_map(slab, len(next(iter(codes.values()))))
    if grouped:
        counts = sum(p[0] for p in parts)
        sums = {a: sum(p[1][a] for p in parts) for a in query.aggs}
        return {"groups": {int(g): {"count": int(counts[g]),
                                    "sums": {a: int(sums[a][g])
                                             for a in query.aggs}}
                           for g in np.flatnonzero(counts)},
                "count": int(counts.sum())}
    return {a: {"sum": sum(p[a][0] for p in parts),
                "count": sum(p[a][1] for p in parts),
                "min": min(p[a][2] for p in parts),
                "max": max(p[a][3] for p in parts)}
            for a in query.aggregates}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def flat_queries():
    from repro.query import And, GroupBy, Pred, Query
    return [
        ("lt fused", Query(Pred("a", "lt", 40), ("b",))),
        ("eq fused", Query(Pred("a", "eq", 17), ("b",))),
        ("ne fused", Query(Pred("b", "ne", 5), ("a",))),
        ("ge fused 16-bit", Query(Pred("c", "ge", 20000), ("c",))),
        ("and mask+agg", Query(And.of(Pred("a", "lt", 64),
                                      Pred("b", "ge", 32)), ("a", "b"))),
        ("groupby dense", GroupBy("a", ("b",), where=Pred("c", "lt",
                                                          16384))),
    ]


def encoded_queries():
    from repro.query import And, GroupBy, Pred, Query
    return [
        ("rle fused", Query(Pred("k", "lt", 40), ("k",))),
        ("for fused", Query(Pred("v", "ge", 15000), ("v",))),
        ("and batched", Query(And.of(Pred("k", "ge", 64),
                                     Pred("v", "lt", 20000)), ("v",))),
        ("groupby rle", GroupBy("k", where=Pred("k", "lt", 100))),
        ("groupby dense", GroupBy("k", ("v",))),
    ]


def serve(eng, label: str, query, want) -> None:
    """Submit + run one query twice through the engine: the first run
    includes its trace and compile, the second is warm. Both must equal
    the oracle."""
    times = []
    for _ in range(2):
        qid = eng.submit(query)
        check(qid is not None, f"{label}: admission rejected the query")
        t0 = time.perf_counter()
        (res,) = eng.run()
        times.append(time.perf_counter() - t0)
        check(not res.degraded, f"{label}: degraded: {res.error}")
        check(res.aggregates == want,
              f"{label}: engine {res.aggregates} != oracle {want}")
    say(f"query {label!r}: first run (trace+compile) {times[0]:.6f} s, "
        f"warm {times[1]:.6f} s, count {res.count}")


def check_launches(engines) -> None:
    """Every kernel family the run launched is a compiled Pallas family
    (mode PALLAS on a TPU resolves to compiled kernels for all of them)
    and no host fallback ran."""
    from repro.kernels import dispatch
    from repro.kernels.dispatch import KernelMode
    seen: dict[str, int] = {}
    for eng in engines:
        check(eng.mode is KernelMode.PALLAS, f"engine mode {eng.mode}")
        r = dispatch.resolve(eng.mode)
        check(r.use_pallas and not r.interpret, f"{eng.mode} -> {r}")
        for fam, n in eng.metrics.launch_counts().items():
            seen[fam] = seen.get(fam, 0) + n
    say(f"launches by family: {dict(sorted(seen.items()))}")
    stray = set(seen) - PALLAS_FAMILIES
    check(not stray, f"launched outside the Pallas families: {stray}")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def shard_table(table, n_chips: int):
    from repro.launch.mesh import make_mesh
    from repro.query import ShardedTable
    t0 = time.perf_counter()
    st = ShardedTable.shard(table, make_mesh((n_chips,), ("data",)))
    for s in st.slices.values():
        s.words.block_until_ready()
        s.valid.block_until_ready()
    dt = time.perf_counter() - t0
    resident = sum(int(s.words.size + s.valid.size) * 4
                   for s in st.slices.values())
    say(f"sharded table: {table.num_rows} rows, {table.nbytes} bytes of "
        f"packed words, {resident} bytes on {n_chips} device(s) with the "
        f"validity planes, placed in {dt:.3f} s")
    for name, s in st.slices.items():
        devs = sorted({str(sh.device) for sh in s.words.addressable_shards})
        say(f"column {name!r} shards on: {devs}")
        check(len(devs) == n_chips,
              f"column {name!r} sits on {len(devs)} device(s), "
              f"expected {n_chips}")
    return st


def merged_partials(partials: list) -> dict:
    out = {}
    for part in partials:
        for col, d in part.items():
            if col not in out:
                out[col] = dict(d)
                continue
            m = out[col]
            m["sum"] += d["sum"]
            m["count"] += d["count"]
            m["min"] = min(m["min"], d["min"])
            m["max"] = max(m["max"], d["max"])
    return out


def run_sharded(n_chips: int, seed: int, devs):
    from repro.query import QueryEngine
    from repro.query.plan import is_grouped
    rows = plan_rows(n_chips, devs)
    t0 = time.perf_counter()
    codes, table = make_table(rows, seed)
    say(f"generated {rows} rows from seed {seed} in "
        f"{time.perf_counter() - t0:.3f} s")
    st = shard_table(table, n_chips)
    eng = QueryEngine(st, mode="pallas")
    for label, q in flat_queries():
        want = oracle(q, codes, SCHEMA)
        serve(eng, label, q, want)
        if n_chips > 1 and not is_grouped(q):
            got = merged_partials(st.execute_partials(
                q.plan(), q.aggregates, mode=eng.mode))
            check(got == want, f"{label}: merged per-shard partials {got} "
                  f"!= oracle {want}")
            say(f"query {label!r}: per-shard partials merged on the host "
                f"equal the psum'd answer")
    say(f"peak_bytes_in_use on {devs[0]}: {peak_bytes(devs[0])}")
    return eng


def run_encoded(seed: int, devs):
    from repro.query import QueryEngine
    t0 = time.perf_counter()
    codes, enc = make_encoded(seed)
    say(f"encoded table: {enc.num_rows} rows in {enc.n_chunks} chunks, "
        f"{enc.nbytes} physical / {enc.logical_nbytes} logical bytes, "
        f"encodings {enc.stats()['encodings']}, built in "
        f"{time.perf_counter() - t0:.3f} s")
    eng = QueryEngine(enc, mode="pallas")
    for label, q in encoded_queries():
        serve(eng, "encoded " + label, q,
              oracle(q, codes, {"k": 8, "v": 16}))
    say(f"peak_bytes_in_use on {devs[0]}: {peak_bytes(devs[0])}")
    return eng


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()       # before the first JAX call
    devs = require_tpu(args.chips)
    say(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s), "
        f"compile cache {cache_dir}")
    engines = [run_sharded(args.chips, args.seed, devs)]
    if args.chips == 1:
        engines.append(run_encoded(args.seed, devs))
    check_launches(engines)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
