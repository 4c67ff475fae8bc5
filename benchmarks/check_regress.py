"""Bench-trajectory regression gate + trace-diff explainer.

Every benchmark module appends one record per run to `BENCH_*.json`, but
until now nothing *read* the trajectory — a silent 10x throughput loss
would sail through CI as long as the newest record was internally sane
(`check_append.py` checks shape, not level). This script closes the loop:

    python benchmarks/check_regress.py            # every known bench
    python benchmarks/check_regress.py tier store # a subset
    python benchmarks/check_regress.py --explain --out bench_diff.json

For each bench it extracts one *headline* metric (higher is better:
GB/s, SLA attainment, hit rate) from every record, takes the median of
the whole trajectory, and fails (exit 1) if the newest record sits more
than `THRESHOLD` (30%) below that median. A missing trajectory file is
skipped with a note — not every CI job runs every bench — but a present
file must parse and yield the metric.

The gate is also an *explainer*: bench records carry a `rec["obs"]`
digest (repro.obs.diff) — per-(shape, category) critical-path seconds
plus snapshot scalars — so when the gate trips, the failure message
names the dominant regressing span category instead of just the level
drop. `--explain` diffs the newest record against the previous one for
every bench and prints the full attribution (optionally writing a JSON
artifact with `--out`), without gating.

The median (not the max) is the baseline on purpose: trajectories mix
machines and modes, and a one-off fast outlier should not permanently
ratchet the gate; a sustained drop still moves the newest record far
below the median of everything that came before it.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

THRESHOLD = 0.30      # fail when newest < (1 - THRESHOLD) * median


def _resilience_headline(rec: dict) -> float:
    """Best recovered-policy attainment at the worst nonzero fault rate —
    the number BENCH_resilience exists to defend."""
    sweep = rec["sweep"]
    rates = [r for r in sweep if float(r) > 0]
    worst = max(rates, key=float) if rates else max(sweep, key=float)
    per = sweep[worst]
    return max(v["attainment"] for k, v in per.items() if k != "norecover")


HEADLINES = {
    # bench -> (label, extractor); every metric is higher-is-better
    "queries": ("scan_agg_gbps", lambda r: r["scan_agg_gbps"]),
    "tier": ("memcache hit_rate @skew=1.1",
             lambda r: r["policies"]["memcache"]["1.1"]["hit_rate"]),
    "energy": ("capped attainment",
               lambda r: r["replay"]["capped"]["attainment"]),
    "store": ("trace physical_gbps",
              lambda r: r["trace"]["physical_gbps"]),
    "resilience": ("recovered attainment @worst rate",
                   _resilience_headline),
}


def _diff_digests_fn():
    """repro.obs.diff.diff_digests, importable even when this script
    runs without PYTHONPATH=src (the bare CI invocation)."""
    try:
        from repro.obs.diff import diff_digests
    except ImportError:
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.obs.diff import diff_digests
        except ImportError:
            return None
    return diff_digests


def _diff_obs(prev: dict, new: dict):
    """DiffReport between two records' obs digests, or None when either
    side predates the digest (old rows stay loadable) or repro is
    unimportable."""
    if not isinstance(prev.get("obs"), dict) \
            or not isinstance(new.get("obs"), dict):
        return None
    diff_digests = _diff_digests_fn()
    if diff_digests is None:
        return None
    return diff_digests(prev["obs"], new["obs"])


def _load(name: str):
    path = ROOT / f"BENCH_{name}.json"
    if not path.exists():
        return None
    hist = json.loads(path.read_text())
    return hist if isinstance(hist, list) else []


def check_bench(name: str) -> tuple[bool, str]:
    """Returns (ok, message) for one bench trajectory."""
    label, extract = HEADLINES[name]
    hist = _load(name)
    if hist is None:
        return True, f"SKIP (BENCH_{name}.json absent — bench not run here)"
    if not hist:
        return False, f"BENCH_{name}.json holds no records"
    values = [extract(rec) for rec in hist]
    newest = values[-1]
    med = statistics.median(values)
    floor = (1.0 - THRESHOLD) * med
    detail = (f"{label}: newest={newest:.6g} median={med:.6g} "
              f"over {len(values)} record(s), floor={floor:.6g}")
    if med > 0 and newest < floor:
        drop = 1.0 - newest / med
        msg = (f"REGRESSION {detail} — newest is {drop:.0%} below "
               f"the trajectory median (>{THRESHOLD:.0%} gate)")
        # name the culprit: diff the newest digest against the previous
        # record's, and lead with the dominant regressing span category
        rep = _diff_obs(hist[-2], hist[-1]) if len(hist) >= 2 else None
        if rep is not None:
            dom = rep.dominant()
            if dom is not None:
                msg += (f"\n  dominant regressing span category: "
                        f"{dom.key} ({dom.base_s:.6g} -> {dom.new_s:.6g} "
                        f"s/query, {dom.delta_s:+.3g})")
            else:
                msg += ("\n  no span category regressed — the headline "
                        "moved without the modeled ledgers (snapshot "
                        "deltas below)")
            for line in rep.render().splitlines():
                msg += f"\n  | {line}"
        else:
            msg += ("\n  (no obs digest on both records yet — rerun the "
                    "bench twice to enable trace-diff explanations)")
        return False, msg
    return True, f"ok  {detail}"


def explain_bench(name: str) -> tuple[str, dict | None]:
    """Diff the last two records' digests (no gating). Returns
    (message, JSON-safe payload or None)."""
    hist = _load(name)
    if hist is None:
        return f"SKIP (BENCH_{name}.json absent)", None
    if len(hist) < 2:
        return f"SKIP (only {len(hist)} record(s); need 2 to diff)", None
    rep = _diff_obs(hist[-2], hist[-1])
    if rep is None:
        return "SKIP (records predate the obs digest)", None
    dom = rep.dominant()
    payload = {
        "bench": name,
        "exact": rep.exact,
        "dominant": dom.key if dom is not None else None,
        "dominant_delta_s_per_query": dom.delta_s if dom is not None
        else None,
        "delta_total_s_per_query": rep.delta_total_s,
        "rows": [{"key": r.key, "base_s": r.base_s, "new_s": r.new_s,
                  "delta_s": r.delta_s} for r in rep.rows],
        "snapshot_deltas": {k: list(v)
                            for k, v in rep.snapshot_deltas.items()},
    }
    return rep.render(), payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*",
                    help=f"subset of {sorted(HEADLINES)} (default: all)")
    ap.add_argument("--explain", action="store_true",
                    help="diff the last two records per bench instead of "
                         "gating; always exits 0")
    ap.add_argument("--out", default=None,
                    help="with --explain: write the diff payloads as a "
                         "JSON artifact to this path")
    args = ap.parse_args(argv)
    names = args.benches or sorted(HEADLINES)
    unknown = [n for n in names if n not in HEADLINES]
    if unknown:
        raise SystemExit(f"unknown benches {unknown}; known: "
                         f"{sorted(HEADLINES)}")
    if args.explain:
        payloads = []
        for name in names:
            msg, payload = explain_bench(name)
            print(f"BENCH_{name}.json:")
            for line in msg.splitlines():
                print(f"  {line}")
            if payload is not None:
                payloads.append(payload)
        if args.out:
            Path(args.out).write_text(json.dumps(payloads, indent=1))
            print(f"wrote {len(payloads)} diff payload(s) to {args.out}")
        return 0
    failed = False
    for name in names:
        ok, msg = check_bench(name)
        print(f"BENCH_{name}.json: {msg}")
        failed = failed or not ok
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
