"""The control of the check: the reference in float32 put in the
program's place, at a cell's own size.

    python3 -m chipbench.control --workload q1_power --seeds 1 2 3

For each seed it generates the cell's table, computes every template's
answer in exact integers and again with float32 sums, and prints the check's
numbers for the float32 answers as one JSON line. The check holds them to
the same limits as a run; the control has to fail them. It uses the host
only, not the chip, and is never part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import time

from chipbench import reference, run, tpch


def readings(cell_name: str, seed: int) -> dict:
    _, _, config, traffic = run.load_cell(cell_name)
    codes = tpch.generate(config, seed)
    idx = list(range(len(traffic["queries"])))
    control = [reference.answer(traffic["queries"][i], codes,
                                precision="float32") for i in idx]
    numbers = run.check({"template": idx, "answers": control}, traffic,
                        codes)
    return dict(numbers, correct=all(numbers[k] <= run.LIMITS[k]
                                     for k in run.LIMITS))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(args.workload, seed)
        print(json.dumps(dict(out, workload=args.workload, seed=seed,
                              seconds=time.perf_counter() - t)), flush=True)


if __name__ == "__main__":
    main()
