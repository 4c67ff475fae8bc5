"""TPC-H `lineitem` generator: the columns a configuration holds, as codes.

Follows the LINEITEM rules of the TPC-H specification (clause 4.2.3), with
every range read from the configuration file:

- orders get 1-7 lines (uniform) and an order date uniform over the
  order-date range; all lines of an order share its date, and rows stay in
  order-key order, so `l_shipdate` is not sorted;
- `l_shipdate` = order date + U[1, 121] days, `l_receiptdate` = ship date
  + U[1, 30] days (used for the return flag, not held);
- `l_quantity` U[1, 50], `l_discount` U{0..10} hundredths, `l_tax` U{0..8}
  hundredths, held as those integers;
- `l_linestatus` is O when the ship date is after the current date, else F;
  `l_returnflag` is R or A at random when the receipt date is on or before
  the current date, else N. The pair is held as one dictionary code
  `l_rfls` (dictionary order AF, NF, NO, RF: Q1's group order).

Dates are day numbers since the configuration's `date_epoch`. The row
count is exactly the configuration's `rows`, whatever the seed: the order
that holds the last row is cut there. Every array is made slab by slab on a
thread pool, each slab from its own generator keyed by (seed, stream, slab),
so a seed gives the same table on any machine.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLAB_ROWS = 1 << 24
RFLS = ("AF", "NF", "NO", "RF")


def slab_map(fn, n_rows: int, slab: int = SLAB_ROWS) -> list:
    """fn(index, lo, hi) over the slabs of [0, n_rows) on a thread pool
    (numpy releases the GIL in these loops); results in slab order."""
    starts = range(0, n_rows, slab)
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        return list(pool.map(
            lambda i: fn(i, starts[i], min(n_rows, starts[i] + slab)),
            range(len(starts))))


def _uniform(rng, lo_hi, n, dtype):
    lo, hi = lo_hi
    return rng.integers(lo, hi + 1, n, dtype=dtype)


def lines_per_order(config: dict, seed: int) -> np.ndarray:
    """Lines of each order, for just enough orders to hold `rows` lines
    (the last order cut to fit)."""
    gen, rows = config["generation"], config["rows"]
    lo, hi = gen["lines_per_order"]
    mean, var = (lo + hi) / 2, ((hi - lo + 1) ** 2 - 1) / 12
    # orders drawn: the mean count plus a margin of 20 standard deviations
    n = int(rows / mean + 20 * math.sqrt(rows * var) / mean) + 16
    lines = _uniform(np.random.default_rng((seed, 0)), (lo, hi), n,
                     np.uint8)
    ends = np.cumsum(lines, dtype=np.int64)
    if ends[-1] < rows:
        raise ValueError(f"{n} orders hold {ends[-1]} < {rows} lines")
    last = int(np.searchsorted(ends, rows))     # order holding the last row
    lines = lines[:last + 1].copy()
    lines[last] -= np.uint8(ends[last] - rows)
    return lines


def generate(config: dict, seed: int) -> dict[str, np.ndarray]:
    """{column: codes} for every column the configuration holds, uint8
    for 8-bit codes and uint16 for 16-bit ones. Every column is drawn
    whether held or not, so a column reads the same in every
    configuration of one seed."""
    gen, rows = config["generation"], config["rows"]
    lines = lines_per_order(config, seed)
    ends = np.cumsum(lines, dtype=np.int64)
    odate = _uniform(np.random.default_rng((seed, 1)),
                     gen["orderdate_days"], lines.size, np.uint16)
    cur = np.uint16(gen["current_date_day"])
    out = {name: np.empty(rows, np.uint8 if bits <= 8 else np.uint16)
           for name, bits in config["columns"].items()}

    def fill(i, lo, hi):
        n = hi - lo
        rng = np.random.default_rng((seed, 2, i))
        o0 = int(np.searchsorted(ends, lo, side="right"))
        o1 = int(np.searchsorted(ends, hi - 1, side="right")) + 1
        first = int(ends[o0] - lines[o0])           # first row of order o0
        od = np.repeat(odate[o0:o1], lines[o0:o1])[lo - first:hi - first]
        ship = od + _uniform(rng, gen["ship_after_order_days"], n,
                             np.uint16)
        receipt = ship + _uniform(rng, gen["receipt_after_ship_days"], n,
                                  np.uint16)
        made = {"l_shipdate": ship}
        for c in ("l_quantity", "l_discount", "l_tax"):
            made[c] = _uniform(rng, gen[c], n, np.uint8)
        coin = rng.integers(0, 2, n, dtype=np.uint8)    # R (1) or A (0)
        if "l_rfls" in out:
            made["l_rfls"] = np.where(
                receipt <= cur, coin * np.uint8(RFLS.index("RF")),
                np.where(ship > cur, np.uint8(RFLS.index("NO")),
                         np.uint8(RFLS.index("NF"))))
        for c, dst in out.items():
            dst[lo:hi] = made[c]

    slab_map(fill, rows)
    return out


def needed_bytes(columns: dict[str, int], rows: int, names) -> int:
    """Bytes a query has to read: the packed words of each column it
    references, at `32 // bits` codes per 32-bit word. No validity planes
    (lineitem has no NULLs) and no shard padding: the same count whatever
    implements the scan."""
    return sum(4 * -(-rows // (32 // columns[n])) for n in set(names))
