"""Bit-packed in-memory column store (WideTable/BitWeaving-style).

The paper's workload is scans over an in-memory analytic database; this is
that database. Columns hold dictionary-encoded codes bit-packed into int32
words (delimiter MSB per field kept 0 — see kernels/scan_filter), sharded
row-wise across devices for cluster-scale scans.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.kernels.scan_filter import ref as packref


@dataclass
class BitPackedColumn:
    """One column's packed words, kept on the host: the device copy is
    whatever an execution binds (a ShardedTable's slices, or the words a
    flat-table query ships), so a table is never resident twice."""
    name: str
    code_bits: int
    num_rows: int
    words: np.ndarray                  # (n_words,) uint32
    dictionary: np.ndarray | None = None   # code -> value (optional)
    _valid: np.ndarray | None = field(default=None, repr=False,
                                      compare=False)

    @classmethod
    def from_values(cls, name: str, values, code_bits: int,
                    dictionary=None) -> "BitPackedColumn":
        values = np.asarray(values)
        if code_bits not in (2, 4, 8, 16):
            raise ValueError(
                f"column {name!r}: code_bits={code_bits} unsupported; must "
                f"be 2, 4, 8, or 16 (fields divide the 32-bit word, and "
                f"exact aggregation needs payloads < 2^16)")
        vmax = (1 << (code_bits - 1)) - 1
        if values.min(initial=0) < 0:
            raise ValueError(
                f"column {name!r}: min code {int(values.min())} is "
                f"negative; dictionary codes are unsigned indices")
        if values.max(initial=0) > vmax:
            raise ValueError(
                f"column {name!r}: max code {int(values.max())} exceeds "
                f"the {code_bits}-bit payload max {vmax} (the delimiter "
                f"MSB must stay 0); widen code_bits or re-encode the "
                f"dictionary")
        words = packref.pack(values, code_bits)
        return cls(name, code_bits, len(values), words,
                   None if dictionary is None else np.asarray(dictionary))

    @property
    def valid_words(self) -> np.ndarray:
        """Packed delimiter-bit mask set only for real rows: cancels the
        pack()-to-a-word-multiple tail padding during query evaluation
        (cached — reused by every query touching this column)."""
        if self._valid is None:
            self._valid = packref.valid_mask(int(self.words.size),
                                             self.num_rows, self.code_bits)
        return self._valid

    @property
    def codes_per_word(self) -> int:
        return 32 // self.code_bits

    @property
    def nbytes(self) -> int:
        return int(self.words.size) * 4

    def decode(self) -> np.ndarray:
        vals = np.asarray(packref.unpack(self.words, self.code_bits))
        vals = vals[:self.num_rows]
        if self.dictionary is not None:
            return self.dictionary[vals]
        return vals


@dataclass
class Table:
    name: str
    columns: dict[str, BitPackedColumn] = field(default_factory=dict)

    @property
    def num_rows(self) -> int:
        return next(iter(self.columns.values())).num_rows if self.columns else 0

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.columns.values())

    def add(self, col: BitPackedColumn) -> "Table":
        if self.columns and col.num_rows != self.num_rows:
            raise ValueError(
                f"column {col.name!r} has {col.num_rows} rows but table "
                f"{self.name!r} has {self.num_rows}; all columns of a "
                f"table share one row count")
        self.columns[col.name] = col
        return self

    @classmethod
    def synthetic(cls, name: str, num_rows: int, spec: dict[str, int],
                  seed: int = 0) -> "Table":
        """spec: column name -> code_bits; values uniform in payload range."""
        rng = np.random.default_rng(seed)
        t = cls(name)
        for cname, bits in spec.items():
            vmax = (1 << (bits - 1)) - 1
            vals = rng.integers(0, vmax + 1, num_rows)
            t.add(BitPackedColumn.from_values(cname, vals, bits))
        return t
