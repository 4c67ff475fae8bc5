"""Chunk-granular compressed encodings over the bit-packed code planes.

The paper's whole problem is bytes-per-second: a big-memory system can scan
under 10% of its capacity in a second, and die-stacking is the expensive way
to buy more bandwidth. Compression is the cheap way — every byte not moved
is bandwidth *and* fast-tier capacity gained — so this module gives the
columnar store three chunk-granular encodings and a stats-driven selector:

- RLE: sorted / low-cardinality chunks become (value, length) run pairs,
  run arrays padded to a power of two (TPU-friendly static shapes;
  zero-length padding runs are inert). Scans aggregate directly on runs
  through the `scan_compressed` kernel family — a run of length n matching
  a predicate contributes n to the count and n*value to the sum without
  ever materializing rows.
- FOR (frame-of-reference + delta bit-packing): clustered chunks store
  `code - min(chunk)` packed at the narrowest power-of-two field width
  whose payload holds the chunk's span. The packed delta plane is a valid
  BitWeaving plane, so the *existing* scan/aggregate/fused kernels execute
  on compressed words at the narrower width — predicates translate into
  the delta domain (store.exec) and aggregates get an exact host-side base
  fix-up. Effective scan bandwidth multiplies by code_bits/delta_bits.
- PLAIN: today's packed layout, the fallback the selector never loses to.

All run/word metadata is host-side numpy; payloads land as device arrays
in int32/uint32 planes. Layouts follow "Simultaneous Multi Layer Access"
(Lee et al., PAPERS.md): win bandwidth by moving fewer bits per row, not
by exotic formats — everything stays word-aligned and pow2-sized.
"""
from __future__ import annotations

import enum
import math
import zlib
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.kernels.scan_filter import ref as packref

#: Widths the BitWeaving word layout supports (fields divide 32 bits and
#: payloads stay below 2^15 so exact aggregation holds).
WIDTHS = (2, 4, 8, 16)

#: Hard cap on rows per chunk: keeps every per-chunk sum partial
#: (vmax * rows < 2^31) int32-exact in the RLE kernel and bounds run
#: lengths to one int32 plane.
MAX_CHUNK_ROWS = 65536

DEFAULT_CHUNK_ROWS = 4096


class Encoding(str, enum.Enum):
    PLAIN = "plain"
    RLE = "rle"
    FOR = "for"


def width_for_span(span: int) -> int:
    """Narrowest supported field width whose payload (2^(w-1)-1) holds
    `span`."""
    if span < 0:
        raise ValueError(f"span={span} must be non-negative")
    for w in WIDTHS:
        if span <= (1 << (w - 1)) - 1:
            return w
    raise ValueError(f"span={span} exceeds the 16-bit payload max 32767; "
                     f"codes this wide cannot be stored exactly")


def next_pow2(n: int) -> int:
    return 0 if n <= 0 else 1 << (n - 1).bit_length()


def plain_nbytes(n_rows: int, code_bits: int) -> int:
    """Packed bytes of `n_rows` codes at `code_bits` (the logical size a
    chunk streams uncompressed)."""
    cpw = 32 // code_bits
    return 4 * (-(-n_rows // cpw))


@dataclass(frozen=True)
class EncodingStats:
    """Per-chunk statistics the encoding selector decides from."""

    n_rows: int
    n_runs: int
    n_distinct: int
    vmin: int
    vmax: int
    delta_bits: int          # FOR field width for (vmax - vmin)
    plain_nbytes: int
    rle_nbytes: int          # 8 bytes per pow2-padded run (value + length)
    for_nbytes: int          # delta words + 8 bytes (base, width) metadata

    @classmethod
    def from_codes(cls, codes: np.ndarray, code_bits: int) -> "EncodingStats":
        n = len(codes)
        if n == 0:
            return cls(0, 0, 0, 0, 0, WIDTHS[0], 0, 0, 0)
        vmin, vmax = int(codes.min()), int(codes.max())
        n_runs = 1 + int(np.count_nonzero(np.diff(codes)))
        dbits = width_for_span(vmax - vmin)
        return cls(
            n_rows=n, n_runs=n_runs,
            n_distinct=int(len(np.unique(codes))),
            vmin=vmin, vmax=vmax, delta_bits=dbits,
            plain_nbytes=plain_nbytes(n, code_bits),
            rle_nbytes=8 * next_pow2(n_runs),
            for_nbytes=plain_nbytes(n, dbits) + 8,
        )

    def nbytes(self, encoding: Encoding) -> int:
        return {Encoding.PLAIN: self.plain_nbytes,
                Encoding.RLE: self.rle_nbytes,
                Encoding.FOR: self.for_nbytes}[Encoding(encoding)]


def choose_encoding(stats: EncodingStats) -> Encoding:
    """Smallest physical footprint wins; PLAIN wins ties, so a chosen
    encoding is never larger than today's format."""
    best = Encoding.PLAIN
    for cand in (Encoding.RLE, Encoding.FOR):
        if stats.nbytes(cand) < stats.nbytes(best):
            best = cand
    return best


@dataclass
class EncodedChunk:
    """One row-range of one column in its chosen physical layout.

    PLAIN/FOR hold a packed word plane at `width` (== code_bits for PLAIN,
    the delta width for FOR) plus the matching packed validity mask; the
    codes it stores are `base + packed_field`. RLE holds pow2-padded
    (values, lengths) int32 planes (zero-length runs are padding) plus a
    validity mask at the *logical* width for the decoded fallback path.
    """

    encoding: Encoding
    n_rows: int
    code_bits: int                      # logical width of decoded codes
    stats: EncodingStats
    width: int = 0                      # payload field width (PLAIN/FOR)
    base: int = 0                       # frame of reference (FOR)
    words: jnp.ndarray | None = None    # packed payload (PLAIN/FOR)
    values: jnp.ndarray | None = None   # (n_runs_padded,) int32 (RLE)
    lengths: jnp.ndarray | None = None  # (n_runs_padded,) int32 (RLE)
    n_runs: int = 0
    valid: jnp.ndarray | None = field(default=None, repr=False)
    checksum: int = 0                   # crc32 over payload + layout meta

    @property
    def nbytes(self) -> int:
        """Physical bytes a scan streams for this chunk (a zero-row
        chunk streams nothing, metadata included)."""
        if self.encoding is Encoding.RLE:
            return 4 * (int(self.values.size) + int(self.lengths.size))
        n = 4 * int(self.words.size)
        return n + 8 if self.encoding is Encoding.FOR and n else n

    @property
    def logical_nbytes(self) -> int:
        return plain_nbytes(self.n_rows, self.code_bits)

    # --- integrity --------------------------------------------------------
    def payload_checksum(self) -> int:
        """crc32 over the payload planes plus the layout metadata that
        interprets them — a flipped bit anywhere a scan would read
        changes this, so corruption is *detected* on read, never
        silently aggregated (repro.resilience.ChunkGuard)."""
        crc = zlib.crc32(
            f"{self.encoding.value}|{self.n_rows}|{self.code_bits}|"
            f"{self.width}|{self.base}|{self.n_runs}".encode())
        for plane in (self.words, self.values, self.lengths):
            if plane is not None:
                crc = zlib.crc32(np.asarray(plane).tobytes(), crc)
        return crc

    def seal(self) -> "EncodedChunk":
        """Stamp the checksum of the current payload (encode time, or
        after an authorized repair re-encode)."""
        self.checksum = self.payload_checksum()
        return self

    def verify(self) -> bool:
        """Does the stored payload still match its sealed checksum?"""
        return self.payload_checksum() == self.checksum

    def decode(self) -> np.ndarray:
        """Exact logical codes back out of the physical layout."""
        if self.n_rows == 0:
            return np.zeros(0, np.uint32)
        if self.encoding is Encoding.RLE:
            lens = np.asarray(self.lengths)[:self.n_runs]
            return np.repeat(np.asarray(self.values, np.uint32)
                             [:self.n_runs], lens)
        vals = np.asarray(packref.unpack(self.words, self.width),
                          np.uint32)[:self.n_rows]
        return vals + np.uint32(self.base)


def encode_chunk(codes, code_bits: int,
                 encoding: Encoding | None = None) -> EncodedChunk:
    """Encode one chunk of dictionary codes; `encoding=None` lets the
    stats selector pick. Round-trips exactly (chunk.decode() == codes)."""
    codes = np.asarray(codes, np.uint32)
    n = len(codes)
    if n > MAX_CHUNK_ROWS:
        raise ValueError(
            f"chunk of {n} rows exceeds MAX_CHUNK_ROWS={MAX_CHUNK_ROWS} "
            f"(the bound that keeps per-chunk sum partials int32-exact); "
            f"re-chunk the column")
    vmax = (1 << (code_bits - 1)) - 1
    if n and int(codes.max()) > vmax:
        raise ValueError(
            f"codes exceed the {code_bits}-bit payload max {vmax}; encode "
            f"after db.columnar validation, not before")
    stats = EncodingStats.from_codes(codes, code_bits)
    enc = Encoding(encoding) if encoding is not None \
        else choose_encoding(stats)
    if enc is Encoding.RLE:
        if n == 0:
            values = lengths = np.zeros(0, np.int32)
            n_runs = 0
        else:
            starts = np.r_[0, np.flatnonzero(np.diff(codes)) + 1]
            lengths = np.diff(np.r_[starts, n]).astype(np.int32)
            values = codes[starts].astype(np.int32)
            n_runs = len(starts)
            pad = next_pow2(n_runs) - n_runs
            values = np.pad(values, (0, pad))
            lengths = np.pad(lengths, (0, pad))
        return EncodedChunk(
            enc, n, code_bits, stats, n_runs=n_runs,
            values=jnp.asarray(values), lengths=jnp.asarray(lengths),
            valid=jnp.asarray(packref.valid_mask(
                plain_nbytes(n, code_bits) // 4, n, code_bits))).seal()
    if enc is Encoding.FOR:
        base, width = stats.vmin, stats.delta_bits
        payload = codes - np.uint32(base)
    else:
        base, width = 0, code_bits
        payload = codes
    words = packref.pack(payload, width)
    valid = packref.valid_mask(len(words), n, width)
    return EncodedChunk(enc, n, code_bits, stats, width=width, base=base,
                        words=jnp.asarray(words),
                        valid=jnp.asarray(valid)).seal()


@dataclass
class EncodedColumn:
    """A column as a sequence of independently-encoded row chunks.

    Duck-types the metadata surface the query/tier layers need from
    `db.columnar.BitPackedColumn`: `code_bits`, `num_rows`, `nbytes`
    (physical, compressed — what a scan actually streams) plus the new
    `logical_nbytes` (what the plain format would stream).
    """

    name: str
    code_bits: int
    num_rows: int
    chunk_rows: int
    chunks: list[EncodedChunk]
    dictionary: np.ndarray | None = None

    @classmethod
    def from_values(cls, name: str, values, code_bits: int,
                    chunk_rows: int = DEFAULT_CHUNK_ROWS,
                    encoding: Encoding | None = None,
                    dictionary=None) -> "EncodedColumn":
        values = np.asarray(values, np.uint32)
        if not 1 <= chunk_rows <= MAX_CHUNK_ROWS:
            raise ValueError(
                f"column {name!r}: chunk_rows={chunk_rows} outside "
                f"[1, {MAX_CHUNK_ROWS}]")
        chunks = [encode_chunk(values[i:i + chunk_rows], code_bits,
                               encoding)
                  for i in range(0, len(values), chunk_rows)]
        return cls(name, code_bits, len(values), chunk_rows, chunks,
                   None if dictionary is None else np.asarray(dictionary))

    @classmethod
    def from_column(cls, col, chunk_rows: int = DEFAULT_CHUNK_ROWS,
                    encoding: Encoding | None = None) -> "EncodedColumn":
        """Encode an existing BitPackedColumn (exact logical codes)."""
        codes = np.asarray(packref.unpack(col.words, col.code_bits),
                           np.uint32)[:col.num_rows]
        return cls.from_values(col.name, codes, col.code_bits, chunk_rows,
                               encoding, dictionary=col.dictionary)

    @property
    def nbytes(self) -> int:
        """Physical (compressed) bytes — the scan-traffic numerator."""
        return sum(c.nbytes for c in self.chunks)

    @property
    def logical_nbytes(self) -> int:
        return sum(c.logical_nbytes for c in self.chunks)

    @property
    def ratio(self) -> float:
        return self.logical_nbytes / self.nbytes if self.nbytes else 1.0

    def chunk_physical_bytes(self, chunk_rows: int) -> list[int]:
        """Physical bytes per placement chunk (the tier engine's unit).
        `chunk_rows` must be a multiple of the store's chunking so
        placement chunks aggregate whole encoded chunks."""
        if chunk_rows % self.chunk_rows:
            raise ValueError(
                f"column {self.name!r}: placement chunk_rows={chunk_rows} "
                f"is not a multiple of the store's chunk_rows="
                f"{self.chunk_rows}; build the PlacementEngine with the "
                f"store's chunking (or a multiple of it)")
        k = chunk_rows // self.chunk_rows
        return [sum(c.nbytes for c in self.chunks[i:i + k])
                for i in range(0, len(self.chunks), k)]

    def decode(self) -> np.ndarray:
        """Exact logical codes (dictionary not applied — parity with
        BitPackedColumn requires `dictionary[decode()]`)."""
        if not self.chunks:
            return np.zeros(0, np.uint32)
        return np.concatenate([c.decode() for c in self.chunks])

    def encodings(self) -> dict[str, int]:
        out = {e.value: 0 for e in Encoding}
        for c in self.chunks:
            out[c.encoding.value] += 1
        return out


@dataclass
class EncodedTable:
    """A compressed columnar table the QueryEngine executes directly.

    Duck-types `db.columnar.Table` where the engine reads metadata
    (`columns`, `num_rows`, `nbytes`); `nbytes` is *physical* so byte
    accounting (admission, tier service, energy) charges what actually
    crosses the memory bus, with `logical_nbytes` preserved beside it.
    """

    name: str
    chunk_rows: int
    columns: dict[str, EncodedColumn] = field(default_factory=dict)

    @classmethod
    def from_table(cls, table, chunk_rows: int = DEFAULT_CHUNK_ROWS,
                   encodings: dict[str, Encoding] | None = None
                   ) -> "EncodedTable":
        """Encode a db.Table chunk-by-chunk. `chunk_rows` is aligned so a
        chunk boundary is a word boundary for every column's *logical*
        width (the invariant tier placement and shard splitting already
        share); `encodings` pins named columns, others use the selector."""
        if not table.columns:
            return cls(table.name, max(1, chunk_rows))
        align = math.lcm(*(32 // c.code_bits
                           for c in table.columns.values()))
        chunk_rows = -(-max(1, chunk_rows) // align) * align
        if chunk_rows > MAX_CHUNK_ROWS:
            raise ValueError(
                f"chunk_rows={chunk_rows} exceeds MAX_CHUNK_ROWS="
                f"{MAX_CHUNK_ROWS} after width alignment")
        forced = dict(encodings or {})
        unknown = set(forced) - set(table.columns)
        if unknown:
            raise ValueError(f"encodings pin unknown column(s) "
                             f"{sorted(unknown)}; table has "
                             f"{sorted(table.columns)}")
        t = cls(table.name, chunk_rows)
        for name, col in table.columns.items():
            t.columns[name] = EncodedColumn.from_column(
                col, chunk_rows, forced.get(name))
        return t

    @property
    def num_rows(self) -> int:
        return (next(iter(self.columns.values())).num_rows
                if self.columns else 0)

    @property
    def n_chunks(self) -> int:
        return (len(next(iter(self.columns.values())).chunks)
                if self.columns else 0)

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.columns.values())

    @property
    def logical_nbytes(self) -> int:
        return sum(c.logical_nbytes for c in self.columns.values())

    @property
    def ratio(self) -> float:
        return self.logical_nbytes / self.nbytes if self.nbytes else 1.0

    def decode_table(self):
        """The exact plain-format table (the parity oracle's input)."""
        from repro.db.columnar import BitPackedColumn, Table
        t = Table(self.name)
        for name, col in self.columns.items():
            t.add(BitPackedColumn.from_values(
                name, col.decode(), col.code_bits,
                dictionary=col.dictionary))
        return t

    def stats(self) -> dict:
        return {
            "chunk_rows": self.chunk_rows,
            "physical_bytes": self.nbytes,
            "logical_bytes": self.logical_nbytes,
            "ratio": round(self.ratio, 4),
            "encodings": {n: c.encodings()
                          for n, c in self.columns.items()},
        }
