"""Text parsers: CSV / TSV / LibSVM with format sniffing.

Re-design of /root/reference/src/io/parser.cpp:9-145 and parser.hpp:15-109.
Behavioral parity:

- format sniffed from the first two lines by comma/tab/colon counts
  (parser.cpp:94-124),
- label-column presence heuristics for predict-time files
  (parser.cpp:24-62),
- values with ``|v| <= 1e-10`` are treated as zero (parser.hpp:32,62),
- ``na``/``nan``/unparseable tokens parse as 0 (utils/common.h:177-178).

The TPU-first difference: instead of emitting per-line ``(col, val)`` pairs,
parsers return whole dense ``float64 [num_rows, num_cols]`` NumPy matrices —
the downstream dense bin matrix is the device format, so there is no reason
to keep a sparse intermediate.  A native C++ fast path (lightgbm_tpu/native)
accelerates tokenization when built.
"""
from __future__ import annotations

import itertools
import math
import os
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from ..utils import log

_WARNED_NO_PANDAS = False

ZERO_THRESHOLD = 1e-10  # parser.hpp:32


# every casing of na/nan — the NA vocabulary of the reference's data files
# (generated, not hand-enumerated: a missing casing would silently dump
# whole files onto the slow per-token tier).  Both tiers map these to 0
# either way; the list only controls which tier handles them.
_NA_SPELLINGS = sorted(
    {"".join(cs) for w in ("na", "nan")
     for cs in itertools.product(*((c.lower(), c.upper()) for c in w))})


def _atof(token: str) -> float:
    """Locale-free float parse; na/nan/inf and garbage parse as 0
    (common.h Atof treats unparseable as 0)."""
    token = token.strip()
    if not token:
        return 0.0
    try:
        value = float(token)
    except ValueError:
        return 0.0
    if math.isnan(value):
        return 0.0
    return value


def _count_stats(line: str) -> Tuple[int, int, int]:
    """comma/tab/colon counts (parser.cpp:9-22)."""
    return line.count(","), line.count("\t"), line.count(":")


@dataclass
class ParsedData:
    """Dense parse result: the whole file as matrices."""
    # [num_rows, num_raw_features] raw feature values (label column removed,
    # later columns shifted left by one as in parser.hpp's ``bias``)
    features: np.ndarray
    # [num_rows] labels (0.0 when the file has no label column)
    labels: np.ndarray


class Parser:
    """Base parser.  ``label_idx < 0`` means the file has no label column."""

    format_name = "unknown"

    def __init__(self, label_idx: int):
        self.label_idx = label_idx

    def parse(self, lines: List[str]) -> ParsedData:
        raise NotImplementedError

    def parse_one_line(self, line: str) -> Tuple[List[Tuple[int, float]], float]:
        """Single-line parse emitting sparse pairs; used by the predictor
        (mirrors Parser::ParseOneLine)."""
        raise NotImplementedError


class _DelimitedParser(Parser):
    delimiter = ","

    def parse_one_line(self, line: str):
        pairs: List[Tuple[int, float]] = []
        label = 0.0
        bias = 0
        for idx, token in enumerate(line.rstrip("\r\n").split(self.delimiter)):
            value = _atof(token)
            if idx == self.label_idx:
                label = value
                bias = -1
            elif abs(value) > ZERO_THRESHOLD:
                pairs.append((idx + bias, value))
        return pairs, label

    def parse(self, lines: List[str]) -> ParsedData:
        num_rows = len(lines)
        if num_rows == 0:
            return ParsedData(np.zeros((0, 0)), np.zeros((0,), dtype=np.float32))
        # Fast path: uniform column count via np.loadtxt-like parsing.
        matrix = _parse_delimited_fast(lines, self.delimiter)
        labels = np.zeros((num_rows,), dtype=np.float32)
        if 0 <= self.label_idx < matrix.shape[1]:
            labels = matrix[:, self.label_idx].astype(np.float32)
            matrix = np.delete(matrix, self.label_idx, axis=1)
        # zero-dropping parity: tiny values are zeros (parser.hpp:32)
        matrix[np.abs(matrix) <= ZERO_THRESHOLD] = 0.0
        return ParsedData(matrix, labels)


# chunks parsed per tier in THIS process (a harness reads it to say which
# tier a load actually ran on; exec'd ingest workers log theirs)
TIER_CALLS = {"native": 0, "pandas": 0, "exact": 0}


def _parse_delimited_fast(lines: List[str], delimiter: str) -> np.ndarray:
    """Tokenize uniform delimited lines to float64; na/nan → 0.

    Three tiers: the native OpenMP parser (built at first use), a
    vectorized pandas C-engine pass, then the exact-semantics per-token
    loop (which also produces the format-error fatal for ragged input)."""
    native = _try_native()
    if native is not None:
        out = native.parse_delimited(lines, delimiter)
        if out is not None:
            TIER_CALLS["native"] += 1
            return out
    out = _parse_delimited_pandas(lines, delimiter)
    if out is not None:
        TIER_CALLS["pandas"] += 1
        return out
    TIER_CALLS["exact"] += 1
    first_cols = len(lines[0].rstrip("\r\n").split(delimiter))
    out = np.empty((len(lines), first_cols), dtype=np.float64)
    for i, line in enumerate(lines):
        tokens = line.rstrip("\r\n").split(delimiter)
        if len(tokens) != first_cols:
            log.fatal("input format error, should be %s" %
                      ("CSV" if delimiter == "," else "TSV"))
        for j, token in enumerate(tokens):
            out[i, j] = _atof(token)
    return out


class CSVParser(_DelimitedParser):
    format_name = "csv"
    delimiter = ","


class TSVParser(_DelimitedParser):
    format_name = "tsv"
    delimiter = "\t"


class LibSVMParser(Parser):
    format_name = "libsvm"

    def __init__(self, label_idx: int):
        if label_idx > 0:
            log.fatal("label should be the first column in Libsvm file")
        super().__init__(label_idx)

    def parse_one_line(self, line: str):
        tokens = line.split()
        pairs: List[Tuple[int, float]] = []
        label = 0.0
        start = 0
        if self.label_idx == 0 and tokens and ":" not in tokens[0]:
            label = _atof(tokens[0])
            start = 1
        for token in tokens[start:]:
            if ":" not in token:
                log.fatal("input format error, should be LibSVM")
            col, value = token.split(":", 1)
            pairs.append((int(col), _atof(value)))
        return pairs, label

    def parse(self, lines: List[str]) -> ParsedData:
        rows = []
        labels = np.zeros((len(lines),), dtype=np.float32)
        max_col = -1
        for i, line in enumerate(lines):
            pairs, label = self.parse_one_line(line)
            labels[i] = label
            rows.append(pairs)
            for col, _ in pairs:
                max_col = max(max_col, col)
        matrix = np.zeros((len(lines), max_col + 1), dtype=np.float64)
        for i, pairs in enumerate(rows):
            for col, value in pairs:
                if abs(value) > ZERO_THRESHOLD:
                    matrix[i, col] = value
        return ParsedData(matrix, labels)


_native_mod = None
_native_checked = False


def _try_native():
    """Lazy import of the native C++ text parsing extension."""
    global _native_mod, _native_checked
    if not _native_checked:
        _native_checked = True
        try:
            from ..native import lib as native_lib
            _native_mod = native_lib if native_lib.available() else None
        except Exception:
            _native_mod = None
    return _native_mod


def create_parser(filename: str, has_header: bool, num_features: int,
                  label_idx: int) -> Parser:
    """Format sniffing + label presence heuristics (parser.cpp:71-143).

    ``num_features > 0`` activates the predict-time heuristic: if a line has
    exactly ``num_features`` columns the file carries no label column
    (parser.cpp:24-62).
    """
    try:
        f = open(filename, "r")
    except OSError:
        log.fatal("Data file: %s doesn't exist" % filename)
    with f:
        if has_header:
            f.readline()
        line1 = f.readline().rstrip("\r\n")
        if not line1:
            log.fatal("Data file: %s at least should have one line" % filename)
        line2 = f.readline().rstrip("\r\n")
        if not line2:
            log.warning("Data file: %s only have one line" % filename)

    comma1, tab1, colon1 = _count_stats(line1)
    comma2, tab2, colon2 = _count_stats(line2)
    data_type = None
    if len(line2) == 0:
        if colon1 > 0:
            data_type = "libsvm"
        elif tab1 > 0:
            data_type = "tsv"
        elif comma1 > 0:
            data_type = "csv"
    else:
        if colon1 > 0 or colon2 > 0:
            data_type = "libsvm"
        elif tab1 == tab2 and tab1 > 0:
            data_type = "tsv"
        elif comma1 == comma2 and comma1 > 0:
            data_type = "csv"
    if data_type is None:
        log.fatal("Unknown format of training data")

    if data_type == "libsvm":
        label_idx = _label_idx_for_libsvm(line1, num_features, label_idx)
        parser: Parser = LibSVMParser(label_idx)
    elif data_type == "tsv":
        label_idx = _label_idx_for_delimited(line1, "\t", num_features, label_idx)
        parser = TSVParser(label_idx)
    else:
        label_idx = _label_idx_for_delimited(line1, ",", num_features, label_idx)
        parser = CSVParser(label_idx)
    if label_idx < 0:
        log.info("Data file: %s doesn't contain label column" % filename)
    return parser


def _label_idx_for_libsvm(line: str, num_features: int, label_idx: int) -> int:
    """parser.cpp:24-36: no label if the first token already has a colon."""
    if num_features <= 0:
        return label_idx
    line = line.strip()
    pos_space = -1
    for i, ch in enumerate(line):
        if ch.isspace():
            pos_space = i
            break
    pos_colon = line.find(":")
    if pos_space < 0 or (pos_colon >= 0 and pos_space < pos_colon):
        return label_idx
    return -1


def _label_idx_for_delimited(line: str, delimiter: str, num_features: int,
                             label_idx: int) -> int:
    """parser.cpp:38-62: token count == num_features ⇒ no label column."""
    if num_features <= 0:
        return label_idx
    if len(line.strip().split(delimiter)) == num_features:
        return -1
    return label_idx


def _parse_delimited_pandas(lines: List[str], delimiter: str):
    """Vectorized fallback via the pandas C engine (na/nan -> 0 like
    _atof); returns None on any irregularity so the caller's per-token
    loop keeps the exact reference error semantics.

    pandas silently NaN-pads SHORT rows, so field counts are validated
    up front (C-level str.count — cheap next to the parse), and quoting
    is disabled so quoted tokens fall back to the _atof path rather than
    being helpfully unquoted."""
    try:
        import csv
        import io as _io
        import pandas as pd
    except ImportError:
        # reached only when the native tier already bowed out: the load is
        # about to drop to the exact per-token loop (orders of magnitude
        # slower on big text files) — say so once
        global _WARNED_NO_PANDAS
        if not _WARNED_NO_PANDAS:
            _WARNED_NO_PANDAS = True
            log.warning(
                "pandas unavailable: text parsing falls back to the exact "
                "per-token tier (slow); pip install 'lightgbm-tpu[fast-parse]'")
        return None
    n_delim = lines[0].count(delimiter)
    if any(ln.count(delimiter) != n_delim for ln in lines):
        return None   # ragged input -> exact loop -> reference fatal
    try:
        # round_trip: the C engine's default xstrtod is ~1 ulp off
        # Python float() on ~1% of tokens, which would make bin boundaries
        # (and therefore trees) depend on which parser tier is active
        # keep_default_na=False: pandas' default NA vocabulary (NULL, N/A,
        # null, #N/A, ...) is wider than _atof's (na/nan spellings only).
        # Both tiers ultimately produce 0.0 for such tokens (_atof maps
        # all garbage to 0 like the reference's Atof, common.h:177-178),
        # but restricting the fast path's vocabulary keeps the TIERS'
        # routing aligned: tokens _atof considers garbage now fail the C
        # engine's float conversion and take the exact per-token tier,
        # instead of silently short-circuiting through pandas' broader NA
        # rules
        df = pd.read_csv(_io.StringIO("\n".join(lines)), header=None,
                         sep=delimiter, engine="c", dtype=np.float64,
                         quoting=csv.QUOTE_NONE,
                         float_precision="round_trip",
                         keep_default_na=False,
                         na_values=_NA_SPELLINGS)
    except Exception:
        return None
    out = df.to_numpy()
    if out.shape != (len(lines), n_delim + 1):
        return None
    out[np.isnan(out)] = 0.0
    return out


def prefetch_chunks(iterable, depth: int = 2):
    """Overlap file reading with downstream parsing/quantization — the
    reference's PipelineReader (utils/pipeline_reader.h:17-71: a reader
    thread fills 16MB blocks while the parser drains them) as a bounded
    background-thread prefetcher over any chunk iterator."""
    import queue
    import threading

    from .. import lifecycle

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    sentinel = object()
    err: List[BaseException] = []
    stop = threading.Event()

    def put_blocking(item) -> bool:
        """Stop-aware blocking put; False when the consumer went away."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in iterable:
                if not put_blocking(item):
                    return
        except BaseException as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            # the sentinel must use the same stop-aware loop: dropping it
            # on a momentarily-full queue would strand the consumer in
            # q.get() forever (and swallow any stored producer exception)
            put_blocking(sentinel)
            # self-deregistration: if _close's bounded join timed out (a
            # slow chunk parse outliving the 1s grace), the entry must
            # still clear when the thread actually exits — only a thread
            # that never reaches here stays registered for the guard
            lifecycle.untrack(thread)

    thread = threading.Thread(target=worker, name="lgbm-tpu-prefetch",
                              daemon=True)

    def _close() -> None:
        """Stop-and-join closer: shared with the generator's own finally
        and the lifecycle leak guard (a leaked prefetch thread holds the
        underlying file handle open past the test that spawned it)."""
        stop.set()
        thread.join(1.0)
        if not thread.is_alive():
            lifecycle.untrack(thread)

    lifecycle.track("prefetch", thread, _close)
    thread.start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        # consumer stopped early OR drained fully: unblock the worker so
        # it exits (releasing the file handle) and deregister it from
        # the live inventory once it is provably gone
        _close()


def read_lines(filename: str, skip_header: bool = False) -> List[str]:
    """Read all data lines (TextReader::ReadAllLines equivalent,
    utils/text_reader.h:20-308 — pipelined IO replaced by buffered reads).

    Implemented ON TOP of ``read_line_chunks`` so the resident and
    streaming loaders provably parse the SAME row set: the two readers
    used to split and skip headers independently (``str.splitlines``
    additionally breaks rows on \\f/\\v/\\u2028-class boundaries that
    file iteration does not, and it dropped the first SPLIT line as the
    header where the chunk reader consumes the first PHYSICAL line), so
    a file could stream to a different dataset than it loaded resident.
    One implementation, one semantics (tests/test_streaming.py pins
    blank-line/header/exotic-separator cases)."""
    out: List[str] = []
    for chunk in read_line_chunks(filename, skip_header=skip_header):
        out.extend(chunk)
    return out


def count_data_rows(filename: str, skip_header: bool = False) -> int:
    """Count the data rows ``read_line_chunks`` would yield, without
    parsing (streaming pass 0: the pinned-index binning sample needs the
    total row count before any chunk is parsed).  Delegates to the chunk
    reader itself — host memory stays bounded by one chunk of line
    strings, and any future change to its header/blank-line filter keeps
    pass 0 and pass 1/2 counting the same rows."""
    return sum(len(chunk) for chunk in
               read_line_chunks(filename, skip_header=skip_header))


def read_line_chunks(filename: str, skip_header: bool = False,
                     chunk_lines: int = 200_000):
    """Stream data lines in bounded chunks (TextReader's 16MB-block
    pipelined reads, utils/text_reader.h:248-281) — the two-round loading
    path's memory bound."""
    with open(filename, "r") as f:
        if skip_header:
            f.readline()
        buf: List[str] = []
        for line in f:
            line = line.rstrip("\n")
            if line:
                buf.append(line)
                if len(buf) >= chunk_lines:
                    yield buf
                    buf = []
        if buf:
            yield buf


# --------------------------------------------------------- byte ranges
#
# Process-parallel ingest (io/parallel_ingest.py) hands each worker a
# BYTE range of the file instead of a line range, so no two workers ever
# read the same bytes.  Correctness rests on three facts about
# ``read_line_chunks``'s semantics:
#
# - text mode is universal-newline: ``\r\n`` and lone ``\r`` translate
#   to ``\n`` before iteration, so the row boundaries are exactly the
#   bytes {0x0A, 0x0D} — and UTF-8 never embeds either inside a
#   multibyte sequence, so byte-level snapping is encoding-safe;
# - a data row is a maximal run of non-terminator bytes: blank physical
#   lines (any mix of \r/\n) are dropped by the truthiness filter, and a
#   missing final newline still yields the last line;
# - \f/\v/ -class separators are NOT terminators (file iteration
#   does not split on them; tests pin this), and they are non-terminator
#   BYTES here, so they stay inside their run.
#
# Snapping a split point to the next run START therefore never lands
# inside row content, and every terminator byte of a row sits before the
# next run start — ranges partition the data bytes with zero overlap.

_SCAN_BLOCK = 8 * 1024 * 1024


def data_byte_start(filename: str, skip_header: bool = False) -> int:
    """Byte offset of the first data byte — the byte-domain twin of the
    ``f.readline()`` header consume in ``read_line_chunks`` (the header
    is the first PHYSICAL line: up to and including the first ``\\n``,
    ``\\r`` or ``\\r\\n``; a file with no terminator is all header)."""
    if not skip_header:
        return 0
    with open(filename, "rb") as f:
        pos = 0
        pending_cr = False
        while True:
            block = f.read(_SCAN_BLOCK)
            if not block:
                return pos  # no terminator at all -> whole file is header
            if pending_cr:
                # header ended on a \r at the previous block's edge; a
                # \n here belongs to the same \r\n terminator
                return pos + (1 if block[0:1] == b"\n" else 0)
            arr = np.frombuffer(block, dtype=np.uint8)
            hits = np.nonzero((arr == 10) | (arr == 13))[0]
            if hits.size == 0:
                pos += len(block)
                continue
            i = int(hits[0])
            if block[i:i + 1] == b"\n":
                return pos + i + 1
            if i + 1 < len(block):
                return pos + i + 1 + (1 if block[i + 1:i + 2] == b"\n"
                                      else 0)
            pos += len(block)
            pending_cr = True


def split_byte_ranges_at(filename: str, candidates,
                         skip_header: bool = False):
    """Snap candidate byte offsets to data-row starts with ONE raw scan.

    Returns ``(ranges, counts, total_rows)``: byte ranges
    ``[(start, end), ...]`` covering the data region exactly once, the
    data-row count of each range, and their sum — the same count
    ``count_data_rows`` produces, so the split scan doubles as pass 0
    (the file is read twice per load, not three times).  Each candidate
    snaps FORWARD to the next row start (or EOF), so any candidate set —
    mid-line, between the bytes of a ``\\r\\n``, inside the skipped
    header, past EOF — yields ranges whose concatenated rows reproduce
    the serial ``read_line_chunks`` sequence exactly."""
    size = os.path.getsize(filename)
    d0 = data_byte_start(filename, skip_header)
    pending = sorted(min(max(int(c), d0), size) for c in candidates)
    snapped: List[Tuple[int, int]] = []  # (byte offset, rows before it)
    total = 0
    in_run = False
    pos = d0
    with open(filename, "rb") as f:
        f.seek(d0)
        while True:
            block = f.read(_SCAN_BLOCK)
            if not block:
                break
            arr = np.frombuffer(block, dtype=np.uint8)
            m = (arr != 10) & (arr != 13)
            prev = np.empty_like(m)
            prev[0] = in_run
            prev[1:] = m[:-1]
            starts = np.nonzero(m & ~prev)[0]
            while pending and pending[0] < pos + len(block):
                j = int(np.searchsorted(starts, pending[0] - pos))
                if j >= starts.size:
                    break  # snaps in a later block (or to EOF)
                snapped.append((pos + int(starts[j]), total + j))
                pending.pop(0)
            total += int(starts.size)
            in_run = bool(m[-1])
            pos += len(block)
    for _ in pending:
        snapped.append((size, total))
    bounds = [d0] + [b for b, _ in snapped] + [size]
    cum = [0] + [c for _, c in snapped] + [total]
    ranges = list(zip(bounds[:-1], bounds[1:]))
    counts = [cum[i + 1] - cum[i] for i in range(len(ranges))]
    return ranges, counts, total


def split_byte_ranges(filename: str, num_ranges: int,
                      skip_header: bool = False):
    """Split the data region into ``num_ranges`` byte-balanced,
    row-start-snapped ranges (see ``split_byte_ranges_at``)."""
    size = os.path.getsize(filename)
    d0 = data_byte_start(filename, skip_header)
    num_ranges = max(int(num_ranges), 1)
    span = max(size - d0, 0)
    cands = [d0 + (span * i) // num_ranges for i in range(1, num_ranges)]
    return split_byte_ranges_at(filename, cands, skip_header=skip_header)


def read_range_lines(filename: str, start: int, end: int) -> List[str]:
    """The data lines of one snapped byte range — bit-identical to the
    slice of ``read_lines`` the range covers.  The replace chain IS
    universal-newline translation; dropping empty segments IS the
    truthiness filter (a \\r\\n "blank" line becomes one empty segment
    on whichever side of a split it falls — dropped either way)."""
    if end <= start:
        return []
    with open(filename, "rb") as f:
        f.seek(start)
        data = f.read(end - start)
    text = data.decode()
    if "\r" in text:
        text = text.replace("\r\n", "\n").replace("\r", "\n")
    return [ln for ln in text.split("\n") if ln]
