"""LIBSVM text ingestion.

TPU-native replacement for the reference's Spark loader
(OptUtils.scala:11-53).  Semantics kept 1:1:

- label token containing ``+`` or parsing to int 1 → +1, anything else → −1
  (OptUtils.scala:35-37; yes, that means "2" silently becomes −1 — documented
  reference quirk #5 in SURVEY.md).
- feature pairs are 1-based ``idx:val`` → 0-based indices
  (OptUtils.scala:40-43).
- ``num_features`` is taken from the caller (the ``--numFeatures`` flag), not
  inferred, matching ``SparseVector(..., numFeats)``.

Instead of an RDD of per-example sparse vectors, the output is a single
columnar CSR triple (row pointers / column indices / values) — the layout
device sharding wants.  A C++ fast path (``native/libsvm_parser.cpp``, loaded
via ctypes) handles large files; the pure-Python path is the fallback and the
semantic oracle.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

# Tokens are delimited by exactly the whitespace set the native parser's
# is_ws() skips (C-locale isspace minus '\n').  NOT str.split(): that also
# splits on Unicode whitespace (NBSP, \x1c-\x1f, \x85) the native scanner
# treats as ordinary junk bytes, which would silently change which pairs a
# line yields depending on which parser ran.
_WS_SPLIT = re.compile(r"[ \t\r\v\f]+")

# Shared numeric grammar, enforced on BOTH parsers: plain ASCII decimal
# (optionally signed, optional fraction/exponent).  Python's int()/float()
# and C's strtol/strtod each accept extras the other rejects (digit-group
# underscores and Unicode digits vs. hex floats, "nan(...)", "inf"); the
# character class below excludes every such form, and within it the two
# accept exactly the same strings, so token validity cannot depend on
# which parser happened to be built.
_INT_CHARS = frozenset("+-0123456789")
_NUM_CHARS = frozenset("+-.eE0123456789")


@dataclasses.dataclass
class LibsvmData:
    """Columnar CSR holding the whole dataset on host.

    ``labels`` ∈ {−1.0, +1.0}; ``indptr`` has n+1 entries; ``indices`` are
    0-based feature ids; ``num_features`` = d.
    """

    labels: np.ndarray     # (n,) float64
    indptr: np.ndarray     # (n+1,) int64
    indices: np.ndarray    # (nnz,) int32
    values: np.ndarray     # (nnz,) float64
    num_features: int
    # a multi-class file read as one (:func:`load_libsvm` ``classes=``):
    # class ids 0..T-1 BESIDE the +-1 ``labels`` (the reference's rule,
    # kept), the count T, and the file's own label value of each id
    # (a MULTI-LABEL file, a row's label a comma-separated set: (n, L) ids,
    # L the largest set, -1 where a set is shorter)
    classes: Optional[np.ndarray] = None   # (n,) or (n, L) int32
    num_classes: int = 1
    class_values: Optional[tuple] = None   # (T,) the labels as the file has them

    @property
    def n(self) -> int:
        return self.labels.shape[0]

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.indptr[i], self.indptr[i + 1]
        return self.indices[lo:hi], self.values[lo:hi]

    # jaxlint: allow=f64 -- host-side densify for tests/oracles; callers
    # pass the compute dtype for device-bound arrays
    def to_dense(self, dtype=np.float64) -> np.ndarray:
        """(n, d) dense matrix — one global scatter, not a per-row Python
        loop (this sits on the oracle path of every dense parity test).
        A duplicate column within a row keeps the LAST occurrence, same
        as the per-row fancy assignment it replaces."""
        out = np.zeros((self.n, self.num_features), dtype=dtype)
        rows = np.repeat(np.arange(self.n), np.diff(self.indptr))
        out[rows, self.indices] = self.values
        return out

    @property
    def max_nnz(self) -> int:
        if self.n == 0:
            return 0
        return int(np.max(np.diff(self.indptr)))


def _parse_label(token: str) -> float:
    """Reference label rule (OptUtils.scala:35-37), restricted to the
    shared decimal grammar (a "0x1" label is −1 on both parsers)."""
    if "+" in token:
        return 1.0
    try:
        if _NUM_CHARS.issuperset(token) and float(token) == 1.0:
            return 1.0
    except ValueError:
        pass
    return -1.0


def _parse_line(line: str):
    """One decoded line → ``(label, idx, val)`` arrays, or None for a
    blank line.  Malformed ``idx:val`` tails (missing ``:``, index or
    value outside the shared decimal grammar, empty value — e.g. a stray
    ``"3: "``) end the pair list for that line; earlier pairs and later
    lines are kept.  The native parser applies the identical rule
    (strtol/strtod longest-prefix parse + whole-token and character-class
    validation), so both paths agree byte-for-byte on such files — pinned
    by the parity cases in
    ``test_native_parser_malformed_whitespace_tails``.  The reference
    simply threw (``"".toDouble``) — crashing on bad input is not
    behavior worth replicating."""
    parts = [t for t in _WS_SPLIT.split(line.rstrip("\n")) if t]
    if not parts:
        return None
    label = _parse_label(parts[0])
    row_idx = np.empty(len(parts) - 1, dtype=np.int32)
    # jaxlint: allow=f64 -- exact text→f64 parse; device arrays cast later
    row_val = np.empty(len(parts) - 1, dtype=np.float64)
    m = 0
    for tok in parts[1:]:
        head, sep, val = tok.partition(":")
        if (not sep or not head or not val
                or not _INT_CHARS.issuperset(head)
                or not _NUM_CHARS.issuperset(val)):
            break
        try:
            i = int(head)
            v = float(val)
        except ValueError:
            break
        # 1-based index must land in int32 after the -1 shift;
        # out-of-range (incl. idx<1) is malformed, same as native —
        # a silent int32 cast there would alias huge indices onto
        # valid features
        if i < 1 or i - 1 > 2**31 - 1:
            break
        row_idx[m] = i - 1  # 1-based → 0-based (OptUtils.scala:42)
        row_val[m] = v
        m += 1
    return label, row_idx[:m], row_val[:m]


def _parse_python_stream(path: str, num_features: int, lo: int, hi):
    """Shared range/whole Python parse: rows whose line START lies in
    [lo, hi) — ``hi=None`` means EOF, and with ``lo == 0`` the file is
    read strictly sequentially (pipes stay supported on the whole-file
    path).  Returns ``(LibsvmData, row_off)`` where ``row_off[i]`` is the
    absolute byte offset of row i's line start.

    Reading is byte-transparent (binary readline + latin-1 decode): every
    byte decodes (a non-UTF-8 byte is junk to reject, not a decode crash
    the native path doesn't have) and a lone ``'\\r'`` stays in-line
    whitespace instead of universal-newlines splitting the row — both
    exactly as the byte-oriented native scanner sees the file.
    """
    labels: list[float] = []
    indptr: list[int] = [0]
    indices: list[np.ndarray] = []
    values: list[np.ndarray] = []
    offsets: list[int] = []
    nnz = 0
    with open(path, "rb") as f:
        pos = 0
        if lo > 0:
            # ownership rule (native resolve_span): a line belongs to the
            # range containing its first byte, so seek to the first line
            # start at or past lo — one past the first '\n' from lo-1
            f.seek(lo - 1)
            pos = None
            probe = lo - 1
            while True:
                chunk = f.read(1 << 20)
                if not chunk:
                    break
                j = chunk.find(b"\n")
                if j >= 0:
                    pos = probe + j + 1
                    break
                probe += len(chunk)
            if pos is None:
                pos = -1  # no line starts at or past lo
            else:
                f.seek(pos)
        while pos >= 0:
            start = pos
            if hi is not None and start >= hi:
                break
            line = f.readline()
            if not line:
                break
            pos = start + len(line)
            row = _parse_line(line.decode("latin-1"))
            if row is None:
                continue
            label, row_idx, row_val = row
            labels.append(label)
            indices.append(row_idx)
            values.append(row_val)
            nnz += len(row_idx)
            indptr.append(nnz)
            offsets.append(start)
    data = LibsvmData(
        # jaxlint: allow=f64 -- exact parse output; cast at device_put
        labels=np.asarray(labels, dtype=np.float64),
        indptr=np.asarray(indptr, dtype=np.int64),
        indices=(
            np.concatenate(indices) if indices else np.empty(0, dtype=np.int32)
        ),
        values=(
            # jaxlint: allow=f64 -- exact parse output; cast at device_put
            np.concatenate(values) if values else np.empty(0, dtype=np.float64)
        ),
        num_features=num_features,
    )
    return data, np.asarray(offsets, dtype=np.int64)


def _load_unlabelled_rows(path: str, num_features: int) -> LibsvmData:
    """A multi-label file's rows (:func:`_label_sets`): a row in no
    label's set opens with its first FEATURE, which the parsers would take
    for the label, so such a line is parsed behind a label of its own.
    Line by line in Python: the file is read whole (``--classes``)."""
    rows = []
    with open(path, "rb") as f:
        for raw in f:
            line = raw.decode("latin-1")
            first = line.split(None, 1)
            if first and ":" in first[0]:
                line = "-1 " + line
            row = _parse_line(line)
            if row is not None:
                rows.append(row)
    indptr = np.zeros(len(rows) + 1, np.int64)
    np.cumsum([len(r[1]) for r in rows], out=indptr[1:])
    cat = lambda i, dt: (np.concatenate([r[i] for r in rows])  # noqa: E731
                         if rows else np.empty(0, dt))
    return _validate(LibsvmData(
        # jaxlint: allow=f64 -- exact parse output; cast at device_put
        labels=np.asarray([r[0] for r in rows], np.float64), indptr=indptr,
        # jaxlint: allow=f64 -- exact parse output; cast at device_put
        indices=cat(1, np.int32), values=cat(2, np.float64),
        num_features=num_features), path)


def load_libsvm_python(path: str, num_features: int) -> LibsvmData:
    """Pure-Python reference parser (semantic oracle for the native one)."""
    return _parse_python_stream(path, num_features, 0, None)[0]


def load_libsvm_python_range(path: str, num_features: int,
                             lo: int, hi: int):
    """Rows owned by the byte range [lo, hi) (ownership rule: a line
    belongs to the range containing its first byte; the last owned line
    parses to ITS end even past ``hi``).  Returns ``(LibsvmData,
    row_off)``.  Ranges that tile the file parse to exactly the
    whole-file result, each row once — pinned byte-for-byte against the
    whole parse by the chunk-boundary parity suite in
    tests/test_libsvm.py."""
    return _parse_python_stream(path, num_features, max(0, lo), hi)


def _validate(data: LibsvmData, path: str) -> LibsvmData:
    if data.indices.size:
        hi = int(data.indices.max())
        if hi >= data.num_features:
            raise ValueError(
                f"{path}: feature index {hi + 1} (1-based) exceeds "
                f"num_features={data.num_features}; pass a larger "
                f"--numFeatures (the reference also requires d up front, "
                f"OptUtils.scala:43)"
            )
        if int(data.indices.min()) < 0:
            raise ValueError(f"{path}: negative feature index after 1→0 shift")
    return data


def _label_sets(path: str) -> tuple:
    """``(values, sizes)`` of a file's label column: every row's labels in
    file order as one flat list of numbers, and how many each row has.  A
    row's label is one number (a multi-class file) or LIBSVM's multi-label
    form, numbers joined by commas (``3,17,204 12:0.5 ...``); a row whose
    line opens with a feature (``12:0.5 ...``, the extreme-classification
    files' row in no label's set) has none."""
    values, sizes = [], []
    with open(path, "rb") as f:
        for raw in f:
            parts = raw.split(None, 1)
            if not parts:
                continue
            token = parts[0].decode("ascii", "replace")
            labels = [] if ":" in token else token.split(",")
            try:
                if not all(t and _NUM_CHARS.issuperset(t) for t in labels):
                    raise ValueError
                values.extend(float(t) for t in labels)
            except ValueError:
                raise ValueError(
                    f"{path}: row {len(sizes) + 1} has the label "
                    f"{token!r}; a multi-class file's labels are numbers "
                    f"(a multi-label row's, numbers joined by commas)"
                ) from None
            sizes.append(len(labels))
    return values, np.asarray(sizes, np.int64)


def read_classes(path: str, expect=None) -> tuple:
    """``(class ids int32, T, the file's label of each id)`` of a
    multi-class LIBSVM file: the first token of every non-blank line, read
    as a number (LIBSVM's multi-class labels are integers: 0..9, 1..T, any
    set of them), the distinct values in ascending order numbered 0..T-1.
    The ids are (n,) where every row has exactly one label, else (n, L)
    label SETS (:func:`_label_sets`), L the largest set and -1 past a
    row's own.  ``expect`` (an int): the count the caller states; a file
    that holds another count is refused with both numbers."""
    values, sizes = _label_sets(path)
    # jaxlint: allow=f64 -- host-side parse of the label column
    found, ids = np.unique(np.asarray(values, np.float64),
                           return_inverse=True)
    if sizes.size and not np.all(sizes == 1):
        sets = np.full((len(sizes), max(1, int(sizes.max()))), -1, np.int64)
        sets[np.arange(sets.shape[1]) < sizes[:, None]] = ids
        ids = sets
    if expect is not None and len(found) != int(expect):
        raise ValueError(
            f"{path}: {int(expect)} classes were stated and the file holds "
            f"{len(found)} distinct labels ({found[:12].tolist()}"
            f"{' ...' if len(found) > 12 else ''}) over {len(sizes)} rows")
    if len(found) < 2:
        raise ValueError(
            f"{path}: a multi-class file needs at least two distinct "
            f"labels, found {found.tolist()}")
    return (ids.astype(np.int32), len(found),
            tuple(int(v) if float(v).is_integer() else float(v)
                  for v in found))


def load_libsvm(path: str, num_features: int, prefer_native: bool = True,
                classes=None) -> LibsvmData:
    """Parse a LIBSVM file; uses the C++ fast path when available.

    ``classes`` (None | ``"auto"`` | an int T): None keeps the reference's
    binary rule alone (a label that parses to 1 is +1, anything else -1).
    Asked for classes, the result ALSO carries integer class ids and the
    count found (:func:`read_classes`; ``labels`` are unchanged), and a
    job over it trains one model per class, one-vs-rest
    (solvers/cocoa.run_cocoa); an int is the count the caller expects."""
    if classes is not None:
        expect = None if str(classes).lower() == "auto" else int(classes)
        ids, count, found = read_classes(path, expect)
        data = (_load_unlabelled_rows(path, num_features) if ids.ndim == 2
                else load_libsvm(path, num_features, prefer_native))
        if len(ids) != data.n:
            raise ValueError(f"{path}: {len(ids)} labelled rows against "
                             f"{data.n} parsed rows")
        return dataclasses.replace(data, classes=ids, num_classes=count,
                                   class_values=found)
    if prefer_native:
        from cocoa_tpu.data import native_loader

        if native_loader.available():
            data = native_loader.parse_file(path, num_features)
            if data is not None:
                return _validate(data, path)
            # None: the path can't be mmap'd (missing or non-regular) —
            # the Python parser owns those cases (clean OSError / pipes)
    return _validate(load_libsvm_python(path, num_features), path)


def load_libsvm_range(path: str, num_features: int, lo: int, hi: int,
                      prefer_native: bool = True):
    """Parse the rows owned by the byte range [lo, hi); C++ fast path when
    available, same fallback contract as :func:`load_libsvm`.  Returns
    ``(LibsvmData, row_off)`` — ``row_off[i]`` the absolute byte offset of
    row i's line start, the per-row index streaming ingest
    (data/ingest.py) uses to map shard row ranges back to byte ranges."""
    if prefer_native:
        from cocoa_tpu.data import native_loader

        if native_loader.available():
            out = native_loader.parse_range(path, lo, hi, num_features)
            if out is not None:
                data, row_off = out
                return _validate(data, path), row_off
    data, row_off = load_libsvm_python_range(path, num_features, lo, hi)
    return _validate(data, path), row_off
