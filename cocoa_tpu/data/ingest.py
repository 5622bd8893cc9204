"""Streaming sharded ingest: parse only your shards, build in place.

The reference's Spark loader reads only the HDFS blocks local to each
executor (OptUtils.scala:11-53).  The whole-file path here
(``load_libsvm`` → ``shard_dataset``) instead parses the ENTIRE LIBSVM
text in every process and only then slices out the local shards — P
redundant full parses, and a full-dataset host-side CSR per process.
This module is the data-local ingest CoCoA+'s design assumes (Ma et al.,
arXiv:1502.03508: each worker only ever touches its own partition), as a
two-pass byte-range pipeline (docs/DESIGN.md §12):

- **pass 1 — index scan.**  Each process scans its 1/P byte range of the
  file in bounded windows (range-parse, keep the stats, drop the rows):
  per-row byte offsets + nnz, and a partial column histogram.  The
  partials are all-gathered over the jax.distributed KV store
  (parallel/distributed.host_allgather_bytes — host data, no device
  round-trip) and summed: integer totals, so the assembled histogram is
  bit-identical to a whole-file ``np.bincount`` and ``--hotCols=auto``
  resolves to exactly the single-process width
  (hybrid.resolve_hot_width).
- **pass 2 — shard parse.**  The global row-offset index maps each
  shard's rows to an EXACT byte range; each process parses only its own
  local shards' ranges (native or Python range parser,
  data/libsvm.load_libsvm_range) — SHARD-GRANULAR, fanned out over an
  intra-process thread pool when the native parser is available (its
  ctypes entry points release the GIL; the pure-Python parser keeps the
  sequential loop) — and builds the padded slabs straight into the
  target layout through the same ``sharding._build_shard_slabs`` the
  whole-file paths use, so the shards are bit-identical by construction.
  The full dataset CSR is never materialized host-side: peak host RSS
  is ~1/P of the dataset plus the index.

**The persistent slab cache** (``--ingestCache=DIR``,
data/slab_cache.py, docs/DESIGN.md §18) makes the SECOND touch free:
pass 1 warm-loads the cached index (zero scan), pass 2 ``np.load``\\ s
each shard's device-ready slabs from memmap-able artifacts (zero parse,
zero slab build) and parses only cache misses; cold builds populate the
cache shard by shard (atomic rename, one writer wins).  Because the
artifacts are keyed by SHARD (0..K−1), not process geometry, an elastic
shrink's survivors re-map their inherited shards warm.  Every
conditional cache shortcut is VOTED across the gang first
(:func:`_all_agree`) — per-host cache state may differ, and a process
skipping an exchange its peers entered would wedge the gang.

The hybrid residual width (global max COLD nnz per row) needs the hot
set, which needs the global histogram — so it is measured on the held
pass-2 pieces and max-reduced across processes (exact integer max, equal
to the whole-file ``bincount(...).max()``), then cached as the hybrid
layout meta so warm runs skip the measurement parse entirely.

The single-process replicated builder (``shard_dataset``) stays bit-exact
as the A/B control; ``stream_shard_dataset`` with one process produces
the identical ``ShardedDataset`` (pinned by tests/test_ingest.py).

This pipeline is also the elastic supervisor's RESHARDING entry
(cocoa_tpu/elastic.py shrink-to-survivors, docs/DESIGN.md §13): after a
gang reforms at P′ < P, each survivor's relaunch lands here with the new
process count and materializes exactly its newly inherited m = K/D′
shards — warm from the cache when ``--ingestCache`` rode the worker
line, since the shard keys ignore the gang geometry.  Every
cross-process exchange below rides the bounded, retrying KV ops
(distributed.blocking_kv_get): a peer that died between the supervisor's
relaunch and this exchange fails the build in bounded time with the
peer named, which the supervisor observes as a worker death and handles.
"""

from __future__ import annotations

import dataclasses
import io
import itertools
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data import hybrid as hybrid_lib
from cocoa_tpu.data import sharding as sharding_lib
from cocoa_tpu.data.libsvm import load_libsvm_range
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.parallel import distributed
from cocoa_tpu.parallel import mesh as mesh_lib
from cocoa_tpu.telemetry import tracing as _tracing

# pass-1 window: bounds the transient CSR a scan holds (rows are parsed
# and dropped per window; only offsets/nnz/histogram survive)
PASS1_WINDOW = 64 << 20

# SPMD-deterministic exchange tags: every process runs the same ingest
# calls in the same order, so a per-process counter yields matching tags
_EXCHANGE_SEQ = itertools.count()


def peak_rss_bytes() -> int:
    """This process's lifetime peak resident set (ru_maxrss is kB on
    Linux) — the ingest telemetry's memory fact.  ``resource`` is
    Unix-only; report 0 where it is absent rather than breaking the
    package import (this module loads with ``cocoa_tpu.data``)."""
    try:
        import resource
    except ImportError:
        return 0
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


@dataclasses.dataclass
class IngestIndex:
    """The pass-1 artifact: the global row index + column histogram.

    ``row_off`` has n+1 entries — ``row_off[i]`` is the byte offset of
    row i's line start, ``row_off[n]`` the file size — so rows [a, b)
    occupy exactly bytes [row_off[a], row_off[b]).
    """

    path: str
    file_bytes: int
    num_features: int
    row_off: np.ndarray      # (n+1,) int64
    row_nnz: np.ndarray      # (n,) int64
    hist: np.ndarray         # (d,) int64 global column histogram
    scan_bytes: int          # bytes THIS process scanned in pass 1
    scan_seconds: float

    @property
    def n(self) -> int:
        return len(self.row_nnz)

    @property
    def total_nnz(self) -> int:
        return int(self.row_nnz.sum())


def _pack_arrays(**arrays) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _unpack_arrays(payload: bytes) -> dict:
    with np.load(io.BytesIO(payload)) as z:
        return {k: z[k] for k in z.files}


def _exchange_max(value: int) -> int:
    """Exact integer max across processes (identity single-process)."""
    tag = f"ingest{next(_EXCHANGE_SEQ)}"
    payloads = distributed.host_allgather_bytes(
        tag, _pack_arrays(v=np.asarray([value], np.int64)))
    return int(max(int(_unpack_arrays(p)["v"][0]) for p in payloads))


def _all_agree(flag: bool) -> bool:
    """Exact all-processes AND (identity single-process).  Cache state
    is per-host: one worker may hold a warm artifact its peers lack, and
    a process that skipped an exchange its peers entered would wedge the
    gang — so every conditional cache shortcut votes first with one tiny
    allgather, and the gang takes the shortcut only unanimously."""
    if jax.process_count() <= 1:
        return flag
    tag = f"ingest{next(_EXCHANGE_SEQ)}"
    payloads = distributed.host_allgather_bytes(
        tag, _pack_arrays(v=np.asarray([1 if flag else 0], np.int64)))
    return all(int(_unpack_arrays(p)["v"][0]) for p in payloads)


def _cache_handle(cache, path: str, num_features: int):
    """Bind the slab cache to the file, or None (a vanished file fails
    the subsequent parse with its own clean error)."""
    if cache is None:
        return None
    try:
        return cache.for_file(path, num_features)
    except OSError:
        return None


def build_index(path: str, num_features: int, *,
                window: int = PASS1_WINDOW, cache=None) -> IngestIndex:
    """Pass 1: scan this process's 1/P byte range, exchange, assemble.

    Every process returns the same global index (offsets concatenated in
    process order — ranges tile the file, so the concatenation IS the
    whole-file row order; histogram summed as int64, bit-identical to the
    whole-file ``np.bincount``).

    With ``cache`` (a :class:`cocoa_tpu.data.slab_cache.SlabCache`), a
    previously stored FULL index for this exact file identity returns
    without reading a byte (``scan_bytes=0``) — unanimously voted across
    the gang — and a cold scan stores its index for the next process.
    """
    with _tracing.span("ingest_pass1", path=path):
        handle = _cache_handle(cache, path, num_features)
        if cache is not None:
            stats = handle.load_index() if handle is not None else None
            have = stats is not None and stats.has_rows
            if not _all_agree(have):
                stats = None
            if stats is not None and stats.has_rows:
                return IngestIndex(
                    path=path, file_bytes=stats.file_bytes,
                    num_features=num_features,
                    row_off=np.asarray(stats.row_off, np.int64),
                    row_nnz=np.asarray(stats.row_nnz, np.int64),
                    hist=np.asarray(stats.hist, np.int64),
                    scan_bytes=0, scan_seconds=0.0,
                )
        index = _build_index(path, num_features, window=window)
        if handle is not None:
            handle.store_index(
                hist=index.hist, n=index.n, total_nnz=index.total_nnz,
                max_row_nnz=int(index.row_nnz.max(initial=0)),
                row_off=index.row_off, row_nnz=index.row_nnz)
        return index


def _build_index(path: str, num_features: int, *,
                 window: int = PASS1_WINDOW) -> IngestIndex:
    size = os.path.getsize(path)
    nproc = jax.process_count()
    me = jax.process_index()
    lo = me * size // nproc
    hi = (me + 1) * size // nproc
    t0 = time.perf_counter()
    offs: list = []
    nnzs: list = []
    hist = np.zeros(num_features, np.int64)
    w = lo
    while w < hi:
        wl, wh = w, min(w + window, hi)
        piece, off = load_libsvm_range(path, num_features, wl, wh)
        hist += np.bincount(piece.indices, minlength=num_features)
        nnzs.append(np.diff(piece.indptr))
        offs.append(off)
        w = wh
    my_off = (np.concatenate(offs) if offs
              else np.empty(0, np.int64)).astype(np.int64)
    my_nnz = (np.concatenate(nnzs) if nnzs
              else np.empty(0, np.int64)).astype(np.int64)
    scan_seconds = time.perf_counter() - t0

    if nproc > 1:
        tag = f"ingest{next(_EXCHANGE_SEQ)}"
        payloads = distributed.host_allgather_bytes(
            tag, _pack_arrays(off=my_off, nnz=my_nnz, hist=hist))
        parts = [_unpack_arrays(p) for p in payloads]
        row_off = np.concatenate([p["off"] for p in parts])
        row_nnz = np.concatenate([p["nnz"] for p in parts])
        hist = np.sum([p["hist"] for p in parts], axis=0,
                      dtype=np.int64)
        scan_seconds = time.perf_counter() - t0
    else:
        row_off, row_nnz = my_off, my_nnz

    return IngestIndex(
        path=path,
        file_bytes=size,
        num_features=num_features,
        row_off=np.append(row_off, np.int64(size)),
        row_nnz=row_nnz,
        hist=hist,
        scan_bytes=hi - lo,
        scan_seconds=scan_seconds,
    )


def _pass2_workers(n_tasks: int) -> int:
    """Thread-pool width for the pass-2 shard parses: the native
    parser's byte-range entry points run per shard and release the GIL
    inside the ctypes call, so they are embarrassingly parallel; the
    pure-Python parser holds the GIL and keeps the sequential loop."""
    if n_tasks <= 1:
        return 1
    from cocoa_tpu.data import native_loader

    if not native_loader.available():
        return 1
    return max(1, min(n_tasks, os.cpu_count() or 1))


def _parse_waves(shards, parse_fn):
    """Yield ``(s, parse_fn(s))`` for every shard id, parsing in
    bounded parallel waves: at most one thread-pool width of pieces is
    in flight, so the peak transient CSR stays ~workers/K of the
    dataset instead of all local pieces at once.  Results are yielded
    in shard order — assembly is keyed by shard id, so the parallelism
    cannot perturb a single output byte."""
    shards = list(shards)
    workers = _pass2_workers(len(shards))
    if workers <= 1:
        for s in shards:
            yield s, parse_fn(s)
        return
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as ex:
        for i in range(0, len(shards), workers):
            chunk = shards[i:i + workers]
            for s, res in zip(chunk, ex.map(parse_fn, chunk)):
                yield s, res


@dataclasses.dataclass
class StreamBuildInfo:
    """Pass-2 facts of one streamed build (this process's share)."""

    rows: int                # rows parsed by THIS process in pass 2
    nnz: int
    bytes_read: int          # pass-2 bytes parsed by this process
    parse_seconds: float     # pass-2 wall time (parse + slab build)
    residual_max_nnz: int    # global max cold nnz (0 unless hybrid)
    shards_cached: int = 0   # local shards served from --ingestCache
    shards_total: int = 0    # local shards this process materialized
    cache_bytes_mapped: int = 0
    cache_status: str = "off"   # off | hit | partial | miss
    seconds_saved: float = 0.0  # the cached cold cost, on a full hit


def stream_shard_dataset(
    path: str,
    num_features: int,
    k: int,
    *,
    layout: str = "auto",
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
    max_nnz: Optional[int] = None,
    eval_dense: bool = False,
    hot_cols: int = 0,
    index: Optional[IngestIndex] = None,
    cache=None,
):
    """Streamed twin of :func:`cocoa_tpu.data.sharding.shard_dataset`
    (see :func:`_stream_build` for the mechanics; this wrapper only
    resolves the pass-1 index first so the ``ingest_pass2`` span times
    exactly the shard parse + slab build)."""
    if index is None:
        index = build_index(path, num_features, cache=cache)
    with _tracing.span("ingest_pass2", path=path):
        return _stream_build(
            path, num_features, k, layout=layout, dtype=dtype, mesh=mesh,
            max_nnz=max_nnz, eval_dense=eval_dense, hot_cols=hot_cols,
            index=index, cache=cache)


def _stream_build(
    path: str,
    num_features: int,
    k: int,
    *,
    layout: str = "auto",
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
    max_nnz: Optional[int] = None,
    eval_dense: bool = False,
    hot_cols: int = 0,
    index: Optional[IngestIndex] = None,
    cache=None,
):
    """Streamed twin of :func:`cocoa_tpu.data.sharding.shard_dataset`:
    same arguments plus the file path instead of parsed data, returning
    ``(ShardedDataset, StreamBuildInfo)``.  The dataset is bit-identical
    to the whole-file build of the same file/config — same slab builders
    over the same parsed values, only the parse granularity changes.

    Multi-process with a dp mesh: each process parses and materializes
    ONLY its local devices' shards (m = K/D shards per device —
    multiplexed meshes are first-class).  Single-process: shards build
    one wave at a time from their byte ranges (the full CSR is still
    never materialized), then place exactly like the replicated builder.
    fp meshes keep whole-file ingest — the feature-axis column split
    re-buckets every row and has no data-local byte range per device;
    that combination is rejected loudly upstream.

    With ``cache`` (--ingestCache), each shard is served from its cached
    slab artifact when present — zero parse, mmap'd straight toward
    ``device_put`` — and every shard parsed cold is stored back
    (slab_cache.ShardCacheView, atomic rename).  A full-hit build parses
    zero bytes.
    """
    if index is None:
        index = build_index(path, num_features, cache=cache)
    n, d = index.n, num_features
    layout = sharding_lib.resolve_layout_stats(n, d, index.total_nnz,
                                               layout, mesh)
    if mesh_lib.has_fp(mesh):
        # sparse+fp is impossible anywhere; dense+fp is whole-ingest only
        raise ValueError(
            "streamed ingest does not support feature-parallel (fp) "
            "meshes: the fp column split has no per-device byte range "
            "to stream; use --ingest=whole"
        )
    if eval_dense and layout != "sparse":
        raise ValueError("eval_dense only applies to the sparse layout "
                         "(the dense layout's eval is already a matvec)")

    np_dtype = np.dtype(dtype)
    sizes = sharding_lib.split_sizes(n, k)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    n_shard = sharding_lib.pad_rows(int(sizes.max())) if k > 0 else 0

    width = 0
    if layout == "sparse":
        width = int(max_nnz if max_nnz is not None
                    else sharding_lib.rectangle_width(
                        index.row_nnz.max(initial=1)))
        if n and int(index.row_nnz.max(initial=0)) > width:
            raise ValueError(
                f"row nnz {int(index.row_nnz.max())} exceeds max_nnz "
                f"{width}"
            )

    rank = None
    hot_ids = None
    n_hot = 0
    if hot_cols:
        if layout != "sparse":
            raise ValueError("hot_cols (the hot/cold column split) only "
                             "applies to the sparse layout")
        if max_nnz is not None:
            raise ValueError("hot_cols and max_nnz cannot combine: the "
                             "residual width is measured from the split")
        n_hot = hybrid_lib.pad_panel(min(int(hot_cols), d))
        # the hot set derives from the ASSEMBLED histogram — identical to
        # the whole-file hottest_columns(column_counts(data), n_hot)
        hot_ids = hybrid_lib.hottest_columns(index.hist, n_hot)
        rank = hybrid_lib.hot_rank(d, hot_ids)

    distributed_build = (mesh is not None and jax.process_count() > 1)
    if distributed_build:
        if k % mesh.devices.size != 0:
            # same divisibility contract as sharding.shard_dataset; the
            # elastic shrink path only relaunches divisor-sized gangs
            # (elastic.shrink_gang_size), so a reformed survivor gang can
            # never trip this — only a hand-built mismatched launch does
            raise ValueError(
                f"multi-process runs need numSplits divisible by the dp "
                f"mesh size: K={k} shards cannot multiplex onto "
                f"{mesh.devices.size} devices"
            )
        locals_ = mesh_lib.dp_local_shards(mesh, k)
    else:
        locals_ = [(None, 0, k)]
    local_shards = [s for _, lo, hi in locals_ for s in range(lo, hi)]

    handle = _cache_handle(cache, path, num_features)
    mapped_before = cache.bytes_mapped if cache is not None else 0

    t0 = time.perf_counter()
    bytes_read = 0
    rows_parsed = 0
    nnz_parsed = 0

    def parse_shard(s):
        """The CSR piece holding exactly shard ``s``'s rows (thread-safe:
        pure function of the index; accounting happens at the consumer)."""
        r0, r1 = int(offsets[s]), int(offsets[s + 1])
        blo = int(index.row_off[r0])
        bhi = int(index.row_off[r1])
        piece, _ = load_libsvm_range(path, d, blo, bhi)
        if piece.n != r1 - r0:
            raise ValueError(
                f"{path}: changed during ingest (index says rows "
                f"[{r0}, {r1}) occupy bytes [{blo}, {bhi}), parsed "
                f"{piece.n} rows); re-run"
            )
        return piece, bhi - blo

    def account(piece, nbytes):
        nonlocal bytes_read, rows_parsed, nnz_parsed
        bytes_read += nbytes
        rows_parsed += piece.n
        nnz_parsed += len(piece.values)

    # hybrid residual width: the cached layout meta when EVERY process
    # holds it (voted — see _all_agree); else measured on the held
    # pass-2 pieces and max-reduced across processes — exact integer,
    # equal to the whole-file bincount(cold_rows).max() — then cached
    pieces: dict = {}
    resid_max = 0
    if n_hot:
        cached_resid = (handle.load_hybrid_meta(n_hot)
                        if handle is not None else None)
        have_meta = cache is not None and _all_agree(
            cached_resid is not None)
        if have_meta:
            resid_max = int(cached_resid)
        else:
            for s, (piece, nbytes) in _parse_waves(local_shards,
                                                   parse_shard):
                account(piece, nbytes)
                pieces[s] = piece
            local_max = 0
            for piece in pieces.values():
                if piece.n == 0:
                    continue
                pr_nnz = np.diff(piece.indptr)
                rows = np.repeat(np.arange(piece.n, dtype=np.int64),
                                 pr_nnz)
                cold = rows[rank[piece.indices] < 0]
                local_max = max(local_max, int(
                    np.bincount(cold, minlength=piece.n).max(initial=0)))
            resid_max = (_exchange_max(local_max)
                         if jax.process_count() > 1 else local_max)
            if handle is not None:
                handle.store_hybrid_meta(n_hot, resid_max)
        width = max(1, resid_max)

    d_eff = mesh_lib.pad_features(d, mesh) if layout == "dense" else d
    view = (handle.view(layout=layout, k=k, n_shard=n_shard, width=width,
                        n_hot=n_hot, d=d_eff, dtype=np_dtype,
                        eval_dense=eval_dense)
            if handle is not None else None)

    cached_count = 0

    def build_from_piece(s, piece):
        """Shard ``s``'s slab dict from its own parsed piece; a cold
        build also publishes the slab to the cache."""
        pr_nnz = np.diff(piece.indptr)
        pr_sq = sharding_lib.segment_sq_norms(piece.values, piece.indptr)
        slab = sharding_lib._build_shard_slabs(
            piece, 0, piece.n, n_shard, layout, np_dtype, d_eff, width,
            pr_nnz, pr_sq, rank=rank, n_hot=n_hot, eval_dense=eval_dense)
        if view is not None:
            view.store(s, slab)
        return slab

    def iter_slabs():
        """Yield ``(s, slab)`` for every local shard: cache hits first
        (zero parse), then the held hybrid-measurement pieces (no
        re-parse), then the remaining misses parsed in bounded parallel
        waves — one slab at a time, so the single-process peak stays the
        stacked arrays plus one wave of pieces."""
        nonlocal cached_count
        to_parse = []
        for s in local_shards:
            if s in pieces:
                continue
            slab = view.load(s) if view is not None else None
            if slab is not None:
                cached_count += 1
                yield s, slab
            else:
                to_parse.append(s)
        for s in sorted(pieces):
            yield s, build_from_piece(s, pieces.pop(s))
        for s, (piece, nbytes) in _parse_waves(to_parse, parse_shard):
            account(piece, nbytes)
            yield s, build_from_piece(s, piece)

    if distributed_build:
        built = dict(iter_slabs())
        ds = sharding_lib._assemble_distributed(
            mesh, k, built, locals_, layout=layout, n=n, d=d_eff,
            n_shard=n_shard, width=width, sizes=sizes, n_hot=n_hot,
            hot_ids=hot_ids, eval_dense=eval_dense, np_dtype=np_dtype)
    else:
        arrs: dict = {}
        for s, slab in iter_slabs():
            for f, v in slab.items():
                arrs.setdefault(f,
                                np.zeros((k, *v.shape), v.dtype))[s] = v
        if n_hot:
            hc = np.zeros(n_hot, dtype=np.int32)
            hc[:len(hot_ids)] = hot_ids
            arrs["hot_cols"] = np.tile(hc[None], (k, 1))
        ds = sharding_lib._finalize_replicated(
            arrs, layout=layout, n=n, d=d_eff, mesh=mesh, sizes=sizes)
    sharding_lib.note_row_lengths(ds, index.row_nnz)

    parse_seconds = time.perf_counter() - t0
    status = "off"
    seconds_saved = 0.0
    if cache is not None:
        if cached_count == len(local_shards):
            status = "hit"
            seconds_saved = (handle.load_cost()
                             if handle is not None else 0.0)
        else:
            status = "partial" if cached_count else "miss"
            if handle is not None and cached_count == 0:
                # record the FULL-miss cold cost so warm runs can report
                # what the cache bought (the seconds_saved estimate);
                # a partial run only re-paid its missed shards — writing
                # that sliver would corrupt the estimate for the cache's
                # lifetime
                handle.store_cost(index.scan_seconds + parse_seconds)
    info = StreamBuildInfo(
        rows=rows_parsed,
        nnz=nnz_parsed,
        bytes_read=bytes_read,
        parse_seconds=parse_seconds,
        residual_max_nnz=resid_max,
        shards_cached=cached_count,
        shards_total=len(local_shards),
        cache_bytes_mapped=(cache.bytes_mapped - mapped_before
                            if cache is not None else 0),
        cache_status=status,
        seconds_saved=seconds_saved,
    )
    return ds, info


def load_cached_dataset(handle, stats, k, *, layout: str, dtype,
                        mesh=None, eval_dense: bool = False,
                        hot_cols: int = 0):
    """Zero-parse :class:`ShardedDataset` entirely from ``--ingestCache``
    artifacts — the warm half of the WHOLE-file path (the streaming path
    warms per shard inside :func:`_stream_build`).  ``layout`` must be
    RESOLVED (the caller resolved it from the cached stats); ``hot_cols``
    is the resolved lane-padded panel width.  Returns
    ``(ShardedDataset, StreamBuildInfo)`` or None when any artifact is
    missing or corrupt — the caller cold-parses, which re-populates."""
    t0 = time.perf_counter()
    n, d = stats.n, handle.num_features
    np_dtype = np.dtype(dtype)
    sizes = sharding_lib.split_sizes(n, k)
    n_shard = sharding_lib.pad_rows(int(sizes.max())) if k > 0 else 0
    width = 0
    resid_max = 0
    hot_ids = None
    if layout == "sparse":
        if hot_cols:
            resid = handle.load_hybrid_meta(hot_cols)
            if resid is None:
                return None
            resid_max = int(resid)
            width = max(1, resid_max)
            hot_ids = hybrid_lib.hottest_columns(stats.hist, hot_cols)
        else:
            width = sharding_lib.rectangle_width(stats.max_row_nnz)
    d_eff = mesh_lib.pad_features(d, mesh) if layout == "dense" else d
    view = handle.view(layout=layout, k=k, n_shard=n_shard, width=width,
                       n_hot=hot_cols, d=d_eff, dtype=np_dtype,
                       eval_dense=eval_dense)
    distributed_build = (mesh is not None and jax.process_count() > 1
                         and not mesh_lib.has_fp(mesh))
    if distributed_build:
        if k % mesh.devices.size != 0:
            return None  # the cold path raises its own loud error
        locals_ = mesh_lib.dp_local_shards(mesh, k)
        needed = [s for _, lo, hi in locals_ for s in range(lo, hi)]
    else:
        locals_ = None
        needed = list(range(k))
    before = handle.cache.bytes_mapped
    built = {}
    for s in needed:
        slab = view.load(s)
        if slab is None:
            return None
        built[s] = slab
    bytes_mapped = handle.cache.bytes_mapped - before
    if distributed_build:
        ds = sharding_lib._assemble_distributed(
            mesh, k, built, locals_, layout=layout, n=n, d=d_eff,
            n_shard=n_shard, width=width, sizes=sizes, n_hot=hot_cols,
            hot_ids=hot_ids, eval_dense=eval_dense, np_dtype=np_dtype)
    else:
        arrs: dict = {}
        for s in needed:
            for f, v in built[s].items():
                arrs.setdefault(f,
                                np.zeros((k, *v.shape), v.dtype))[s] = v
        if hot_cols:
            hc = np.zeros(hot_cols, dtype=np.int32)
            hc[:len(hot_ids)] = hot_ids
            arrs["hot_cols"] = np.tile(hc[None], (k, 1))
        ds = sharding_lib._finalize_replicated(
            arrs, layout=layout, n=n, d=d_eff, mesh=mesh, sizes=sizes)
    sharding_lib.note_row_lengths(ds, longest=stats.max_row_nnz)
    info = StreamBuildInfo(
        rows=0, nnz=0, bytes_read=0,
        parse_seconds=time.perf_counter() - t0,
        residual_max_nnz=resid_max,
        shards_cached=len(needed), shards_total=len(needed),
        cache_bytes_mapped=bytes_mapped, cache_status="hit",
        seconds_saved=handle.load_cost(),
    )
    return ds, info


def resolve_ingest_mode(spec, mesh, *, objective: str = "svm",
                        cached: bool = False) -> str:
    """``--ingest=stream|whole|auto`` → the mode a run uses.

    ``auto`` picks ``stream`` exactly where it wins: multi-process svm
    runs on a dp mesh (every process would otherwise parse the whole
    file) — and, with ``cached`` (--ingestCache armed), EVERY svm run on
    a dp-or-no mesh, since the shard-granular pipeline is what consults
    and populates the cache at shard granularity and its shards are
    bit-identical to the whole-file build (pinned).  Single-process
    uncached, fp meshes, and the lasso column shards keep ``whole`` —
    the replicated builder is the bit-exact A/B control.  Explicit asks
    that cannot be honored raise (loudly, with the remedy).
    """
    spec_s = ("auto" if spec is None else str(spec)).strip().lower()
    if spec_s not in ("auto", "stream", "whole"):
        raise ValueError(f"--ingest must be stream|whole|auto, "
                         f"got {spec!r}")
    if spec_s == "stream":
        if objective == "lasso":
            raise ValueError(
                "--ingest=stream does not apply to --objective=lasso "
                "(column shards re-bucket every row; use --ingest=whole)")
        if mesh_lib.has_fp(mesh):
            raise ValueError(
                "--ingest=stream does not support feature-parallel (fp) "
                "meshes (no per-device byte range to stream); use "
                "--ingest=whole")
        return "stream"
    if spec_s == "whole":
        return "whole"
    if (objective == "svm" and mesh is not None
            and not mesh_lib.has_fp(mesh) and jax.process_count() > 1):
        return "stream"
    if cached and objective == "svm" and not mesh_lib.has_fp(mesh):
        return "stream"
    return "whole"


@dataclasses.dataclass
class IngestReport:
    """The typed ``ingest`` telemetry payload (one per loaded file)."""

    mode: str                # "stream" | "whole"
    path: str
    file_bytes: int
    processes: int
    parse_seconds: float     # this process: scan + shard parse
    bytes_read: int          # this process: scanned + parsed bytes
    rows: int                # rows this process materialized
    nnz: int
    n: int                   # global dataset facts
    total_nnz: int
    peak_rss_bytes: int
    cache: str = "off"       # --ingestCache outcome: off|hit|partial|miss

    def as_fields(self) -> dict:
        return dataclasses.asdict(self)
