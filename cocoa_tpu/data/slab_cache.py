"""Shard-granular persistent slab cache: ingest goes free after first touch.

The compile cache (utils/compile_cache.py) made the SECOND run's XLA
compiles free; ingest stayed the dominant fixed cost — every process
re-parsed the LIBSVM text on every start.  The CoCoA premise (arXiv:1409.1458) is that
local data is touched ONCE and then reused across many cheap rounds;
elastic restarts (PR 9), serve-while-train trainer relaunches (PR 13),
fleet manifests sharing a dataset ref (PR 12), bench sweeps, and CI all
violated that premise at the process level.

This module closes it (docs/DESIGN.md §18).  After a cold parse, each
built shard's DEVICE-READY host slabs — the exact ``_build_shard_slabs``
output: labels/mask/sq_norms plus padded-CSR index/value arrays, the
hybrid hot-panel + cold-residual pair, the dense ``--evalDense`` twin —
are written as memmap-able ``.npy`` artifacts under ``--ingestCache=DIR``,
alongside the pass-1 index (global column histogram + row offsets/nnz)
and the hybrid layout meta (the exchanged residual width).  Warm runs
``np.load(mmap_mode="r")`` the slabs straight into ``device_put``: zero
parse, zero slab build, RSS shared through the page cache across
concurrent processes mapping the same artifact.

**Key derivation** (the invalidation contract):

- the *file tag* hashes ``(st_dev, st_ino, st_size, st_mtime_ns,
  num_features, PARSER_VERSION)``.  ``st_ino`` is load-bearing: an
  atomic-rename rewrite on a coarse-mtime filesystem changes the inode
  even when mtime_ns aliases (the checkpoint-validate lesson from
  PR 13); any content change flips size or mtime_ns or inode.
- the *shard tag* adds the full layout resolution — layout kind, K,
  n_shard, padded width, hot-panel width, eval-twin flag, padded d,
  dtype, LAYOUT_VERSION — plus the shard id ``s``.  Because the key is
  the SHARD (0..K-1), not the process geometry, an elastic shrink's
  survivors re-map their inherited shards warm, and a T-tenant fleet
  maps one build T times.

**Single-writer protocol**: an artifact is a directory written to a
writer-unique (pid + uuid — pids collide across hosts sharing a cache
dir) temp name and atomically ``os.rename``\\ d into place — one writer
wins, the loser reads the winner's (bit-identical) artifact.  A rename
onto an existing artifact fails and the temp is discarded; a reader
never sees a half-written directory.  Publish failures (ENOSPC, lost
permission) degrade to uncached operation with one warning — the cache
is an accelerator, never a dependency.

**Corruption**: every load re-validates shapes/dtypes/field sets against
the artifact's own manifest; a torn, truncated, or short file (the
``tests/_faults.truncate_newest_cache_artifact`` fault) fails the load,
fires ``on_corrupt`` (the typed ``ingest_cache_corrupt`` event), evicts
the bad artifact best-effort, and the caller falls back to a cold parse.

**What is never cached**: device arrays (placement is per-run), the
lasso column shards (the transpose re-buckets every row per run), fleet
``(T, K, …)`` stacks (tenant-geometry-keyed; fleet dedupe is the
in-process ref memo in data/fleet.py), and anything keyed to a mesh —
shard slabs are geometry-free by construction.

Deliberately numpy-only (no jax import): the ingest benchmarks measure
warm loads in clean subprocesses whose RSS must reflect the mapped
artifacts, not a backend baseline.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import uuid
from typing import Callable, Optional

import numpy as np

# bump when the PARSE semantics change (what rows/pairs a byte range
# yields): invalidates every artifact derived from parsed text
PARSER_VERSION = 1
# bump when the SLAB layout changes (the _build_shard_slabs output
# contract: field set, padding, dtypes): invalidates shard artifacts
LAYOUT_VERSION = 1


def _digest(parts: dict) -> str:
    blob = json.dumps(parts, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:20]


def _tmp_name(final: str) -> str:
    """A writer-unique temp name.  pid alone is NOT unique across hosts
    sharing one cache directory (the multi-host elastic gang over NFS —
    two workers with the same pid would interleave writes into one temp
    dir and publish a torn artifact); the uuid component makes every
    writer's staging area its own."""
    return f"{final}.tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}"


def _atomic_publish(tmp_dir: str, final_dir: str) -> bool:
    """Atomically rename a fully-written temp artifact into place.
    Returns True when THIS writer won; False when another writer already
    published (the temp is discarded — the artifacts are bit-identical
    by construction, so the loser simply reads the winner's)."""
    try:
        os.rename(tmp_dir, final_dir)
        return True
    except OSError:
        shutil.rmtree(tmp_dir, ignore_errors=True)
        return False


def _write_json_atomic(path: str, obj: dict) -> None:
    tmp = _tmp_name(path)
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


@dataclasses.dataclass
class CachedStats:
    """The cached pass-1 facts of one source file: enough to resolve
    ``--layout=auto`` / ``--hotCols=auto`` / ``--evalDense=auto`` and to
    key every shard artifact WITHOUT parsing a byte.  ``row_off`` /
    ``row_nnz`` are present only on index artifacts stored by a pass-1
    scan (``has_rows``) — the whole-file populate path has no byte
    offsets to record, and a warm full-hit load never needs them."""

    n: int
    file_bytes: int
    total_nnz: int
    max_row_nnz: int
    hist: np.ndarray                 # (d,) int64 global column histogram
    has_rows: bool
    row_off: Optional[np.ndarray] = None   # (n+1,) int64 when has_rows
    row_nnz: Optional[np.ndarray] = None   # (n,) int64 when has_rows


class SlabCache:
    """One ``--ingestCache=DIR`` root.  Thread-compatible; process-safe
    through the atomic-rename protocol.  Counters accumulate across every
    handle/view created from this instance (the telemetry the CLI's
    ``ingest_cache`` event reports)."""

    def __init__(self, root: str,
                 on_corrupt: Optional[Callable] = None):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.on_corrupt = on_corrupt
        self.shard_hits = 0
        self.shard_misses = 0
        self.corrupt_total = 0
        self.bytes_mapped = 0
        self.store_failures = 0

    def _store_failed(self, what: str, err: Exception) -> None:
        """Publish failures (ENOSPC, lost permission, a yanked volume)
        degrade to UNCACHED operation — the data is already parsed in
        memory and the run must proceed; a cache is an accelerator, not
        a dependency.  Warn once so a dead cache volume is visible."""
        self.store_failures += 1
        if self.store_failures == 1:
            import warnings

            warnings.warn(
                f"--ingestCache could not publish {what} "
                f"({type(err).__name__}: {err}); continuing uncached — "
                f"check the cache volume", RuntimeWarning)

    def for_file(self, path: str, num_features: int) -> "FileCacheHandle":
        """Bind the cache to one source file's CURRENT identity (stat).
        Raises OSError when the file cannot be stat'd — the cold parse
        would fail on the same file, so callers share one error path."""
        st = os.stat(path)
        return FileCacheHandle(self, path, num_features, st)

    def _corrupt(self, path: str, artifact: str, reason: str) -> None:
        self.corrupt_total += 1
        if self.on_corrupt is not None:
            try:
                self.on_corrupt(path=path, artifact=artifact,
                                reason=reason)
            except Exception:
                pass  # telemetry must never turn a recoverable cache
                # miss into a crash


class FileCacheHandle:
    """The per-source-file face of the cache: the index/stats artifact,
    the hybrid layout meta, the cold-cost sidecar, and the
    :class:`ShardCacheView` factory."""

    def __init__(self, cache: SlabCache, path: str, num_features: int,
                 st: os.stat_result):
        self.cache = cache
        self.path = path
        self.num_features = int(num_features)
        self.file_tag = _digest({
            "kind": "file",
            "dev": int(st.st_dev),
            "ino": int(st.st_ino),
            "size": int(st.st_size),
            "mtime_ns": int(st.st_mtime_ns),
            "num_features": self.num_features,
            "parser": PARSER_VERSION,
        })
        self.file_bytes = int(st.st_size)

    # --- the pass-1 index artifact ---------------------------------------

    def _index_dir(self, full: bool) -> str:
        # two artifact kinds, never overwritten in place: "-full" carries
        # the row offset/nnz arrays a streaming pass-2 needs, "-stats"
        # is the whole-path populate (histogram + scalars only).  The
        # loader prefers full; a later scan upgrades stats->full by
        # publishing the OTHER name (no replace-in-place race).
        return os.path.join(self.cache.root,
                            f"index-{self.file_tag}-"
                            f"{'full' if full else 'stats'}")

    def store_index(self, *, hist, n: int, total_nnz: int,
                    max_row_nnz: int, row_off=None, row_nnz=None) -> None:
        full = row_off is not None
        final = self._index_dir(full)
        if os.path.isdir(final):
            return
        tmp = _tmp_name(final)
        try:
            os.makedirs(tmp, exist_ok=True)
            np.save(os.path.join(tmp, "hist.npy"),
                    np.asarray(hist, np.int64))
            if full:
                np.save(os.path.join(tmp, "row_off.npy"),
                        np.asarray(row_off, np.int64))
                np.save(os.path.join(tmp, "row_nnz.npy"),
                        np.asarray(row_nnz, np.int64))
            _write_json_atomic(os.path.join(tmp, "meta.json"), {
                "n": int(n), "file_bytes": self.file_bytes,
                "total_nnz": int(total_nnz),
                "max_row_nnz": int(max_row_nnz), "has_rows": bool(full),
            })
        except OSError as e:
            shutil.rmtree(tmp, ignore_errors=True)
            self.cache._store_failed(os.path.basename(final), e)
            return
        _atomic_publish(tmp, final)

    def load_index(self) -> Optional[CachedStats]:
        """The cached stats (preferring the full index), or None."""
        for full in (True, False):
            d = self._index_dir(full)
            if not os.path.isdir(d):
                continue
            try:
                with open(os.path.join(d, "meta.json")) as f:
                    meta = json.load(f)
                hist = np.load(os.path.join(d, "hist.npy"),
                               mmap_mode="r")
                if hist.shape != (self.num_features,):
                    raise ValueError(
                        f"hist shape {hist.shape} != "
                        f"({self.num_features},)")
                out = CachedStats(
                    n=int(meta["n"]),
                    file_bytes=int(meta["file_bytes"]),
                    total_nnz=int(meta["total_nnz"]),
                    max_row_nnz=int(meta["max_row_nnz"]),
                    hist=np.asarray(hist), has_rows=bool(full))
                if full:
                    row_off = np.load(os.path.join(d, "row_off.npy"),
                                      mmap_mode="r")
                    row_nnz = np.load(os.path.join(d, "row_nnz.npy"),
                                      mmap_mode="r")
                    if (row_off.shape != (out.n + 1,)
                            or row_nnz.shape != (out.n,)):
                        raise ValueError("row index shape mismatch")
                    out.row_off = np.asarray(row_off)
                    out.row_nnz = np.asarray(row_nnz)
                return out
            except (OSError, ValueError, KeyError) as e:
                self.cache._corrupt(self.path, os.path.basename(d),
                                    f"{type(e).__name__}: {e}")
                shutil.rmtree(d, ignore_errors=True)
        return None

    # --- the hybrid layout meta (the exchanged residual width) -----------

    def _hybrid_meta_path(self, n_hot: int) -> str:
        tag = _digest({"kind": "hybridmeta", "file": self.file_tag,
                       "n_hot": int(n_hot), "layout": LAYOUT_VERSION})
        return os.path.join(self.cache.root, f"hybrid-{tag}.json")

    def store_hybrid_meta(self, n_hot: int, resid_max: int) -> None:
        try:
            _write_json_atomic(self._hybrid_meta_path(n_hot),
                               {"resid_max": int(resid_max),
                                "n_hot": int(n_hot)})
        except OSError as e:
            self.cache._store_failed("hybrid meta", e)

    def load_hybrid_meta(self, n_hot: int) -> Optional[int]:
        path = self._hybrid_meta_path(n_hot)
        try:
            with open(path) as f:
                meta = json.load(f)
            return int(meta["resid_max"])
        except FileNotFoundError:
            return None
        except (OSError, ValueError, KeyError) as e:
            self.cache._corrupt(self.path, os.path.basename(path),
                                f"{type(e).__name__}: {e}")
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    # --- the cold-cost sidecar (the seconds_saved estimate) --------------

    def _cost_path(self) -> str:
        return os.path.join(self.cache.root, f"cost-{self.file_tag}.json")

    def store_cost(self, seconds: float) -> None:
        try:
            _write_json_atomic(self._cost_path(),
                               {"cold_seconds": float(seconds)})
        except OSError as e:
            self.cache._store_failed("cost sidecar", e)

    def load_cost(self) -> float:
        try:
            with open(self._cost_path()) as f:
                return float(json.load(f)["cold_seconds"])
        except (OSError, ValueError, KeyError):
            return 0.0

    # --- the per-shard slab view -----------------------------------------

    def view(self, *, layout: str, k: int, n_shard: int, width: int,
             n_hot: int, d: int, dtype, eval_dense: bool
             ) -> "ShardCacheView":
        return ShardCacheView(self, layout=layout, k=k, n_shard=n_shard,
                              width=width, n_hot=n_hot, d=d, dtype=dtype,
                              eval_dense=eval_dense)


class ShardCacheView:
    """One fully-resolved layout's shard artifacts: ``load(s)`` /
    ``store(s, slab)`` over the ``_build_shard_slabs`` field dicts."""

    def __init__(self, handle: FileCacheHandle, *, layout: str, k: int,
                 n_shard: int, width: int, n_hot: int, d: int, dtype,
                 eval_dense: bool):
        self.handle = handle
        self.cache = handle.cache
        np_dtype = np.dtype(dtype)
        self.fields = ["labels", "mask", "sq_norms"]
        if layout == "dense":
            self.fields.append("X")
        else:
            if n_hot:
                self.fields.append("X_hot")
            self.fields += ["sp_indices", "sp_values"]
            if eval_dense:
                self.fields.append("X_eval")
        self.layout_tag = _digest({
            "kind": "slab", "file": handle.file_tag, "layout": layout,
            "k": int(k), "n_shard": int(n_shard), "width": int(width),
            "n_hot": int(n_hot), "d": int(d), "dtype": np_dtype.name,
            "eval_dense": bool(eval_dense), "version": LAYOUT_VERSION,
        })

    def _shard_dir(self, s: int) -> str:
        return os.path.join(self.cache.root,
                            f"slab-{self.layout_tag}-s{int(s):05d}")

    def load(self, s: int, *, mmap: bool = True) -> Optional[dict]:
        """Shard ``s``'s slab dict (memmap'd by default), or None on a
        miss.  Any validation failure — torn file, shape/dtype/field
        drift — counts as CORRUPT: the event fires, the artifact is
        evicted, and None sends the caller to the cold parse."""
        d = self._shard_dir(s)
        if not os.path.isdir(d):
            self.cache.shard_misses += 1
            return None
        try:
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            if sorted(meta.get("fields", {})) != sorted(self.fields):
                raise ValueError(
                    f"field set {sorted(meta.get('fields', {}))} != "
                    f"expected {sorted(self.fields)}")
            out = {}
            nbytes = 0
            for name in self.fields:
                spec = meta["fields"][name]
                arr = np.load(os.path.join(d, f"{name}.npy"),
                              mmap_mode="r" if mmap else None)
                if (list(arr.shape) != list(spec["shape"])
                        or arr.dtype.name != spec["dtype"]):
                    raise ValueError(
                        f"{name}: {arr.shape}/{arr.dtype.name} != "
                        f"manifest {spec['shape']}/{spec['dtype']}")
                # touch the first element: a truncated data segment that
                # survived the header check must fail HERE, not later
                # inside device_put
                if arr.size:
                    arr[(0,) * arr.ndim]
                out[name] = arr
                nbytes += arr.nbytes
            self.cache.shard_hits += 1
            self.cache.bytes_mapped += nbytes
            return out
        except (OSError, ValueError, KeyError) as e:
            self.cache.shard_misses += 1
            self.cache._corrupt(self.handle.path, os.path.basename(d),
                                f"{type(e).__name__}: {e}")
            shutil.rmtree(d, ignore_errors=True)
            return None

    def store(self, s: int, slab: dict) -> None:
        """Publish shard ``s``'s slab dict (atomic rename, one writer
        wins).  Field order/set is validated against the view so a
        builder drift cannot poison the cache silently."""
        if sorted(slab) != sorted(self.fields):
            raise ValueError(
                f"slab fields {sorted(slab)} != view fields "
                f"{sorted(self.fields)} — the cache key no longer "
                f"matches the builder output (bump LAYOUT_VERSION)")
        final = self._shard_dir(s)
        if os.path.isdir(final):
            return
        tmp = _tmp_name(final)
        try:
            os.makedirs(tmp, exist_ok=True)
            meta = {"fields": {}, "shard": int(s)}
            for name in self.fields:
                arr = np.ascontiguousarray(slab[name])
                np.save(os.path.join(tmp, f"{name}.npy"), arr)
                meta["fields"][name] = {"shape": list(arr.shape),
                                        "dtype": arr.dtype.name}
            _write_json_atomic(os.path.join(tmp, "meta.json"), meta)
        except OSError as e:
            # a publish failure (ENOSPC, lost permission) must degrade
            # to uncached operation, not kill a run whose data is
            # already parsed — the read-side contract's write twin
            shutil.rmtree(tmp, ignore_errors=True)
            self.cache._store_failed(os.path.basename(final), e)
            return
        _atomic_publish(tmp, final)
