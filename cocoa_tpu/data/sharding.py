"""Device-sharded dataset layouts.

Replaces the reference's ``RDD[LabeledPoint]`` partitioning
(OptUtils.scala:14 ``textFile(...).coalesce(numSplits)``) with K contiguous,
balanced row blocks placed one-per-mesh-position in HBM.  Two layouts:

- **dense** — shard ``X`` is a (n_shard, d) matrix.  Right for dense data
  (epsilon-like) and moderate d: row access is a ``dynamic_slice``, eval is a
  single MXU matmul.
- **sparse** (padded-CSR) — per-row index/value arrays padded to the dataset's
  ``max_nnz``.  Right for high-d sparse data (rcv1-like): a row dot is a
  gather + small reduction instead of an O(d) dot.  TPU has no native sparse
  support, so padding + gather is the idiomatic encoding.

Shards are padded to equal row counts (XLA needs static shapes).  Padded rows
carry ``mask=0``, ``y=0``, ``x=0`` and are never sampled (index draws are
bounded by the shard's true count), never counted in objectives (mask-weighted
reductions).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.parallel import mesh as mesh_lib


def pad_rows(n_rows: int) -> int:
    """Shard length rounded up to a sublane multiple (8 f32 / 16 bf16) so
    Pallas row blocks and XLA tiles stay aligned; padded rows are masked
    everywhere.  This is THE layout contract — every producer of a
    :class:`ShardedDataset` (here and data/synth.py) must use it."""
    return -(-n_rows // 16) * 16


RECTANGLE_GROUP = 8              # slots a group: the sublanes of a tile, and
                                 # ops/rows.SLOT_GROUP


def rectangle_width(longest: int) -> int:
    """Slots a row of the padded-CSR rectangle (K, n_shard, W) takes where
    the longest row has ``longest`` nonzeros: a whole number of 8-slot
    groups (the slots past a row hold column 0, value 0, as ever).  The
    block passes walk a row's slots eight at a time (ops/rows.SLOT_GROUP),
    and a TPU stores a rectangle with the row index on the lanes and the
    slots on the sublanes only where W is whole sublane tiles: at K = 8 and
    W = 39 it puts K on the sublanes instead (0% padding against 39 -> 40),
    and every program that reads (W, 128) tiles of rows, the row fetch of
    ops/pallas_sparse_hbm.py, then opens with a copy of the whole dataset
    (3.99 GB of temporaries at a quarter of criteo; tests/
    test_device_layout.py).  An explicit ``max_nnz`` is kept as given."""
    return -(-max(1, int(longest)) // RECTANGLE_GROUP) * RECTANGLE_GROUP


def note_row_lengths(ds: "ShardedDataset", row_nnz=(),
                     longest=None) -> "ShardedDataset":
    """Keep on a rectangle what its loader saw of its real rows' lengths on
    the host (``row_nnz``: their counts of nonzeros; or the ``longest``
    alone, from a loader that kept no more): the longest, which the width
    no longer says and a run's record reports (``SolverPath.longest_row``)
    before anything has read the lengths on the device; and whether they
    are all that long, which :func:`passes_want_order` asks."""
    if ds.layout == "sparse" and ds.sp_row_ptr is None and not ds.n_hot:
        row_nnz = np.asarray(row_nnz).reshape(-1)
        ds._longest_row = int(row_nnz.max(initial=0) if longest is None
                              else longest)
        ds._one_length = bool(row_nnz.size) and \
            0 < int(row_nnz.min()) == ds._longest_row
    return ds


def resolve_layout_stats(n: int, d: int, nnz: int, layout: str,
                         mesh=None) -> str:
    """The one place the ``layout="auto"`` rule lives, from dataset
    STATS alone (streaming ingest resolves before any rows are parsed
    into a full dataset): sparse below 10% density (rcv1-like), dense
    otherwise (epsilon-like); feature-parallel meshes are dense-only."""
    if layout != "auto":
        return layout
    density = nnz / max(1, n * d)
    if mesh_lib.has_fp(mesh):
        return "dense"  # fp sharding is dense-only (see shard_dataset)
    return "sparse" if density < 0.10 else "dense"


def resolve_layout(data, layout: str, mesh=None) -> str:
    """``layout="auto"`` against a parsed dataset — shared by
    :func:`shard_dataset` and the CLI (which must know the resolved
    layout before it can resolve sparse-only knobs like ``--hotCols``)."""
    return resolve_layout_stats(data.n, data.num_features,
                                int(data.indptr[-1]), layout, mesh)


# HBM budget for the OPT-IN dense eval twin (``--evalDense=auto``): the
# twin costs K·n_shard·d·itemsize (~3.8 GB at rcv1 scale) — auto
# materializes it only under this bound and otherwise lets the eval ride
# the hot panel + residual stream (ops/rows.eval_margins).
EVAL_DENSE_HBM_BUDGET = 2 << 30


def eval_dense_fits(n: int, d: int, k: int, dtype,
                    budget: int = EVAL_DENSE_HBM_BUDGET) -> bool:
    """Whether the sparse layout's dense eval twin fits the HBM budget —
    the ``--evalDense=auto`` accounting (twin bytes vs budget)."""
    n_shard = pad_rows(int(split_sizes(n, k).max())) if k > 0 else 0
    return k * n_shard * d * np.dtype(dtype).itemsize <= budget


def segment_sq_norms(values, ptr) -> np.ndarray:
    """Exact per-segment f64 Σv² for CSR/CSC-style ``(values, ptr)``.

    Per-segment accumulation (not a global prefix-sum difference, which can
    absorb a tiny segment's squares below the running sum's ulp — a
    vanished sq_norm freezes that coordinate in the lasso prox rule).
    ``np.add.reduceat`` quirks handled here so callers don't copy them:
    a trailing 0.0 sentinel makes start indices equal to nnz (trailing
    empty segments) valid without clamping — clamping would steal the last
    nonzero from the final non-empty segment — and empty segments, which
    reduceat maps to the element AT their start, are zeroed explicitly."""
    nseg = len(ptr) - 1
    if nseg <= 0:
        return np.zeros(0)
    sq = np.empty(len(values) + 1)
    # jaxlint: allow=f64 -- exact host-side ‖x‖² accounting; the kernels
    # consume the result cast to the compute dtype
    np.square(np.asarray(values, np.float64), out=sq[:-1])
    sq[-1] = 0.0
    out = np.add.reduceat(sq, np.asarray(ptr[:-1], dtype=np.intp))
    out[np.diff(ptr) == 0] = 0.0
    return out


def split_sizes(n: int, k: int) -> np.ndarray:
    """Balanced contiguous split: first n % k shards get one extra row.

    The reference's shard sizes come from HDFS block boundaries via
    ``coalesce`` (OptUtils.scala:14) and are only approximately equal; we
    define them exactly.  Row order is preserved (contiguous blocks).
    """
    base = n // k
    sizes = np.full(k, base, dtype=np.int64)
    sizes[: n % k] += 1
    return sizes


@dataclasses.dataclass
class ShardedDataset:
    """K data shards stacked on a leading device axis.

    All arrays have leading dim K and are placed with ``P('dp', ...)`` when a
    mesh is given.  ``counts[k]`` is the number of real rows in shard k;
    rows ≥ counts[k] are padding.
    """

    layout: str                       # "dense" | "sparse"
    n: int                            # total real examples
    num_features: int                 # d (padded up to an fp multiple on a
                                      #   feature-parallel mesh; w matches)
    counts: np.ndarray                # (K,) int, host-side
    labels: jax.Array                 # (K, n_shard)
    mask: jax.Array                   # (K, n_shard)  1.0 real / 0.0 pad
    sq_norms: jax.Array               # (K, n_shard)  ||x_i||^2 (precomputed;
                                      #   the reference recomputes per step,
                                      #   CoCoA.scala:173 — same values)
    X: Optional[jax.Array] = None     # dense: (K, n_shard, d)
    sp_indices: Optional[jax.Array] = None  # sparse: (K, n_shard, max_nnz) int32
    sp_values: Optional[jax.Array] = None   # sparse: (K, n_shard, max_nnz)
    X_eval: Optional[jax.Array] = None  # optional dense twin of a SPARSE
                                      #   dataset, used ONLY by evaluation
                                      #   (ops/rows.eval_margins): the
                                      #   certificate's full margins pass as
                                      #   one MXU matvec instead of an
                                      #   every-nonzero w-gather (31% of the
                                      #   rcv1 production round); costs
                                      #   K*n_shard*d*itemsize HBM
    X_hot: Optional[jax.Array] = None   # hybrid sparse layout (hot/cold
                                      #   column split, data/hybrid.py):
                                      #   (K, n_shard, n_hot) dense panel
                                      #   over the globally hottest columns;
                                      #   sp_indices/sp_values then hold
                                      #   ONLY the cold residual
    hot_cols: Optional[jax.Array] = None  # (K, n_hot) int32 panel lane ->
                                      #   original column id (identical per
                                      #   shard; K-leading so it rides the
                                      #   fan-out plumbing like every leaf)
    row_order: Optional[jax.Array] = None  # (K, n_shard) int32, present once
                                      #   a sparse dataset's rows are in
                                      #   length order
                                      #   (:func:`order_rows_by_length`): the
                                      #   row of shard k now at position j
                                      #   was built at position row_order[k, j]
    # the STREAM storage of a sparse dataset (:func:`stream_suits`): rows of
    # a hundred nonzeros or of thousands, the longest a few times the mean,
    # are not padded to the longest.  sp_indices /
    # sp_values are then (K, n_pieces, STREAM_PIECE): each shard's nonzeros
    # as one run of slots, row after row, a row starting on a STREAM_ALIGN
    # boundary (the slots up to it hold column 0, value 0), and
    sp_row_ptr: Optional[jax.Array] = None  # (K, n_shard) int32: a row's
                                      #   first slot / STREAM_ALIGN
    sp_row_len: Optional[jax.Array] = None  # (K, n_shard) int32: its nonzeros
    sp_row_iota: Optional[jax.Array] = None  # (K, W) int32 0..W-1, W the
                                      #   longest row rounded up to
                                      #   STREAM_ALIGN: the static width the
                                      #   per-row accessors window a row by
                                      #   (ops/rows.get_row)
    target: Optional[jax.Array] = None  # column shards only (data/columns.py):
                                      #   (num_features,) the regression
                                      #   target b, replicated like the
                                      #   residual it is the start of; no
                                      #   K axis, so not in shard_arrays()
    classes: Optional[jax.Array] = None  # (K, n_shard) int32 class ids in
                                      #   [0, num_classes) of a multi-class
                                      #   set, BESIDE ``labels`` (which keep
                                      #   the reference's +-1 rule): the
                                      #   label of class t against the rest,
                                      #   +1 where classes == t else -1, is
                                      #   derived where it is used, never
                                      #   stored T times (:func:`class_labels`).
                                      #   A MULTI-LABEL set keeps a row's SET
                                      #   of labels: (K, n_shard, L) ids, a
                                      #   set shorter than L (and a padding
                                      #   row) filled with -1; y_ti = +1
                                      #   where t is among row i's ids.  One
                                      #   id a row is the set of size one
    num_classes: int = 1              # T: how many models a job over these
                                      #   rows trains, one-vs-rest.  1 (every
                                      #   binary set): ``classes`` is None
                                      #   and nothing of the class axis runs

    @property
    def k(self) -> int:
        return self.labels.shape[0]

    @property
    def n_hot(self) -> int:
        """Hot-panel width (0 = pure stream layout)."""
        return 0 if self.X_hot is None else self.X_hot.shape[-1]

    @property
    def n_shard(self) -> int:
        return self.labels.shape[1]

    @property
    def label_slots(self) -> Optional[int]:
        """Ids a row's label set is stored in: 1 where ``classes`` holds one
        class id a row, L where it holds sets, None at T = 1."""
        if self.classes is None:
            return None
        return 1 if self.classes.ndim == 2 else int(self.classes.shape[-1])

    @property
    def dtype(self):
        return self.labels.dtype

    def shard_arrays(self) -> dict:
        """The pytree of per-shard arrays consumed by local solvers."""
        out = {
            "labels": self.labels,
            "mask": self.mask,
            "sq_norms": self.sq_norms,
        }
        if self.classes is not None:
            out["classes"] = self.classes
        if self.layout == "dense":
            out["X"] = self.X
        else:
            out["sp_indices"] = self.sp_indices
            out["sp_values"] = self.sp_values
            if self.sp_row_ptr is not None:
                out["sp_row_ptr"] = self.sp_row_ptr
                out["sp_row_len"] = self.sp_row_len
                out["sp_row_iota"] = self.sp_row_iota
            if self.X_hot is not None:
                out["X_hot"] = self.X_hot
                out["hot_cols"] = self.hot_cols
            if self.X_eval is not None:
                out["X_eval"] = self.X_eval
            row_len = getattr(self, "_row_len_cache", None)
            if self.row_order is not None and row_len is not None:
                # rows in length order: the all-rows passes that run in row
                # blocks stop at a block's longest row (ops/rows.SLOT_GROUP)
                out["sp_row_len"] = row_len
        return out

    # --- pytree protocol: array fields are leaves, metadata is static, so a
    # ShardedDataset can be passed straight through jit/shard_map ---
    def tree_flatten(self):
        children = (
            self.labels, self.mask, self.sq_norms,
            self.X, self.sp_indices, self.sp_values, self.X_eval,
            self.X_hot, self.hot_cols, self.row_order, self.sp_row_ptr,
            self.sp_row_len, self.sp_row_iota, self.target, self.classes,
        )
        aux = (self.layout, self.n, self.num_features, tuple(self.counts),
               self.num_classes)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (labels, mask, sq_norms, X, sp_indices, sp_values, X_eval,
         X_hot, hot_cols, row_order, sp_row_ptr, sp_row_len,
         sp_row_iota, target, classes) = children
        layout, n, num_features, counts, num_classes = aux
        return cls(
            layout=layout,
            n=n,
            num_features=num_features,
            counts=np.asarray(counts, dtype=np.int64),
            labels=labels,
            mask=mask,
            sq_norms=sq_norms,
            X=X,
            sp_indices=sp_indices,
            sp_values=sp_values,
            X_eval=X_eval,
            X_hot=X_hot,
            hot_cols=hot_cols,
            row_order=row_order,
            sp_row_ptr=sp_row_ptr,
            sp_row_len=sp_row_len,
            sp_row_iota=sp_row_iota,
            target=target,
            classes=classes,
            num_classes=num_classes,
        )


def class_labels(classes: jax.Array, mask: jax.Array, t) -> jax.Array:
    """The labels of class ``t`` against the rest over rows whose class ids
    are ``classes``: +1 where the id is ``t`` (label sets, a trailing axis
    of ids beside ``mask``'s: where ``t`` is among the row's ids), -1
    elsewhere, 0 on padding (``mask``), in ``mask``'s dtype.  ``t`` may be
    traced, or an array that broadcasts against ``mask`` (a leading class
    axis gives every class's labels at once)."""
    if classes.ndim > mask.ndim:
        hit = (classes == jnp.asarray(t, classes.dtype)[..., None]).any(-1)
    else:
        hit = classes == t
    return jnp.where(hit, 1.0, -1.0).astype(mask.dtype) * mask


# the class axis on the LANES (sparse rows, ops/pallas_sparse_lanes.py): T
# class models are held T_pad wide, whole (8, 128) float32 tiles, and kept
# AS tiles: W is (d, R, 128) and alpha (K, n_shard, R, 128), R = T_pad / 128,
# class t at [t // 128, t % 128].  A TPU tiles an array's last two axes, so
# this is the shape in which one column's T values, or one row's, are one
# contiguous 4 KB run of HBM (a (d, T_pad) array is tiled over 8 columns)
CLASS_TILE = 1024
CLASS_LANES = 128


def class_pad(num_classes: int) -> int:
    """T_pad: ``num_classes`` rounded up to whole CLASS_TILE-lane tiles."""
    return -(-int(num_classes) // CLASS_TILE) * CLASS_TILE


def class_tile_shape(num_classes: int) -> tuple:
    """(R, 128): the trailing axes that hold T_pad class values."""
    return (class_pad(num_classes) // CLASS_LANES, CLASS_LANES)


def class_vector(tiles: jax.Array, num_classes: int) -> jax.Array:
    """The (.., T) values of the real classes out of (.., R, 128) tiles."""
    return tiles.reshape(tiles.shape[:-2] + (-1,))[..., :num_classes]


def label_sets(classes: jax.Array, rows_ndim: int) -> jax.Array:
    """``classes`` with the slot axis it may lack: (.., L) ids for rows of
    ``rows_ndim`` leading axes (one class id a row is the set of size
    one)."""
    return classes if classes.ndim > rows_ndim else classes[..., None]


def class_signs(ids: jax.Array, num_classes: int, dtype) -> jax.Array:
    """y with the class axis LAST, as tiles: for rows whose label sets are
    ``ids`` (.., L) (:func:`label_sets`; -1 fills a short set), the
    (.., R, 128) array that is +1 where class t is among the row's ids and
    -1 elsewhere.  A padding row is all -1: the caller's mask leaves it
    out."""
    shape = class_tile_shape(num_classes)
    lanes = jnp.arange(shape[0] * shape[1], dtype=ids.dtype).reshape(shape)
    return jnp.where((ids[..., None, None] == lanes).any(-3), 1.0,
                     -1.0).astype(dtype)


try:
    jax.tree_util.register_pytree_node(
        ShardedDataset, ShardedDataset.tree_flatten, ShardedDataset.tree_unflatten
    )
except ValueError:
    pass  # already registered (module re-imported/reloaded)


# --- the stream storage ------------------------------------------------------
# The rectangle (K, n_shard, W) pads every row to the longest: fine where
# rows are short and even (kddb: W = 64 over a mean of 29), impossible where
# the longest row is many times the mean (webspam: 350,000 x 32,768 slots is
# 92 GB for 10 GB of nonzeros).  The stream keeps a shard's rows end to end.
STREAM_ALIGN = 8                 # slots a row's start is aligned to (the
                                 # kernels' slot group, ops/pallas_longrows)
STREAM_PIECE = 128               # slots a piece: one full lane row
STREAM_SPARE_PIECES = 8          # past a shard's last row: a kernel's last
                                 # chunk of a row may read this far
STREAM_MIN_MEAN = 96             # rows this long on average, and
STREAM_RECTANGLE_RATIO = 2.0     # a rectangle this many times the stream


def stream_row_slots(row_nnz) -> np.ndarray:
    """Slots each row takes in the stream: its nonzeros up to the next
    STREAM_ALIGN boundary."""
    row_nnz = np.asarray(row_nnz, np.int64)
    return -(-row_nnz // STREAM_ALIGN) * STREAM_ALIGN


def stream_suits(row_nnz, itemsize: int = 4) -> bool:
    """Whether rows of these lengths are kept as a stream: from the lengths
    the loader observes alone.  Rows of a hundred nonzeros or more on
    average (the mean past STREAM_MIN_MEAN: a step's work is then mostly
    its nonzeros, not its fetch — the kernels' DMA ring runs across rows,
    so a row shorter than a chunk does not wait for its own — and the
    stream's own padding is under 4%) that the rectangle would at least
    double: url (115.6 a row, the longest 4 x that: 9.8 GB as a rectangle,
    2.3 as a stream) as much as webspam (3,727).  Short-rowed sets (kddb:
    29 a row, a rectangle 1.9 x its stream; rcv1: 73; every small test set)
    keep the rectangle and with it their bytes, kernels and trajectories.
    The three points the gate sits between and what each read on the v5e:
    PERF.md §6, PR 41.  float32 only: the stream's passes are the kernels
    of ops/pallas_longrows.py."""
    row_nnz = np.asarray(row_nnz, np.int64)
    if itemsize != 4 or not row_nnz.size:
        return False
    rectangle = row_nnz.size * int(row_nnz.max(initial=1))
    return (row_nnz.mean() >= STREAM_MIN_MEAN and rectangle
            > STREAM_RECTANGLE_RATIO * int(stream_row_slots(row_nnz).sum()))


def stream_pieces(slots_per_shard) -> int:
    """Pieces a shard's stream array holds for the fullest shard's
    ``slots_per_shard``: whole (8, 128) tiles, STREAM_SPARE_PIECES spare."""
    need = -(-int(np.max(slots_per_shard)) // STREAM_PIECE)
    return -(-(need + STREAM_SPARE_PIECES) // 8) * 8


def stream_row_iota(longest: int, k: int) -> np.ndarray:
    """``sp_row_iota`` for a longest row of ``longest`` nonzeros."""
    w = max(STREAM_ALIGN, -(-int(longest) // STREAM_ALIGN) * STREAM_ALIGN)
    return np.tile(np.arange(w, dtype=np.int32)[None], (k, 1))


def _build_stream_slab(data, lo, hi, n_shard, n_pieces, np_dtype,
                       row_nnz) -> dict:
    """Rows [lo, hi) of ``data`` as one shard's stream arrays."""
    m = hi - lo
    nnz = np.asarray(row_nnz[lo:hi], np.int64)
    first = np.concatenate([[0], np.cumsum(stream_row_slots(nnz))])[:m]
    a, b = data.indptr[lo], data.indptr[hi]
    at = (np.repeat(first, nnz) + np.arange(a, b)
          - np.repeat(np.asarray(data.indptr[lo:hi], np.int64), nnz))
    spi = np.zeros(n_pieces * STREAM_PIECE, np.int32)
    spv = np.zeros(n_pieces * STREAM_PIECE, np_dtype)
    spi[at] = data.indices[a:b]
    spv[at] = data.values[a:b]
    ptr = np.zeros(n_shard, np.int32)
    ptr[:m] = first // STREAM_ALIGN
    length = np.zeros(n_shard, np.int32)
    length[:m] = nnz
    return dict(sp_indices=spi.reshape(n_pieces, STREAM_PIECE),
                sp_values=spv.reshape(n_pieces, STREAM_PIECE),
                sp_row_ptr=ptr, sp_row_len=length)


# Ordering a shard's rows sorts each of its (n_shard, c) row arrays with the
# lengths as the key: the sort's operands and results are the shard's rows
# twice over.  Row arrays past this size on one device are ordered a shard at
# a time into the donated array, so that the whole set is never held twice
# (kddb: 4.9 GB an array beside 16.9 GB of HBM; a shard's is 0.6 GB);
# smaller ones, and arrays spread over a mesh, all shards at once.
ORDER_AT_ONCE_BYTES = 1 << 30


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _order_rows(a, key, first, shards: int):
    """Rows of shards [first, first + shards) of ``a`` (K, n_shard, c) put
    in ascending order of ``key`` (shards, n_shard), stably, in place."""
    from jax import lax

    part = lax.dynamic_slice_in_dim(a, first, shards, 0)
    _, part = lax.sort(
        (jnp.broadcast_to(key[:, :, None], part.shape), part),
        dimension=1, num_keys=1, is_stable=True)
    return lax.dynamic_update_slice_in_dim(a, part, first, 0)


@jax.jit
def _order_row_scalars(row_len, width, scalars):
    """The stable descending-length order of every shard's rows, (K,
    n_shard) int32, its sort key, and the (K, n_shard) ``scalars`` in it."""
    from jax import lax

    key = width - row_len
    _, order = lax.sort(
        (key, lax.broadcasted_iota(jnp.int32, key.shape, 1)), dimension=1,
        num_keys=1, is_stable=True)
    return order, key, [jnp.take_along_axis(a, order, 1) for a in scalars]


def order_rows_by_length(ds: "ShardedDataset") -> "ShardedDataset":
    """Put each shard's rows of a sparse dataset in descending order of
    length (ops/pallas_sparse.row_lengths), IN PLACE: ``ds``'s row fields
    are rebound to the ordered arrays, the row arrays made in their donated
    buffers (any other reference to them dies), and ``ds.row_order`` says
    where each row was.  Stable, and a padding row is as short as a row
    gets, so padding rows stay last and ``counts`` keeps its meaning.  A
    dataset that has a ``row_order`` is returned as it is.

    Which rows a shard holds does not change, so neither does anything
    CoCoA computes from a shard as a set: the objectives, the certificate,
    w(α).  What the order buys: rows of one length share a row block, and
    the all-rows passes that run in row blocks stop at a block's longest
    row (ops/rows.SLOT_GROUP).  α, and anything else kept by row, is in the
    dataset's order from here on; :func:`rows_as_built` maps it back."""
    if (ds.layout != "sparse" or ds.row_order is not None
            or ds.sp_row_ptr is not None):
        return ds               # (a stream's passes go by nonzeros as it is)
    from cocoa_tpu.ops.pallas_sparse import row_lengths

    row_len = getattr(ds, "_row_len_cache", None)
    if row_len is None:
        row_len = row_lengths(ds.sp_values)
    order, key, (row_len, ds.labels, ds.mask, ds.sq_norms) = \
        _order_row_scalars(row_len, ds.sp_values.shape[-1],
                           [row_len, ds.labels, ds.mask, ds.sq_norms])
    if ds.classes is not None:
        # the class ids (a row's label set) go with their rows
        ds.classes = jnp.take_along_axis(
            ds.classes, order.reshape(order.shape + (1,) * (
                ds.classes.ndim - 2)), 1)
    for name in ("sp_indices", "sp_values", "X_hot", "X_eval"):
        a = getattr(ds, name)
        if a is None:
            continue
        at_once = (a.nbytes <= ORDER_AT_ONCE_BYTES
                   or len(a.sharding.device_set) > 1)
        step = ds.k if at_once else 1
        for first in range(0, ds.k, step):
            a = _order_rows(a, key[first:first + step], first, step)
        setattr(ds, name, a)
    ds.row_order = order
    ds._row_len_cache = row_len
    return ds


def rows_of_one_length(ds: "ShardedDataset") -> bool:
    """Whether every row of a padded-CSR rectangle has the same count of
    nonzeros (click logs: one nonzero a field), as its loader noted from
    the lengths it saw on the host (:func:`note_row_lengths`); a dataset
    nobody noted anything on answers no and is ordered as ever."""
    return bool(getattr(ds, "_one_length", False))


def passes_want_order(ds: "ShardedDataset") -> bool:
    """Where :func:`order_rows_by_length` moves a number and has not run: a
    sparse dataset whose all-rows passes run in row blocks
    (ops/rows.row_block, from the shapes alone) and whose rows differ in
    length — rows of one length have nothing to order: every block's
    longest row is every row, the stable sort is the identity, and it
    would relay the whole rectangle to say so."""
    from cocoa_tpu.ops import rows

    return (ds.layout == "sparse" and ds.row_order is None
            and ds.sp_row_ptr is None and rows.row_block(
            ds.n_shard, ds.sp_indices.shape[-1]) < ds.n_shard
            and not rows_of_one_length(ds))


def order_rows_for_passes(ds: "ShardedDataset") -> "ShardedDataset":
    """:func:`order_rows_by_length` where :func:`passes_want_order`.  A set
    that one block holds (rcv1, every small set) keeps its rows as built,
    and its runs stay what they were bit for bit."""
    if passes_want_order(ds):
        order_rows_by_length(ds)
    return ds


def _on_host(a) -> np.ndarray:
    """``a`` whole on this host: a multi-process run gathers its shards, as
    ``checkpoint.save`` does with α."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.experimental import multihost_utils

        a = multihost_utils.process_allgather(a, tiled=True)
    return np.asarray(a)


def rows_as_built(ds: "ShardedDataset", by_row) -> np.ndarray:
    """A (.., K, n_shard) array kept by row in ``ds``'s order (α, the
    ``--accel`` window bank), by the rows' positions as the shards were
    built: what a checkpoint stores, so that it resumes under a freshly
    built dataset whatever order that one keeps its rows in."""
    a = _on_host(by_row)
    if ds.row_order is None:
        return a
    out = np.empty_like(a)
    np.put_along_axis(out, np.broadcast_to(_on_host(ds.row_order), a.shape),
                      a, axis=-1)
    return out


def rows_as_ordered(ds: "ShardedDataset", as_built) -> np.ndarray:
    """The inverse of :func:`rows_as_built`: a checkpoint's by-row array in
    ``ds``'s order.  A shard axis shorter than ``n_shard`` (a checkpoint from
    before a larger padding) is zero-padded first, as ``align_alpha`` does."""
    a = np.asarray(as_built)
    if ds.row_order is None or a.shape[-1] > ds.n_shard:
        return a        # too long a shard axis: ``align_alpha`` says so
    if a.shape[-1] < ds.n_shard:
        a = np.pad(a, [(0, 0)] * (a.ndim - 1)
                   + [(0, ds.n_shard - a.shape[-1])])
    return np.take_along_axis(
        a, np.broadcast_to(_on_host(ds.row_order), a.shape), axis=-1)


def _densify_rows(data, lo, hi, n_shard, d, np_dtype, row_nnz) -> np.ndarray:
    """Rows [lo, hi) of the CSR ``data`` as a zero-padded (n_shard, d)
    dense slab — the one CSR→dense scatter shared by the dense layout,
    the distributed per-shard builder, and the eval twin."""
    a, b = data.indptr[lo], data.indptr[hi]
    rows = np.repeat(np.arange(hi - lo), row_nnz[lo:hi])
    X = np.zeros((n_shard, d), np_dtype)
    X[rows, data.indices[a:b]] = data.values[a:b]
    return X


def _build_shard_slabs(data, lo, hi, n_shard, layout, np_dtype, d, width,
                       row_nnz, row_sq, *, rank=None, n_hot=0,
                       eval_dense=False, stream=False) -> dict:
    """One shard's COMPLETE padded host arrays (rows [lo, hi) of
    ``data``): labels/mask/sq_norms plus the layout slabs — dense X,
    plain padded-CSR, or (``n_hot > 0``) the hybrid hot panel + cold
    residual — plus the optional dense eval twin.  The ONE slab builder
    shared by the replicated, whole-file-distributed, and streaming
    ingest paths, so every build produces bit-identical shards from the
    same parsed rows.  ``lo``/``hi`` and the ``row_nnz``/``row_sq``
    arrays index into ``data`` — streaming callers pass a range-parsed
    PIECE with piece-relative bounds."""
    m = hi - lo
    labels = np.zeros(n_shard, np_dtype)
    labels[:m] = data.labels[lo:hi]
    mask = np.zeros(n_shard, np_dtype)
    mask[:m] = 1.0
    sq = np.zeros(n_shard, np_dtype)
    sq[:m] = row_sq[lo:hi]
    out = dict(labels=labels, mask=mask, sq_norms=sq)
    if layout == "dense":
        out["X"] = _densify_rows(data, lo, hi, n_shard, d, np_dtype, row_nnz)
    elif n_hot:
        from cocoa_tpu.data import hybrid

        X_hot, spi, spv = hybrid.split_slab(data, lo, hi, n_shard, rank,
                                            n_hot, width, np_dtype)
        out["X_hot"] = X_hot
        out["sp_indices"] = spi
        out["sp_values"] = spv
    elif stream:
        # ``width`` is the stream's piece count (:func:`stream_pieces`)
        out.update(_build_stream_slab(data, lo, hi, n_shard, width, np_dtype,
                                      row_nnz))
    else:
        a, b = data.indptr[lo], data.indptr[hi]
        rows = np.repeat(np.arange(m), row_nnz[lo:hi])
        cols = np.arange(a, b) - np.repeat(data.indptr[lo:hi], row_nnz[lo:hi])
        spi = np.zeros((n_shard, width), np.int32)
        spv = np.zeros((n_shard, width), np_dtype)
        spi[rows, cols] = data.indices[a:b]
        spv[rows, cols] = data.values[a:b]
        out["sp_indices"] = spi
        out["sp_values"] = spv
    if eval_dense:
        out["X_eval"] = _densify_rows(data, lo, hi, n_shard, d, np_dtype,
                                      row_nnz)
    return out


def _assemble_distributed(mesh, k, built, locals_, *, layout, n, d,
                          n_shard, width, sizes, n_hot, hot_ids,
                          eval_dense, np_dtype) -> ShardedDataset:
    """Assemble the global (K, ...) sharded arrays from per-device
    (m, ...) slab stacks (``jax.make_array_from_single_device_arrays``):
    ``built`` maps shard id → slab dict for THIS process's shards only,
    ``locals_`` is the :func:`cocoa_tpu.parallel.mesh.dp_local_shards`
    placement.  Shared by the whole-file distributed builder and
    streaming ingest — the same assembly regardless of how the rows were
    parsed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def assemble(field, trailing, synth=None):
        sh = NamedSharding(mesh,
                           P(mesh_lib.DP_AXIS, *([None] * len(trailing))))
        pieces = [
            jax.device_put(
                np.stack([built[s][field] for s in range(lo, hi)])
                if synth is None else np.tile(synth[None], (hi - lo, 1)),
                dev)
            for dev, lo, hi in locals_
        ]
        return jax.make_array_from_single_device_arrays(
            (k, *trailing), sh, pieces
        )

    kwargs: dict = {}
    if layout == "dense":
        kwargs["X"] = assemble("X", (n_shard, d))
    else:
        kwargs["sp_indices"] = assemble("sp_indices", (n_shard, width))
        kwargs["sp_values"] = assemble("sp_values", (n_shard, width))
        if n_hot:
            # panel lanes past the real hot count carry column id 0 and
            # all-zero values — inert, the standing padding trick
            hc = np.zeros(n_hot, dtype=np.int32)
            hc[:len(hot_ids)] = hot_ids
            kwargs["X_hot"] = assemble("X_hot", (n_shard, n_hot))
            kwargs["hot_cols"] = assemble("hot_cols", (n_hot,), synth=hc)
        if eval_dense:
            kwargs["X_eval"] = assemble("X_eval", (n_shard, d))
    return ShardedDataset(
        layout=layout,
        n=n,
        num_features=d,
        counts=sizes.astype(np.int64),
        labels=assemble("labels", (n_shard,)),
        mask=assemble("mask", (n_shard,)),
        sq_norms=assemble("sq_norms", (n_shard,)),
        **kwargs,
    )


def _slab_view(cache, layout, k, n_shard, width, n_hot, d, np_dtype,
               eval_dense):
    """The fully-resolved layout's slab-cache view, or None when no
    ``--ingestCache`` handle rides the build (data/slab_cache.py)."""
    if cache is None:
        return None
    return cache.view(layout=layout, k=k, n_shard=n_shard, width=width,
                      n_hot=n_hot, d=d, dtype=np_dtype,
                      eval_dense=eval_dense)


def _cached_or_built(view, s, build):
    """One shard through the optional slab-cache view: a valid cached
    artifact wins (zero build), a miss builds and publishes (atomic
    rename, one writer wins) — the whole-path twin of the per-shard
    logic in data/ingest._stream_build."""
    if view is not None:
        slab = view.load(s)
        if slab is not None:
            return slab
    slab = build()
    if view is not None:
        view.store(s, slab)
    return slab


def _shard_dataset_distributed(data, k, layout, np_dtype, mesh, sizes,
                               offsets, n_shard, d, width, row_nnz,
                               row_sq, *, rank=None, n_hot=0,
                               hot_ids=None,
                               eval_dense=False,
                               cache_view=None) -> ShardedDataset:
    """Multi-process assembly from a WHOLE-parsed dataset: each process
    materializes ONLY the shards whose dp mesh position is one of its own
    devices — m = K/D consecutive logical shards per device when the mesh
    is multiplexed (D < K, the Spark ``coalesce`` analogue) — then the
    global (K, ...) arrays are assembled from the per-device (m, ...)
    stacks.  Per-process host memory stays ~1/P of the padded layout
    instead of P full copies (VERDICT r1 item 5; the reference reads only
    local HDFS blocks per executor, OptUtils.scala:14) — though every
    process still parses the whole file here; ``--ingest=stream``
    (data/ingest.py) removes that last full-dataset pass too.  The hybrid
    hot/cold split and the dense eval twin build per shard exactly as on
    the replicated path.  dp-only meshes (the fp extension keeps the
    replicated-assembly path)."""
    locals_ = mesh_lib.dp_local_shards(mesh, k)
    built = {
        s: _cached_or_built(
            cache_view, s,
            lambda s=s: _build_shard_slabs(
                data, offsets[s], offsets[s + 1], n_shard, layout,
                np_dtype, d, width, row_nnz, row_sq, rank=rank,
                n_hot=n_hot, eval_dense=eval_dense))
        for _, lo, hi in locals_ for s in range(lo, hi)
    }
    return _assemble_distributed(mesh, k, built, locals_, layout=layout,
                                 n=data.n, d=d, n_shard=n_shard,
                                 width=width, sizes=sizes, n_hot=n_hot,
                                 hot_ids=hot_ids, eval_dense=eval_dense,
                                 np_dtype=np_dtype)


def shard_dataset(
    data: LibsvmData,
    k: int,
    layout: str = "auto",
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
    max_nnz: Optional[int] = None,
    eval_dense: bool = False,
    hot_cols: int = 0,
    cache=None,
    rectangle: bool = False,
) -> ShardedDataset:
    """Partition ``data`` into K balanced contiguous shards and device_put them.

    ``layout="auto"`` picks sparse when the density nnz/(n*d) is below 10%
    (rcv1-like) and dense otherwise (epsilon-like).

    ``eval_dense=True`` (sparse layout only) additionally materializes a
    dense (K, n_shard, d) twin consumed ONLY by evaluation
    (ops/rows.eval_margins): the duality-gap certificate's full margins
    pass is then one MXU matvec instead of an every-nonzero w-gather.
    Measured through the production device-loop path at rcv1 scale
    (debugIter=25): 9.42 -> 6.46 ms/round — the gather-based eval was 31%
    of the round time.  Opt-in because the twin costs K·n_shard·d·itemsize of HBM
    (~3.8 GB at rcv1 scale); training paths never touch it.

    ``hot_cols > 0`` (sparse layout only; flag ``--hotCols``) builds the
    HYBRID layout (data/hybrid.py): a dense (K, n_shard, hot_cols) panel
    over the globally hottest columns — chosen once from the column
    histogram — plus the cold-residual padded-CSR.  The split partitions
    each row's nonzeros by column, so every consumer's per-row sum is a
    permutation of the unsplit one (docs/DESIGN.md §3b-vi).

    Multi-process runs (``jax.process_count() > 1`` with a dp mesh)
    materialize only each process's own shards host-side — see
    :func:`_shard_dataset_distributed`.

    ``cache`` (an optional ``slab_cache.FileCacheHandle``,
    ``--ingestCache``) serves each shard from its persistent slab
    artifact when present and publishes every shard built cold — the
    whole-file path's half of the docs/DESIGN.md §18 cache contract
    (the zero-parse warm path lives in data/ingest.load_cached_dataset;
    here the parse is already paid, so a hit saves the slab build and a
    miss populates for the next process).

    ``rectangle=True``: the caller runs a solver that reads sparse rows as
    the (K, n_shard, W) rectangle alone (the primal baselines: the CLI
    under ``--justCoCoA=false``), so the rows stay one whatever their
    lengths;
    by default the lengths decide (:func:`stream_suits`).
    """
    n, d = data.n, data.num_features
    layout = resolve_layout(data, layout, mesh)
    if layout == "sparse" and mesh_lib.has_fp(mesh):
        # padded-CSR rows index the full feature space; splitting them over
        # fp would need per-device re-bucketing of each row's nnz (ragged) —
        # use the dense layout for feature-parallel runs
        raise ValueError(
            "feature-axis (fp) sharding requires layout='dense'; the "
            "padded-CSR layout cannot column-partition"
        )

    np_dtype = np.dtype(dtype)
    sizes = split_sizes(n, k)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    n_shard = pad_rows(int(sizes.max())) if k > 0 else 0

    row_nnz = np.diff(data.indptr)
    row_sq = segment_sq_norms(data.values, data.indptr)
    width = 0
    if layout == "sparse":
        width = int(max_nnz if max_nnz is not None
                    else rectangle_width(row_nnz.max(initial=1)))
        if n and int(row_nnz.max(initial=0)) > width:
            raise ValueError(
                f"row nnz {int(row_nnz.max())} exceeds max_nnz {width}"
            )

    # rows of a hundred nonzeros or of thousands, the longest a few times
    # the mean: kept as a stream, not padded to the longest
    # (:func:`stream_suits`: from the lengths alone; a single process's
    # plain sparse layout only: the hybrid split, a forced width, the
    # multi-process assembly and a caller that asks for it keep the
    # rectangle)
    stream = (layout == "sparse" and not hot_cols and max_nnz is None
              and not rectangle
              and not (mesh is not None and jax.process_count() > 1)
              and stream_suits(row_nnz, np_dtype.itemsize))
    if stream:
        width = stream_pieces([stream_row_slots(
            row_nnz[offsets[s]:offsets[s + 1]]).sum() for s in range(k)])
        cache = None            # the slab cache keys rectangles

    hot_ids = None
    rank = None
    n_hot = 0
    if hot_cols:
        from cocoa_tpu.data import hybrid

        if layout != "sparse":
            raise ValueError("hot_cols (the hot/cold column split) only "
                             "applies to the sparse layout")
        if max_nnz is not None:
            raise ValueError("hot_cols and max_nnz cannot combine: the "
                             "residual width is measured from the split")
        n_hot = hybrid.pad_panel(min(int(hot_cols), d))
        # the hot set derives from the same deterministic
        # hottest_columns(column_counts(data), n) that resolve_hot_cols
        # measured, so the manifest's split stats describe THIS layout
        # (lockstep pinned by tests/test_hybrid_sparse.py)
        hot_ids = hybrid.hottest_columns(hybrid.column_counts(data), n_hot)
        rank = hybrid.hot_rank(d, hot_ids)
        # the residual padded-CSR width is the max COLD nnz across rows —
        # the whole point: the stream kernels' padded width drops to the
        # tail's max, not the full row's
        cold_rows = np.repeat(np.arange(n, dtype=np.int64),
                              row_nnz)[rank[data.indices] < 0]
        resid_max = int(np.bincount(cold_rows, minlength=max(1, n))
                        .max(initial=0))
        width = max(1, resid_max)
        if cache is not None:
            # the measured residual width is what keys the hybrid shard
            # artifacts — persist it so a warm run (data/ingest.py
            # load_cached_dataset) resolves the SAME width with no parse
            cache.store_hybrid_meta(n_hot, resid_max)

    if eval_dense and layout != "sparse":
        raise ValueError("eval_dense only applies to the sparse layout "
                         "(the dense layout's eval is already a matvec)")
    if (
        mesh is not None
        and jax.process_count() > 1
        and not mesh_lib.has_fp(mesh)
    ):
        if getattr(data, "num_classes", 1) > 1:
            raise ValueError(
                "a multi-class set (class ids beside the labels) is built "
                "by one process: the per-process shard builder does not "
                "carry the class ids (one-vs-rest runs on one chip, "
                "docs/DESIGN.md)")
        if k % mesh.devices.size != 0:
            # the multiplexed distributed builder stacks m = K/D shards
            # per device; a non-divisor D has no even placement — the same
            # rule fanout.shards_per_device enforces for the solvers, and
            # the divisibility contract the elastic supervisor's
            # shrink-to-survivors path resolves gang sizes against
            # (elastic.shrink_gang_size: a reformed gang is always a
            # divisor, so a post-failure relaunch can never trip this)
            raise ValueError(
                f"multi-process runs need numSplits divisible by the dp "
                f"mesh size: K={k} shards cannot multiplex onto "
                f"{mesh.devices.size} devices"
            )
        d_eff = mesh_lib.pad_features(d, mesh) if layout == "dense" else d
        return order_rows_for_passes(note_row_lengths(
            _shard_dataset_distributed(
                data, k, layout, np_dtype, mesh, sizes, offsets, n_shard,
                # mirror the replicated path: only the dense layout pads d
                d_eff,
                width, row_nnz, row_sq, rank=rank, n_hot=n_hot,
                hot_ids=hot_ids, eval_dense=eval_dense,
                cache_view=_slab_view(cache, layout, k, n_shard, width,
                                      n_hot, d_eff, np_dtype, eval_dense),
            ), row_nnz))

    if layout == "dense":
        d = mesh_lib.pad_features(d, mesh)
    view = _slab_view(cache, layout, k, n_shard, width, n_hot, d,
                      np_dtype, eval_dense)
    arrs: dict = {}
    for s in range(k):
        slab = _cached_or_built(
            view, s,
            lambda s=s: _build_shard_slabs(
                data, offsets[s], offsets[s + 1], n_shard, layout,
                np_dtype, d, width, row_nnz, row_sq, rank=rank,
                n_hot=n_hot, eval_dense=eval_dense, stream=stream))
        for f, v in slab.items():
            arrs.setdefault(f, np.zeros((k, *v.shape), v.dtype))[s] = v
    if n_hot:
        # panel lanes past the real hot count (d < n_hot after lane
        # padding) carry column id 0 and all-zero values — inert in
        # every gather and scatter, the standing padding trick
        hc = np.zeros(n_hot, dtype=np.int32)
        hc[:len(hot_ids)] = hot_ids
        arrs["hot_cols"] = np.tile(hc[None], (k, 1))
    if stream:
        arrs["sp_row_iota"] = stream_row_iota(row_nnz.max(initial=1), k)
    num_classes = int(getattr(data, "num_classes", 1))
    if num_classes > 1:
        # beside the labels, not through the slab cache: ids by row
        # (label sets: a trailing axis of ids, -1 where a set is shorter;
        # a padding row has no label)
        sets = np.ndim(data.classes) == 2
        arrs["classes"] = np.full(
            (k, n_shard) + np.shape(data.classes)[1:], -1 if sets else 0,
            np.int32)
        for s in range(k):
            arrs["classes"][s, :sizes[s]] = \
                data.classes[offsets[s]:offsets[s + 1]]
    return order_rows_for_passes(note_row_lengths(_finalize_replicated(
        arrs, layout=layout, n=n, d=d, mesh=mesh, sizes=sizes,
        num_classes=num_classes), row_nnz))


def _finalize_replicated(arrs, *, layout, n, d, mesh, sizes, num_classes=1
                         ) -> ShardedDataset:
    """device_put the stacked (K, ...) host arrays and wrap them — the
    tail of every single-process build (replicated whole-file and
    streaming alike)."""
    def put(arr, fp_last=False):
        if arr is None:
            return None
        if mesh is not None:
            if fp_last:
                return jax.device_put(arr, mesh_lib.x_sharding(mesh))
            return jax.device_put(
                arr, mesh_lib.sharded_rows(mesh, extra_dims=arr.ndim - 1)
            )
        return jnp.asarray(arr)

    return ShardedDataset(
        layout=layout,
        n=n,
        num_features=d,
        counts=sizes.astype(np.int64),
        labels=put(arrs["labels"]),
        mask=put(arrs["mask"]),
        sq_norms=put(arrs["sq_norms"]),
        X=put(arrs.get("X"), fp_last=True) if "X" in arrs else None,
        sp_indices=put(arrs.get("sp_indices")),
        sp_values=put(arrs.get("sp_values")),
        X_eval=put(arrs.get("X_eval")),
        X_hot=put(arrs.get("X_hot")),
        hot_cols=put(arrs.get("hot_cols")),
        sp_row_ptr=put(arrs.get("sp_row_ptr")),
        sp_row_len=put(arrs.get("sp_row_len")),
        sp_row_iota=put(arrs.get("sp_row_iota")),
        classes=put(arrs.get("classes")),
        num_classes=num_classes,
    )
