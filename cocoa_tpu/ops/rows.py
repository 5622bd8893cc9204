"""Row access over the shard layouts (dense / padded-CSR / hybrid).

The sequential local solvers touch one example per step: a row gather, one or
two dots against d-vectors, and a scaled row-axpy back into d-vectors
(CoCoA.scala:157-185 shape).  These helpers give that per-row contract a
layout-independent form:

- dense: the row is a (d,) slice; dot is an O(d) dense dot; axpy is dense add.
- sparse: the row is (max_nnz,) index/value arrays; dot is gather+reduce;
  axpy is scatter-add.  Padded slots carry index 0 / value 0, so they
  contribute exactly 0 to every dot and axpy — no masking needed.
- hybrid (the hot/cold column split, data/hybrid.py ``--hotCols``): the row
  additionally carries its dense (n_hot,) hot-panel slice; dot and axpy add
  the panel term through the ``hot_cols`` lane→column map.  Columns
  partition between panel and residual, so hot + cold is a permutation of
  the unsplit per-nonzero sum — identical real arithmetic, fp reassociated
  (docs/DESIGN.md §3b-vi).

Layout choice is static (Python-level), so each jit specialization contains
only its own code path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.custom_batching


class Row(NamedTuple):
    """One example's features, in whichever layout the shard uses."""

    dense: Optional[jax.Array] = None    # (d,)
    idx: Optional[jax.Array] = None      # (max_nnz,) int32
    val: Optional[jax.Array] = None      # (max_nnz,)
    hot: Optional[jax.Array] = None      # hybrid: (n_hot,) panel values
    hot_cols: Optional[jax.Array] = None  # hybrid: (n_hot,) int32 column ids


def get_row(shard: dict, i) -> Row:
    if "X" in shard:
        return Row(dense=jax.lax.dynamic_index_in_dim(shard["X"], i, 0, keepdims=False))
    if "sp_row_ptr" in shard:
        return _stream_row(shard, i)
    hot = hot_cols = None
    if "X_hot" in shard:
        hot = jax.lax.dynamic_index_in_dim(shard["X_hot"], i, 0,
                                           keepdims=False)
        hot_cols = shard["hot_cols"]
    return Row(
        idx=jax.lax.dynamic_index_in_dim(shard["sp_indices"], i, 0, keepdims=False),
        val=jax.lax.dynamic_index_in_dim(shard["sp_values"], i, 0, keepdims=False),
        hot=hot,
        hot_cols=hot_cols,
    )


def _stream_row(shard: dict, i) -> Row:
    """Row ``i`` of a stream shard (data/sharding.py, STREAM_ALIGN) in the
    padded-CSR form of the rectangle's rows, (W,) columns and values with W
    the set's longest row (``sp_row_iota``'s static width): a W-slot window
    of the stream from the row's first slot, what lies past its length set
    to column 0, value 0.  The window of a row near the stream's end starts
    early enough to fit and is rotated back.  The portable per-row path (the
    ``fori`` solve, the oracles); the kernels of ops/pallas_longrows.py read
    the stream as it is stored."""
    import jax.numpy as jnp
    from jax import lax

    from cocoa_tpu.data.sharding import STREAM_ALIGN

    iota = shard["sp_row_iota"]
    width = iota.shape[0]
    cols, vals = (shard[f].reshape(-1) for f in ("sp_indices", "sp_values"))
    first = shard["sp_row_ptr"][i] * STREAM_ALIGN
    start = jnp.minimum(first, cols.shape[0] - width)
    live = iota < shard["sp_row_len"][i]

    def window(a):
        win = jnp.roll(lax.dynamic_slice_in_dim(a, start, width),
                       start - first)
        return jnp.where(live, win, jnp.zeros_like(win))

    return Row(idx=window(cols), val=window(vals))


def _stream_interpret() -> bool:
    """The stream kernels run compiled on a TPU, interpreted anywhere else."""
    return jax.default_backend() != "tpu"


def _stream_margins_stacked(w, cols, vals, row_ptr, row_len):
    """x_i . w for the rows of stacked (K, ..) stream shards: one kernel
    over all of them, w loaded once."""
    from cocoa_tpu.ops import pallas_longrows

    shard = dict(sp_indices=cols, sp_values=vals, sp_row_ptr=row_ptr,
                 sp_row_len=row_len)
    return pallas_longrows.shard_margins(w, shard, _stream_interpret())


@jax.custom_batching.custom_vmap
def _stream_margins(w, cols, vals, row_ptr, row_len):
    """x_i . w for the rows of ONE stream shard; under ``vmap`` over shards
    (base.fanout) it stays the one kernel of the stacked form."""
    return _stream_margins_stacked(
        w, *(a[None] for a in (cols, vals, row_ptr, row_len)))[0]


@_stream_margins.def_vmap
def _stream_margins_vmapped(axis_size, in_batched, w, *rows):
    import jax.numpy as jnp

    if in_batched[0]:
        raise NotImplementedError("stream margins against a batch of w")
    stacked = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
               for a, b in zip(rows, in_batched[1:])]
    return _stream_margins_stacked(w, *stacked), True


def row_dot(row: Row, vec: jax.Array) -> jax.Array:
    """x · vec."""
    if row.dense is not None:
        return row.dense @ vec
    d = vec[row.idx] @ row.val
    if row.hot is not None:
        d = d + row.hot @ vec[row.hot_cols]
    return d


def row_axpy(row: Row, coef, vec: jax.Array) -> jax.Array:
    """vec + coef * x."""
    if row.dense is not None:
        return vec + coef * row.dense
    vec = vec.at[row.idx].add(coef * row.val)
    if row.hot is not None:
        # hot and cold columns are disjoint (the split partitions by
        # column), so the two scatters never race on a coordinate
        vec = vec.at[row.hot_cols].add(coef * row.hot)
    return vec


# a gather over all nonzeros of a shard makes a temporary the size of the
# shard's rows again (w[sp_indices] is (n_shard, W) floats): past this many
# slots the pass runs in blocks of rows, and the temporary is one block's.
# The size is measured (v5e, kddb's 1.2e9 slots, PR 26): a block of 2^20
# slots a shard gathers at 16.8 ns a slot and scatters at 9.8, any smaller
# block the same; blocks of 2^24 take 23 and 12.7 ns a slot.
GATHER_BLOCK_SLOTS = 1 << 20


def row_block(n_rows: int, width: int) -> int:
    """Rows per block of a pass over a padded-CSR shard's nonzeros (whole
    128-row lane tiles), or ``n_rows`` where one block holds them all."""
    if n_rows * width <= GATHER_BLOCK_SLOTS:
        return n_rows
    return max(128, GATHER_BLOCK_SLOTS // max(1, width) // 128 * 128)


# A gather of single elements from a d-vector that the program was HANDED
# (w as the device loop carries it: a buffer the runtime places) runs at one
# of two speeds on a v5e, by where that buffer happens to sit: 16.8 or 22.6
# ns an element at kddb (20.7 or 28 s a certificate eval; 12 of 26 placements
# slow, the same buffer always the same; PR 26).  The hot head of a Zipf
# column law is half of all reads, and it lands on few or many memory banks
# with the buffer's address.  So the big gathers read a copy made inside the
# program, with the vector cut into SPREAD_ROWS-element rows and transposed:
# neighbouring columns end up d / SPREAD_ROWS apart, and every run reads at
# one speed (24.9 s, eight placements, to the millisecond).  The values
# gathered are the same.
SPREAD_ROWS = 4096


def spread_table(vec: jax.Array) -> jax.Array:
    """``vec`` with element q·SPREAD_ROWS + r moved to r·C + q (C = the
    number of SPREAD_ROWS-element rows): read it at :func:`spread_index`."""
    import jax.numpy as jnp

    c = -(-vec.shape[0] // SPREAD_ROWS)
    return jnp.pad(vec, (0, SPREAD_ROWS * c - vec.shape[0])).reshape(
        c, SPREAD_ROWS).T.reshape(-1)


def spread_index(idx: jax.Array, d: int) -> jax.Array:
    """Where :func:`spread_table` of a d-vector keeps element ``idx``."""
    c = -(-d // SPREAD_ROWS)
    return (idx % SPREAD_ROWS) * c + idx // SPREAD_ROWS


# A block's rows sit on the lanes and their slots on the sublanes (how the
# device stores a (n, W) shard), so a block whose longest row has L nonzeros
# needs only its first ceil(L / SLOT_GROUP) sublane tiles: the block passes
# below take a block's slots a tile at a time and stop there.  That pays once
# the shard's rows are in length order (data/sharding.order_rows_by_length):
# in file order every 16,384-row block of kddb holds a 64-long row.
SLOT_GROUP = 8


@jax.custom_batching.custom_vmap
def _longest(row_len: jax.Array) -> jax.Array:
    """``row_len.max()``, which under ``vmap`` stays ONE number: the longest
    row of every batched shard's block.  A loop bounded by it keeps an
    unbatched trip count and counter (a batched one would turn the loop's
    slices into gathers); a shard whose own block is shorter reads slots
    that hold column 0, value 0."""
    return row_len.max()


@_longest.def_vmap
def _longest_of_all(axis_size, in_batched, row_len):
    del axis_size, in_batched
    return _longest(row_len), False


def _slot_groups(width: int) -> tuple:
    """``(slots a group, groups a row)`` of a W-slot padded-CSR row."""
    group = min(SLOT_GROUP, width)
    return group, -(-width // group)


def _group_trips(row_len, start, block: int, axis: int, width: int):
    """How many slot groups rows [start, start + block) fill: all of them
    where the lengths are not known."""
    from jax import lax

    group, n_groups = _slot_groups(width)
    if row_len is None:
        return n_groups
    longest = _longest(lax.dynamic_slice_in_dim(row_len, start, block, axis))
    return (longest + (group - 1)) // group


def _block_by_groups(a, start, block: int):
    """Rows [start, start + block) of a (.., n, W) padded-CSR array with
    their slots cut into groups and the rows last, as the device stores
    them: (groups, .., group, block), a short last group filled with zeros
    (column 0, value 0).  The block is taken whole, as one slice of the
    array in the layout it is stored in — a group sliced straight from the
    array inside the loop makes layout assignment copy the whole array
    slot-minor first, 2 x 9.2 GB at kddb — and streaming a block's padding
    costs nothing beside its gathers."""
    import jax.numpy as jnp
    from jax import lax

    width = a.shape[-1]
    group, n_groups = _slot_groups(width)
    part = lax.dynamic_slice_in_dim(a, start, block, a.ndim - 2)
    if n_groups * group != width:
        part = jnp.pad(part, [(0, 0)] * (a.ndim - 1)
                       + [(0, n_groups * group - width)])
    part = jnp.swapaxes(part, -1, -2)
    return jnp.moveaxis(
        part.reshape(part.shape[:-2] + (n_groups, group, block)), -3, 0)


def _margins_by_row_blocks(w: jax.Array, idx: jax.Array, val: jax.Array,
                           row_len=None) -> jax.Array:
    """x_i·w for the rows of an (n, W) padded-CSR shard, :func:`row_block`
    (< n) rows at a time (``lax.map``) and within a block one slot group at
    a time, as far as the block's longest row reaches (``row_len``: (n,)
    int32, ops/pallas_sparse.row_lengths; without it, every group).  The
    last block starts early enough to end on the last row; the rows it
    shares with its neighbour are taken from the neighbour."""
    import jax.numpy as jnp
    from jax import lax

    n, width = idx.shape
    block = row_block(n, width)
    nb = -(-n // block)
    ws, d = spread_table(w), w.shape[0]     # note above SPREAD_ROWS

    def one(b):
        start = jnp.minimum(b * block, n - block)
        cols = _block_by_groups(idx, start, block)
        vals = _block_by_groups(val, start, block)

        def add_group(g, m):
            i = lax.dynamic_index_in_dim(cols, g, 0, keepdims=False)
            v = lax.dynamic_index_in_dim(vals, g, 0, keepdims=False)
            return m + (ws[spread_index(i, d)] * v).sum(-2)

        # zeros LIKE a row of values: under shard_map the carry has to vary
        # over the mesh as what is added to it does
        return lax.fori_loop(0, _group_trips(row_len, start, block, 0, width),
                             add_group, jnp.zeros_like(vals[0, 0]))

    out = lax.map(one, jnp.arange(nb))                   # (nb, block)
    tail = n - (nb - 1) * block                          # rows only the last has
    return jnp.concatenate([out[:-1].reshape(-1), out[-1, block - tail:]])


def pass_slots(row_len, width: int, together: int = 0) -> int:
    """Slots one all-rows pass over (K, n, W) padded-CSR shards touches,
    counted from their (K, n) row lengths as the block passes run:
    ``together`` shards at a time share a loop bound (0: all K, the
    single-chip ``vmap``; on a mesh the shards of one device), and a block
    goes as far as the longest row it reads.  The rows the last block shares
    with its neighbour count as the neighbour's, so the count is K·n·W
    wherever every block holds a full-width row — and where one block holds
    a shard (:func:`row_block`), which takes the single pass."""
    import numpy as np

    k, n = row_len.shape
    block = row_block(n, width)
    if block >= n:
        return k * n * width
    group, _ = _slot_groups(width)
    nb = -(-n // block)
    starts = np.minimum(np.arange(nb) * block, n - block)
    own = np.full(nb, block)
    own[-1] = n - (nb - 1) * block
    lens = np.asarray(row_len).reshape(-1, together or k, n)
    longest = np.stack([lens[:, :, s:s + block].max(axis=(1, 2))
                        for s in starts], axis=1)        # (devices, blocks)
    trips = -(-longest.astype(np.int64) // group)
    return int((trips * own).sum()) * (together or k) * group


def shard_margins(w: jax.Array, shard: dict) -> jax.Array:
    """x_i·w for every row of one shard at once, shape (n_shard,).

    The batched counterpart of ``row_dot`` — on the dense layout a single
    MXU matvec; on padded-CSR a gather + reduction (padded slots contribute
    0); on the hybrid layout the residual gather-sum PLUS the hot panel as
    one MXU matvec against the gathered hot w slice.  Shared by the
    vectorized inner solver (ops/subgradient.py) and the fast-math margins
    pass so layout dispatch lives in one place.

    TRAINING-side: deliberately ignores the dense eval twin ``X_eval`` a
    sparse shard may carry — the twin's float summation order differs
    from the gather-sum, and every training path must stay bit-identical
    whether or not the twin exists (see :func:`eval_margins`).
    """
    if "X" in shard:
        return shard["X"] @ w
    idx, val = shard["sp_indices"], shard["sp_values"]
    if "sp_row_ptr" in shard:
        return _stream_margins(w, idx, val, shard["sp_row_ptr"],
                               shard["sp_row_len"])
    if row_block(*idx.shape) < idx.shape[0]:
        m = _margins_by_row_blocks(w, idx, val, shard.get("sp_row_len"))
    else:
        m = (w[idx] * val).sum(-1)
    if "X_hot" in shard:
        m = m + shard["X_hot"] @ w[shard["hot_cols"]]
    return m


def class_row_block(n_rows: int, t_pad: int) -> int:
    """Rows per block of an all-rows pass with a class axis ``t_pad`` wide
    on the lanes: a slot group's gather is (group, block, t_pad) values, so
    the block follows T — GATHER_BLOCK_SLOTS values a slot, 32 MB a group
    (1,024 rows at one class tile), where :func:`row_block`'s 2^20 slots
    would be a 4 GB temporary at 4 KB a slot."""
    return min(n_rows, max(128, GATHER_BLOCK_SLOTS // t_pad // 128 * 128))


def class_loss_sums(w: jax.Array, alpha, arrays: dict, classes: int,
                    loss: str, smoothing: float) -> jax.Array:
    """What the rows of (K, n_shard, W) padded-CSR shards add to T
    one-vs-rest certificates, from ONE pass over them in blocks of rows
    (:func:`class_row_block`), a shard after another: (3, R, 128) per-class
    sums over the real rows of the primal loss at the margins x_i.W (W (d,
    R, 128), the class axis on the lanes as tiles:
    data/sharding.class_tile_shape), of the dual term of ``alpha`` (K,
    n_shard, R, 128; None: zeros) and of the wrong signs y·margin <= 0.  A
    block's margins are T values a row, a W row a nonzero, a slot group at
    a time as far as the block's longest row reaches; y_ti comes from the
    rows' label sets (``arrays["classes"]``) and never exists T times
    over.  Every block is sliced from the whole arrays where it is used:
    nothing the size of a shard's alpha is ever copied."""
    import jax.numpy as jnp
    from jax import lax

    from cocoa_tpu.data.sharding import class_signs, label_sets
    from cocoa_tpu.ops import losses
    from cocoa_tpu.ops.pallas_sparse_hbm import rows_on_lanes

    idx, val, mask = (arrays[f] for f in ("sp_indices", "sp_values", "mask"))
    # (the label sets as the device stores them, the row index on the lanes)
    ids_t = jnp.swapaxes(label_sets(arrays["classes"], 2), -1, -2)
    row_len = arrays.get("sp_row_len")
    k, n, width = idx.shape
    tile = w.shape[1:]
    block = class_row_block(n, tile[0] * tile[1])
    nb = -(-n // block)
    # a block is sliced from the arrays in the layout the device stores
    # them in (the note at :func:`_block_by_groups`): rows on the lanes
    # where that pads less (kddb's W = 64), else row-major (W = 256), where
    # a group is 8 neighbouring slots of the block's rows
    on_lanes = rows_on_lanes(n, width)
    group, n_groups = _slot_groups(width)

    def one(t, sums):
        shard, b = t // nb, t % nb
        start = jnp.minimum(b * block, n - block)

        def rows(a, axis=1):
            """Rows [start, start + block) of shard ``shard`` of ``a``."""
            a = lax.dynamic_slice_in_dim(a, shard, 1, 0)
            return lax.dynamic_slice_in_dim(a, start, block, axis)[0]

        if on_lanes:
            cols, vals = (_block_by_groups(
                lax.dynamic_index_in_dim(a, shard, 0, keepdims=False),
                start, block) for a in (idx, val))
        else:
            cols, vals = (jnp.pad(
                rows(a), ((0, 0), (0, n_groups * group - width)))
                for a in (idx, val))

        def add_group(g, m):
            if on_lanes:
                i = lax.dynamic_index_in_dim(cols, g, 0, keepdims=False)
                v = lax.dynamic_index_in_dim(vals, g, 0, keepdims=False)
                return m + (w[i] * v[..., None, None]).sum(0)
            i = lax.dynamic_slice_in_dim(cols, g * group, group, 1)
            v = lax.dynamic_slice_in_dim(vals, g * group, group, 1)
            return m + (w[i] * v[..., None, None]).sum(1)

        trips = _group_trips(None if row_len is None else rows(row_len),
                             0, block, 0, width)
        m = lax.fori_loop(0, trips, add_group,
                          jnp.zeros((block,) + tile, w.dtype))
        # the last block starts early enough to end on the last row: the
        # rows it shares with its neighbour are the neighbour's
        own = (rows(mask) * (start + jnp.arange(block) >= b * block)
               )[:, None, None]
        ym = class_signs(rows(ids_t, 2).T, classes, w.dtype) * m
        primal = losses.primal(loss, ym, smoothing=smoothing)
        dual = (jnp.zeros_like(m) if alpha is None else
                losses.dual_term(loss, rows(alpha), smoothing=smoothing))
        return sums + jnp.stack([(primal * own).sum(0), (dual * own).sum(0),
                                 (jnp.where(ym <= 0, 1.0, 0.0) * own).sum(0)])

    return lax.fori_loop(0, k * nb, one, jnp.zeros((3,) + tile, w.dtype))


# The largest temporary an all-rows pass over DENSE rows with a class axis
# on the lanes may hold: a block's margins are (block, T_pad) float32, and
# the loss values and the dual terms as much again.  Under it the pass is
# one block a shard (a small set's certificate is one product); over it the
# rows go by blocks: 16,384 rows at one class tile, where a shard of
# 40,037 rows x 1,024 lanes would hold three 164 MB temporaries and K
# shards at once three of 1.3 GB.
DENSE_CLASS_BLOCK_BYTES = 64 << 20


def dense_class_row_block(n_rows: int, t_pad: int, itemsize: int = 4) -> int:
    """Rows per block of :func:`dense_class_loss_sums`: a shard whole where
    its (n_rows, t_pad) margins stay under ``DENSE_CLASS_BLOCK_BYTES``,
    else as many rows, in whole sublane groups, as do."""
    return min(n_rows, max(
        8, DENSE_CLASS_BLOCK_BYTES // (t_pad * itemsize) // 8 * 8))


def dense_class_loss_sums(w: jax.Array, alpha, arrays: dict, classes: int,
                          loss: str, smoothing: float) -> tuple:
    """:func:`class_loss_sums` on DENSE rows (K, n_shard, d) that carry one
    class id a row: ``(sums (2, R, 128), wrong)`` — per class, over the
    real rows, the primal loss at the margins X . W (W (d, R, 128), one
    product a block of rows at ``highest`` precision: a TPU's default is
    one bfloat16 pass, which moves a margin by ~1e-3 and the certificate
    with it) and the dual term of ``alpha`` (K, n_shard, R, 128; None:
    zeros); ``wrong`` the rows whose largest margin is not their own
    class's (the multi-class error's count).  The rows go by blocks
    (:func:`dense_class_row_block`), a shard after another, each sliced
    from the whole arrays where it is used: no temporary is T times a
    shard."""
    import jax.numpy as jnp
    from jax import lax

    from cocoa_tpu.data.sharding import class_signs
    from cocoa_tpu.ops import losses

    x, mask, ids = arrays["X"], arrays["mask"], arrays["classes"]
    k, n, d = x.shape
    tile = w.shape[1:]
    t_pad = tile[0] * tile[1]
    w2 = w.reshape(d, t_pad)
    block = dense_class_row_block(n, t_pad, w.dtype.itemsize)
    nb = -(-n // block)
    live = jnp.arange(t_pad) < classes

    def one(t, carry):
        sums, wrong = carry
        shard, b = t // nb, t % nb
        start = jnp.minimum(b * block, n - block)

        def rows(a):
            """Rows [start, start + block) of shard ``shard`` of ``a``."""
            a = lax.dynamic_slice_in_dim(a, shard, 1, 0)
            return lax.dynamic_slice_in_dim(a, start, block, 1)[0]

        m = jnp.dot(rows(x), w2, precision=lax.Precision.HIGHEST)
        # the last block starts early enough to end on the last row: the
        # rows it shares with its neighbour are the neighbour's
        own = rows(mask) * (start + jnp.arange(block) >= b * block)
        cls = rows(ids)
        ym = class_signs(cls[:, None], classes, w.dtype) \
            * m.reshape((block,) + tile)
        primal = losses.primal(loss, ym, smoothing=smoothing)
        dual = (jnp.zeros_like(primal) if alpha is None else
                losses.dual_term(loss, rows(alpha), smoothing=smoothing))
        guess = jnp.argmax(jnp.where(live, m, -jnp.inf), axis=1)
        own3 = own[:, None, None]
        return (sums + jnp.stack([(primal * own3).sum(0),
                                  (dual * own3).sum(0)]),
                wrong + ((guess != cls) * own).sum())

    return lax.fori_loop(0, k * nb, one,
                         (jnp.zeros((2,) + tile, w.dtype),
                          jnp.zeros((), w.dtype)))


def gather_dequant(w: jax.Array, idx: jax.Array) -> jax.Array:
    """``w[idx]`` that understands the packed low-precision serving
    forms (serving/quantize.py): the model's DEVICE dtype is the
    trace-time dispatch key, so one jitted scoring function specializes
    per (bucket, dtype) and a hot-swap between forms never retraces.

    - f32 (the training dtype): a plain gather — BIT-IDENTICAL to the
      pre-quantization path, which is what makes the certificate
      fallback a normal slot publish.
    - uint32 = two packed bf16 lanes per word: gather word ``i>>1``,
      shift lane ``i&1`` down, widen by bit-shift + bitcast (bf16->f32
      is exact).  The gather rides the hardware 4-byte path at HALF the
      f32 cache/HBM footprint — XLA would EMULATE a narrow bf16 gather,
      so the packing, not the arithmetic, is the throughput mechanism.
    - int32 = four packed int8 lanes per word: gather word ``i>>2``,
      shift lane ``i&3`` down, sign-extend exactly; the caller applies
      the per-model symmetric scale ONCE on the reduced margins.

    Padded query slots (index 0, value 0) dequantize whatever lane 0
    holds and multiply by 0 — the padding convention is unchanged.
    """
    import jax.numpy as jnp
    from jax import lax

    if w.dtype == jnp.uint32:
        word = w[idx >> 1]
        lane = (word >> ((idx & 1).astype(jnp.uint32) << 4)) \
            & jnp.uint32(0xFFFF)
        return lax.bitcast_convert_type(lane << 16, jnp.float32)
    if w.dtype == jnp.int32:
        word = w[idx >> 2]
        lane = (word >> ((idx & 3) << 3)) & jnp.int32(0xFF)
        lane = lane - ((lane & jnp.int32(0x80)) << 1)
        return lane.astype(jnp.float32)
    return w[idx]


def serve_margins(w: jax.Array, shard: dict, scale=None) -> jax.Array:
    """Dtype-generic serving twin of :func:`shard_margins`: the same
    panel+residual split, but every model read goes through
    :func:`gather_dequant` so packed bf16/int8 models ride the same
    dispatch.  With an f32 model and ``scale=None`` this traces to
    EXACTLY the :func:`shard_margins` sparse/hybrid graph (the
    serving bit-identity pin in tests/test_serving.py).

    ``scale`` is the int8 per-model symmetric scale as a TRACED scalar
    (a new scale per swap never retraces); it multiplies the reduced
    margins once — the hot panel term gathers the same quantized model,
    so panel + residual share the one scale.

    **Catalogue mode** (the multi-tenant fleet, docs/DESIGN.md §21): a
    2-D ``w`` of shape ``(T, d)`` is a served catalogue of T tenant
    models, and the shard carries a per-row ``"tenant"`` vector
    (``(bucket,)`` int32).  Row r then scores against ``w[tenant[r]]``
    via ONE flat gather — ``w.reshape(-1)[tenant*d + idx]`` with the
    static row stride ``d`` — so a cross-tenant batch shares the same
    single compiled executable per bucket, and each row's gathered
    values and reduction order are IDENTICAL to the 1-D gather-sum a
    single-tenant server runs on ``w[tenant[r]]``: per-tenant answers
    are bit-identical to T independent servers (pinned,
    tests/test_serving.py).  Padded slots (tenant 0, index 0, value 0)
    contribute exactly 0, the unchanged padding convention.
    """
    if w.ndim == 2:
        stride = w.shape[1]
        flat_idx = (shard["tenant"][:, None] * stride
                    + shard["sp_indices"])
        m = (gather_dequant(w.reshape(-1), flat_idx)
             * shard["sp_values"]).sum(-1)
    else:
        m = (gather_dequant(w, shard["sp_indices"])
             * shard["sp_values"]).sum(-1)
        if "X_hot" in shard:
            m = m + shard["X_hot"] @ gather_dequant(w,
                                                    shard["hot_cols"])
    if scale is not None:
        m = m * scale
    return m


def shards_axpy(coefs: jax.Array, shards: dict, vec: jax.Array) -> jax.Array:
    """vec + Σ_{k,i} coefs[k,i] · x_{k,i} over EVERY row of the stacked
    (K, …) shard arrays — the transpose counterpart of
    :func:`shard_margins` (margins contract each row against a d-vector;
    this scatters one coefficient per row back into a d-vector).  Used by
    the ``--accel`` secant jump (solvers/cocoa.py): the extrapolated
    dual's exact correspondence update Δw = Σ y·Δα·x/(λn) in one batched
    pass at eval cadence.

    Same layout dispatch and padding conventions as the row accessors
    above (plus the dense kernel's folded twin ``X_folded`` where the
    caller supplies it): padded CSR slots carry value 0, so they
    contribute exactly 0; the hybrid split's hot and cold columns are
    disjoint, so the two scatters never race on a coordinate.
    TRAINING-side: never reads the dense eval twin.
    """
    import jax.numpy as jnp

    if "X_folded" in shards:
        # the dense Pallas path: contract against the folded rows, the one
        # array anything wants row-major.  Against ``X`` this einsum is an
        # MXU convolution (bf16 coefficients) that wants X row-major too,
        # and where the TPU stores X with its rows on the lanes
        # (ops/pallas_sdca.fold_rows) layout assignment then copies all of
        # X at the loop's entry on every dispatch — 10 ms a job at epsilon,
        # for a branch that cell never takes.  Here it is one f32
        # multiply-reduce streaming the rows once, and X stays as stored
        # for the eval.  Lanes past the fold's own are zero padding
        # (fold_rows', or lane_aligned's at a dispatch's entry).
        from cocoa_tpu.ops.pallas_sdca import unfold_vec

        dw = jnp.einsum("kn,knsc->sc", coefs, shards["X_folded"])
        return vec + unfold_vec(dw, vec.shape[0])
    if "X" in shards:
        return vec + jnp.einsum("kn,knd->d", coefs, shards["X"])
    idx, val = shards["sp_indices"], shards["sp_values"]
    if "sp_row_ptr" in shards:
        from cocoa_tpu.ops import pallas_longrows

        return pallas_longrows.shards_axpy(coefs, shards, vec,
                                           _stream_interpret())
    n, block = idx.shape[1], row_block(idx.shape[1], idx.shape[2])
    if block >= n:
        vec = vec.at[idx].add(coefs[..., None] * val)
    else:
        # in blocks of rows, so that coefs x values is never the size of
        # the rows again; the last block starts early enough to end on the
        # last row, and the rows it shares with its neighbour add nothing.
        # Within a block a slot group at a time, as far as its longest row
        # reaches (note above SLOT_GROUP)
        from jax import lax

        row_len = shards.get("sp_row_len")

        def add_block(b, vec):
            start = jnp.minimum(b * block, n - block)
            own = start + jnp.arange(block) >= b * block
            c = jnp.where(own, lax.dynamic_slice_in_dim(coefs, start, block,
                                                        1), 0)

            cols = _block_by_groups(idx, start, block)
            vals = _block_by_groups(val, start, block)

            def add_group(g, vec):
                i = lax.dynamic_index_in_dim(cols, g, 0, keepdims=False)
                v = lax.dynamic_index_in_dim(vals, g, 0, keepdims=False)
                return vec.at[i].add(c[:, None, :] * v)

            return lax.fori_loop(
                0, _group_trips(row_len, start, block, 1, idx.shape[2]),
                add_group, vec)

        vec = lax.fori_loop(0, -(-n // block), add_block, vec)
    if "X_hot" in shards:
        # hot_cols arrives (K, n_hot) — replicated per shard by the
        # loader — so the panel contribution scatters per shard: a
        # summed (n_hot,) update here would be added K times by the
        # leading index dim (pinned against the dense einsum in
        # tests/test_accel.py::test_shards_axpy_hybrid_matches_dense)
        vec = vec.at[shards["hot_cols"]].add(
            jnp.einsum("kn,knh->kh", coefs, shards["X_hot"]))
    return vec


def eval_margins(w: jax.Array, shard: dict) -> jax.Array:
    """EVAL-side :func:`shard_margins`: additionally prefers the dense
    eval twin ``X_eval`` (data/sharding.py ``eval_dense=True``) — the
    certificate's full margins pass then rides one MXU matvec instead of
    an every-nonzero w-gather.  Measured through the production rcv1
    device-loop path, the gather-based eval was 31% of the round time
    (9.42 -> 6.46 ms/round with the twin).  Without the twin, a HYBRID
    shard (``--hotCols`` + ``--evalDense=auto`` when the twin exceeds the
    HBM budget) still gets most of that win structurally: the falls-through
    :func:`shard_margins` runs the hot majority of nonzeros as one MXU
    panel matvec and gathers only the residual tail.  Eval-only by
    construction: training uses :func:`shard_margins` directly, which
    never reads the twin, so trained (w, α) are bit-identical with or
    without it."""
    if "X_eval" in shard:
        return shard["X_eval"] @ w
    return shard_margins(w, shard)
