"""Column (feature) shards for the primal prox solvers (ProxCoCoA+).

The L1 framework partitions the design matrix A (n × d) by **columns**:
worker k owns a coordinate block x_[k] and its columns A_[k], and the
shared n-vector v = A·x is the replicated state (the exact mirror of the
dual solvers, where examples are sharded and the d-vector w is shared).

This builder reuses :class:`~cocoa_tpu.data.sharding.ShardedDataset` with
the roles transposed: the shard's "rows" are columns a_j (shape (n,)),
``labels`` is all-ones (the prox rules have no y factor), ``sq_norms`` are
column norms ‖a_j‖², ``counts`` the per-shard column counts, and
``num_features`` is n (padded) — the length of the replicated residual
vector r = A·x − b.  Every downstream consumer — the fan-out machinery,
the fori_loop inner solvers, both Pallas kernels — works unchanged on
this transposed layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import (
    ShardedDataset,
    segment_sq_norms,
    split_sizes,
)
from cocoa_tpu.parallel import mesh as mesh_lib


def shard_columns(
    data: LibsvmData,
    k: int,
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
    layout: str = "auto",
    max_col_nnz: Optional[int] = None,
) -> ShardedDataset:
    """Partition A's d columns into K balanced contiguous blocks.

    Returns the transposed-role ShardedDataset (shard "row" j = column
    ``offs[k]+j`` of A); its ``target`` is the (n_pad,) regression target
    b (``data.labels``, zero-padded — padding rows of A are zero so they
    touch nothing).  A dense A already on the device takes
    :func:`shard_dense_columns`, which builds the same dataset with no
    host CSR.

    Layouts mirror :func:`~cocoa_tpu.data.sharding.shard_dataset`:

    - ``dense``  — each column a dense (n_pad,) vector.
    - ``sparse`` — padded-CSC: per-column (row-index, value) arrays padded
      to the widest column.  Column nnz is often far more skewed than row
      nnz (hot features touch most examples), so the padded width can
      approach n — ``max_col_nnz`` guards against silent blow-up.
    - ``auto``   — sparse below 10% density (matching shard_dataset), but
      only when the widest column keeps the padded encoding smaller than
      dense.
    """
    if layout not in ("auto", "dense", "sparse"):
        raise ValueError(f"layout must be auto|dense|sparse, got {layout!r}")
    n, d = data.n, data.num_features
    np_dtype = np.dtype(dtype)
    sizes = split_sizes(d, k)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    # pad the column count per shard to a sublane multiple (row axis of the
    # shard) and n to a sublane multiple (the kernels' "feature" axis)
    d_shard = -(-int(sizes.max()) // 16) * 16
    n_pad = mesh_lib.pad_features(n, mesh)

    # CSR -> CSC once (also yields per-column nnz for the layout choice)
    row_ids = np.repeat(np.arange(n, dtype=np.int32), np.diff(data.indptr))
    order = np.argsort(data.indices, kind="stable")
    csc_rows = row_ids[order]
    csc_vals = np.asarray(data.values)[order]
    col_nnz = np.bincount(data.indices, minlength=d)
    col_ptr = np.concatenate([[0], np.cumsum(col_nnz)])
    widest = int(col_nnz.max(initial=1))

    if layout == "auto":
        nnz = int(data.indptr[-1])
        density = nnz / max(1, n * d)
        layout = ("sparse" if density < 0.10 and widest * 2 < n_pad
                  and (max_col_nnz is None or widest <= max_col_nnz)
                  else "dense")   # auto's job is to pick a VIABLE layout
        if mesh_lib.has_fp(mesh):
            layout = "dense"
    if layout == "sparse":
        if mesh_lib.has_fp(mesh):
            raise ValueError(
                "sparse column shards cannot combine with an fp mesh"
            )
        if max_col_nnz is not None and widest > max_col_nnz:
            raise ValueError(
                f"widest column has {widest} nonzeros > max_col_nnz="
                f"{max_col_nnz}; hot features make padded-CSC degenerate — "
                f"use layout='dense'"
            )

    labels = np.zeros((k, d_shard), dtype=np_dtype)
    mask = np.zeros((k, d_shard), dtype=np_dtype)
    sq_norms = np.zeros((k, d_shard), dtype=np_dtype)
    col_sq = segment_sq_norms(csc_vals, col_ptr)
    for s in range(k):
        lo, hi = offsets[s], offsets[s + 1]
        m = hi - lo
        labels[s, :m] = 1.0   # prox rules have no y factor
        mask[s, :m] = 1.0
        sq_norms[s, :m] = col_sq[lo:hi]

    kwargs: dict = {}
    if layout == "dense":
        X = np.zeros((k, d_shard, n_pad), dtype=np_dtype)
        for s in range(k):
            lo, hi = offsets[s], offsets[s + 1]
            a, bnd = col_ptr[lo], col_ptr[hi]
            cols = np.repeat(np.arange(hi - lo),
                             col_nnz[lo:hi].astype(np.int64))
            X[s][cols, csc_rows[a:bnd]] = csc_vals[a:bnd]
        kwargs["X"] = X
    else:
        sp_idx = np.zeros((k, d_shard, widest), dtype=np.int32)
        sp_val = np.zeros((k, d_shard, widest), dtype=np_dtype)
        for s in range(k):
            lo, hi = offsets[s], offsets[s + 1]
            a, bnd = col_ptr[lo], col_ptr[hi]
            cols = np.repeat(np.arange(hi - lo),
                             col_nnz[lo:hi].astype(np.int64))
            slots = (np.arange(a, bnd)
                     - np.repeat(col_ptr[lo:hi], col_nnz[lo:hi].astype(np.int64)))
            sp_idx[s][cols, slots] = csc_rows[a:bnd]
            sp_val[s][cols, slots] = csc_vals[a:bnd]
        kwargs["sp_indices"] = sp_idx
        kwargs["sp_values"] = sp_val

    def put(arr, fp_last=False):
        if mesh is not None:
            if fp_last:
                return jax.device_put(arr, mesh_lib.x_sharding(mesh))
            return jax.device_put(
                arr, mesh_lib.sharded_rows(mesh, extra_dims=arr.ndim - 1)
            )
        return jnp.asarray(arr)

    b = np.zeros(n_pad, dtype=np_dtype)
    b[:n] = data.labels
    return ShardedDataset(
        layout=layout,
        n=d,                      # "examples" of this transposed view
        num_features=n_pad,       # the replicated vector length
        counts=sizes.astype(np.int64),
        labels=put(labels),
        mask=put(mask),
        sq_norms=put(sq_norms),
        X=put(kwargs["X"], fp_last=True) if "X" in kwargs else None,
        sp_indices=put(kwargs["sp_indices"]) if "sp_indices" in kwargs else None,
        sp_values=put(kwargs["sp_values"]) if "sp_values" in kwargs else None,
        target=(jnp.asarray(b) if mesh is None
                else jax.device_put(b, mesh_lib.primal_sharding(mesh))),
    )


@functools.partial(jax.jit, static_argnames=("n_pad", "dtype"))
def _lay_out_columns(cols, b, take, mask, n_pad: int, dtype):
    """(d, n) columns -> the (K, d_shard, n_pad) shards, their norms and
    the padded target, in one program (``take``: each slot's column, any
    real one on a padding slot; ``mask`` zeroes it).  Blocks of one
    length are written a block at a time into the zeroed result: the
    program holds the columns, the shards and one block (a reshape to
    (K, d / K, n) compiles for a minute at 3.2 GB and, as the gather of
    the uneven case does, holds a third array of A's size)."""
    k, d_shard = take.shape
    d, n = cols.shape
    if d % k == 0:
        def put(s, X):
            block = jax.lax.dynamic_slice(cols, (s * (d // k), 0),
                                          (d // k, n))
            return jax.lax.dynamic_update_slice(
                X, block.astype(dtype)[None], (s, 0, 0))

        X = jax.lax.fori_loop(0, k, put,
                              jnp.zeros((k, d_shard, n_pad), dtype))
    else:
        X = jnp.pad(cols.astype(dtype)[take] * mask[..., None],
                    ((0, 0), (0, 0), (0, n_pad - n)))
    return (X, jnp.sum(X * X, axis=-1),
            jnp.pad(b.astype(dtype), (0, n_pad - n)))


def shard_dense_columns(
    cols: jax.Array,
    b: jax.Array,
    k: int,
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> ShardedDataset:
    """:func:`shard_columns` (``layout="dense"``) for a design matrix that
    is already an array on the device: ``cols`` is (d, n), A's columns as
    rows (Aᵀ), ``b`` the (n,) target.  The K balanced contiguous blocks,
    the sublane padding of the block length, the padding of n, the mask,
    the all-ones-on-real-columns ``labels`` and the column norms are
    :func:`shard_columns`'s; nothing passes through the host, so a set of
    billions of entries (epsilon: 8·10⁸) needs no CSR, no ``argsort`` of
    its nonzeros and no host copy.  The norms are summed on the device in
    ``dtype`` (``shard_columns`` sums them exactly on the host and rounds
    once: the two agree to the last bit wherever the squares sum
    exactly)."""
    d, n = cols.shape
    sizes = split_sizes(d, k)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    d_shard = -(-int(sizes.max()) // 16) * 16
    n_pad = mesh_lib.pad_features(n, mesh)
    slot = np.arange(d_shard)
    mask = jnp.asarray(
        (slot[None, :] < sizes[:, None]).astype(np.dtype(dtype)))
    take = np.minimum(offsets[:-1, None] + slot[None, :], d - 1)
    X, sq_norms, target = _lay_out_columns(
        cols, b, jnp.asarray(take, jnp.int32), mask, n_pad, np.dtype(dtype))
    if mesh is not None:
        rows = mesh_lib.sharded_rows(mesh, extra_dims=1)
        X = jax.device_put(X, mesh_lib.x_sharding(mesh))
        mask, sq_norms = (jax.device_put(a, rows) for a in (mask, sq_norms))
        target = jax.device_put(target, mesh_lib.primal_sharding(mesh))
    return ShardedDataset(
        layout="dense", n=d, num_features=n_pad,
        counts=sizes.astype(np.int64), labels=mask, mask=mask,
        sq_norms=sq_norms, X=X, target=target)
