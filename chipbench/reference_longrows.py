"""The plain reference for sparse rows kept as a stream: the L2-regularised
linear classifier of ``reference.py``, written out for rows stored end to
end.

Independent of ``cocoa_tpu.ops``, ``cocoa_tpu.evals`` and every kernel: for
a returned pair (w, alpha) on the program's stream shards — columns and
values (K, n_pieces, P) read as one run of slots a shard, row i of a shard
holding slots [A * ptr_i, A * ptr_i + len_i) (``sp_row_ptr``,
``sp_row_len``; A slots the alignment of a row's start; every other slot
column 0, value 0) — it recomputes, in straight ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``,

    margins  m_i     = sum_j val_ij * w[col_ij]          (``jnp.take``)
    primal  P(w)     = (1/n) sum_i loss(y_i m_i) + (lam/2) |w|^2
    dual    D(alpha) = (1/n) sum_i -loss*(-alpha_i) - (lam/2) |w(alpha)|^2
    w(alpha)         = (1/(lam n)) sum_i y_i alpha_i x_i  (scatter-add)

with the losses of ``reference.py``'s head.  It goes a block of
``BLOCK_SLOTS`` slots of one shard at a time.  A slot's row is the count
of row starts at or before it (an int32 running sum of marks put at the
starts); a row's margin is the sum of its slots' products, added up over
the blocks it spans; the K shards' and the blocks' partial sums are added
on the host in float64 (w(alpha)'s: ``BLOCKS_ON_DEVICE`` blocks' at a
time).  No temporary is larger than a block's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _losses

BLOCK_SLOTS = 1 << 22           # slots of a shard per block
BLOCKS_ON_DEVICE = 8            # blocks whose shares of sum_i coef_i x_i are
                                # added in float32 on the device before the
                                # host takes them (a d-vector is 66 MB)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block(block, align, start, own_from, cols, vals, first, length, coef, w,
           wsum):
    """Slots [start, start + block) of ONE shard, those before ``own_from``
    left to the neighbour's block: the block's share of every row's margin
    (rows,), ``wsum`` (d,) with its share of sum_i coef_i x_i added, and
    its count of values that are not zero.  A row starts on a group of ``align`` slots (``first``, in
    groups), so a group belongs to one row: rows are looked up by group."""
    rows, groups = first.shape[0], block // align
    cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        a.reshape(-1), start, block).reshape(groups, align)
    own = (start + jnp.arange(block).reshape(groups, align)) >= own_from
    cols, vals = cut(cols), jnp.where(own, cut(vals), 0.0)
    rel = first - start // align
    inside = (rel >= 0) & (rel < groups) & (length > 0)
    marks = jnp.zeros((groups,), jnp.int32).at[
        jnp.where(inside, rel, groups)].add(1, mode="drop")
    row = jnp.sum((rel < 0) & (length > 0)) + jnp.cumsum(marks) - 1
    row = jnp.where(row >= 0, row, rows)        # before the first row: none
    margins = jnp.zeros((rows,), vals.dtype).at[row].add(
        jnp.sum(vals * jnp.take(w, cols), axis=1), mode="drop")
    by_group = jnp.take(coef, row, mode="fill", fill_value=0.0)
    wsum = wsum.at[cols].add(vals * by_group[:, None])
    return margins, wsum, jnp.sum(vals != 0)


def recompute(ds, w, alpha, lam: float, loss: str = "hinge",
              block_slots: int = BLOCK_SLOTS) -> dict:
    """Objectives and w(alpha) on dataset ``ds`` (a stream
    ``ShardedDataset``, read as plain arrays).  ``stray_values``: values
    that are not zero less the rows' lengths added up — above 0, a slot
    outside every row holds a value (the storage says it holds none, and
    this reference, like the program, adds such a slot to the row before
    it)."""
    from cocoa_tpu.data.sharding import STREAM_ALIGN

    if ds.layout != "sparse" or ds.sp_row_ptr is None or ds.X_hot is not None:
        raise ValueError("the long-row reference reads rows kept as a "
                         "stream, with no panel")
    k, n_pieces, piece = ds.sp_indices.shape
    slots = n_pieces * piece
    block = min(block_slots, slots) // STREAM_ALIGN * STREAM_ALIGN
    coef = ds.labels * alpha * ds.mask
    wsum = np.zeros(w.shape[0])
    margins = np.zeros((k, ds.n_shard))
    values = 0
    with jax.default_matmul_precision("highest"):
        for s in range(k):
            shard = (ds.sp_indices[s], ds.sp_values[s], ds.sp_row_ptr[s],
                     ds.sp_row_len[s], coef[s])
            n_blocks = -(-slots // block)
            for b in range(n_blocks):
                if b % BLOCKS_ON_DEVICE == 0:
                    ws = jnp.zeros_like(w)
                m, ws, nz = _block(
                    block, STREAM_ALIGN, min(b * block, slots - block),
                    b * block, *shard, w, ws)
                margins[s] += np.asarray(m, np.float64)
                values += int(nz)
                if (b + 1) % BLOCKS_ON_DEVICE == 0 or b + 1 == n_blocks:
                    wsum += np.asarray(ws, np.float64)
        z = ds.labels * jnp.asarray(margins, ds.labels.dtype)
        primal, dual = _losses(loss, z, alpha)
        psum = np.asarray(jnp.sum(primal * ds.mask, axis=1), np.float64)
        asum = np.asarray(jnp.sum(dual * ds.mask, axis=1), np.float64)
    w64, a_host = np.asarray(w, np.float64), np.asarray(alpha)
    w_ref = wsum / (lam * ds.n)
    primal = float(psum.sum() / ds.n + 0.5 * lam * (w64 @ w64))
    dual = float(asum.sum() / ds.n - 0.5 * lam * (w_ref @ w_ref))
    # what w rounded to the nearest precision below float32 would read
    # against w(alpha): the second reading the w tolerance sits under
    w_bf16 = np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(
        jnp.float32), np.float64)
    return dict(primal=primal, dual=dual, gap=primal - dual,
                w_err=float(np.abs(w64 - w_ref).max()),
                w_err_bf16=float(np.abs(w_bf16 - w_ref).max()),
                w_scale=float(np.abs(w_ref).max()),
                stray_values=values - int(np.asarray(ds.sp_row_len).sum()),
                alpha_min=float(a_host.min()), alpha_max=float(a_host.max()))
