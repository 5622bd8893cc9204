"""The plain reference for padded-CSR shards: the L2-regularised linear
classifier of ``reference.py``, written out for sparse rows.

Independent of ``cocoa_tpu.ops``, ``cocoa_tpu.evals`` and every kernel: for
a returned pair (w, alpha) on the program's padded-CSR shards — columns
(K, n_shard, W) int32, values (K, n_shard, W), slots past a row's length
holding column 0 and value 0 — it recomputes, in straight ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``,

    margins  m_i     = sum_j val_ij * w[col_ij]          (``jnp.take``)
    primal  P(w)     = (1/n) sum_i loss(y_i m_i) + (lam/2) |w|^2
    dual    D(alpha) = (1/n) sum_i -loss*(-alpha_i) - (lam/2) |w(alpha)|^2
    w(alpha)         = (1/(lam n)) sum_i y_i alpha_i x_i  (scatter-add)

with the losses of ``reference.py``'s head (hinge, logistic).  It goes row
block by row block, so no temporary is larger than one block's
(K, rows, W) products; the K shards' and the blocks' partial sums are added
on the host in float64.  A block's last start is pulled back so that it
ends on the shard's last row, and the rows it then shares with its
neighbour are counted once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _losses

BLOCK_SLOTS = 1 << 22           # slots (row x W) of a shard per block


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block(loss, rows, start, first, cols, vals, y, mask, w, alpha):
    """Partial sums of rows [start, start + rows) of every shard; rows
    before ``first`` belong to the neighbour's block.  Returns (K,) primal
    and dual sums and the block's share of sum_i y_i alpha_i x_i, (d,)."""
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows, 1)  # noqa: E731
    cols, vals, y, alpha = cut(cols), cut(vals), cut(y), cut(alpha)
    own = (start + jnp.arange(rows) >= first).astype(vals.dtype)
    mask = cut(mask) * own
    margins = jnp.sum(vals * jnp.take(w, cols), axis=-1)    # (K, rows)
    primal, dual = _losses(loss, y * margins, alpha)
    coef = y * alpha * mask
    wsum = jnp.zeros_like(w).at[cols].add(vals * coef[..., None])
    return (jnp.sum(primal * mask, axis=1), jnp.sum(dual * mask, axis=1),
            wsum)


def recompute(ds, w, alpha, lam: float, loss: str = "hinge",
              block_slots: int = BLOCK_SLOTS) -> dict:
    """Objectives and w(alpha) on dataset ``ds`` (a padded-CSR
    ``ShardedDataset``, read as plain arrays)."""
    if ds.layout != "sparse" or ds.X_hot is not None:
        raise ValueError(f"the sparse reference reads plain padded-CSR "
                         f"rows, not the {ds.layout} layout with a panel")
    k, n_shard, width = ds.sp_indices.shape
    rows = min(n_shard, max(1, block_slots // width))
    psum, asum = np.zeros(k), np.zeros(k)
    wsum = np.zeros(w.shape[0])
    with jax.default_matmul_precision("highest"):
        for b in range(-(-n_shard // rows)):
            first = b * rows
            parts = _block(loss, rows, min(first, n_shard - rows), first,
                           ds.sp_indices, ds.sp_values, ds.labels, ds.mask,
                           w, alpha)
            p, a, ws = (np.asarray(x, np.float64) for x in parts)
            psum, asum, wsum = psum + p, asum + a, wsum + ws
    w64, a_host = np.asarray(w, np.float64), np.asarray(alpha)
    w_ref = wsum / (lam * ds.n)
    primal = float(psum.sum() / ds.n + 0.5 * lam * (w64 @ w64))
    dual = float(asum.sum() / ds.n - 0.5 * lam * (w_ref @ w_ref))
    return dict(primal=primal, dual=dual, gap=primal - dual,
                w_err=float(np.abs(w64 - w_ref).max()),
                w_scale=float(np.abs(w_ref).max()),
                alpha_min=float(a_host.min()), alpha_max=float(a_host.max()))
