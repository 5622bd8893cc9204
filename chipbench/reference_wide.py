"""The plain reference for one-vs-rest with a WIDE class axis on DENSE
rows: T L2-regularised linear classifiers over one set of dense rows, a
row carrying one class id, written out.

Independent of ``cocoa_tpu.evals.objectives``, ``ops/losses.py``,
``ops/rows.py``, ``ops/block_lanes.py`` and every kernel: from the returned
alpha and the rows' class ids ALONE it recomputes, in straight
``jax.numpy`` float32,

    y_ti         = +1 where class_i = t, else -1
    w_t(alpha_t) = (1/(lam n)) sum_i alpha_ti y_ti x_i
    P_t(w_t)     = (1/n) sum_i loss(y_ti x_i . w_t) + (lam/2) |w_t|^2
    D_t(alpha_t) = (1/n) sum_i -loss*(-alpha_ti) - (lam/2) |w_t(alpha_t)|^2

with P at the RETURNED w_t, as the other cells' references take it.  The
program holds the class axis as (R, 128) tiles — W (d, R, 128), alpha (K,
n_shard, R, 128), class t at [t // 128, t % 128] — which are (d, T_pad)
and (K, n_shard, T_pad) row-major, and the reference reads them so, a
shard a call, ``ROW_BLOCK`` rows at a time: a block's margins are one (rows,
d) . (d, T_pad) product and its share of sum_i alpha_ti y_ti x_i one (d,
rows) . (rows, T_pad) product, both at ``highest`` precision (at a thousand
classes a multiply-and-sum on the vector unit, ``reference_ovr.py``'s way,
would be 2.6e12 of them a pass); no temporary is larger than a block's
(rows, T_pad), 8 MB.  The sum over the rows is float32 in two levels, as
``reference_labels.py``'s: a block's part starts at ZERO, and the part
joins the running sum — over the blocks of all K shards — as a two-float
value (``hi``, ``lo``: Knuth's TwoSum, the rounding error of every add
kept), so the sum of 320,292 terms is as good as its 2,048-term parts.

Two counter-readings ride along, for the check's limits to sit between
(checks/certified_gap_wide.py), as ``reference_ovr.py`` carries them:
every class's gap with the margins as ONE bfloat16 pass of the matrix unit
would take them — rows and W rounded once to bfloat16, on the bits,
products and sums in float32 (``gaps_bf16``) — and the returned W rounded
once to bfloat16 against w(alpha) (``w_err_bf16``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the losses, the rounding to bfloat16 on the bits and Knuth's TwoSum are
# the label-set reference's, which is as plain as this one
from chipbench.reference_labels import _as_bf16, _losses, _two_sum

ROW_BLOCK = 2048                # rows a step of a call
HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _shard_parts(loss, rows, whi, wlo, x, ids, mask, w2, alpha, s):
    """Shard ``s``: (loss sums, the same through a bf16 pass, dual sums,
    (``whi``, ``wlo``) + sum_i alpha_ti y_ti x_i as a two-float value), the
    first three (T_pad,), the last two (d, T_pad)."""
    n_shard, t_pad = x.shape[1], w2.shape[1]
    w16 = _as_bf16(w2)
    lanes = jnp.arange(t_pad)
    n_blocks = -(-n_shard // rows)

    def block(b, carry):
        psum, psum16, asum, whi, wlo = carry
        start = jnp.minimum(b * rows, n_shard - rows)

        def take(a, trailing):
            return jax.lax.dynamic_slice(
                a, (s, start) + (0,) * len(trailing), (1, rows) + trailing)[0]

        xb = take(x, (x.shape[2],))
        # the rows this block shares with the one before it are that one's
        own = take(mask, ()) * (start + jnp.arange(rows) >= b * rows)
        y = jnp.where(take(ids, ())[:, None] == lanes[None, :], 1.0, -1.0)
        # (a block of the tiles, not the whole of alpha, is read flat)
        a = take(alpha, alpha.shape[2:]).reshape(rows, t_pad)
        m = jnp.dot(xb, w2, precision=HIGHEST)
        m16 = jnp.dot(_as_bf16(xb), w16, precision=HIGHEST)
        part = jnp.dot(xb.T, a * y * own[:, None], precision=HIGHEST)
        primal, dual = _losses(loss, y * m, a)
        primal16, _ = _losses(loss, y * m16, a)
        weigh = lambda v: jnp.sum(v * own[:, None], axis=0)  # noqa: E731
        return (psum + weigh(primal), psum16 + weigh(primal16),
                asum + weigh(dual), *_two_sum(whi, wlo, part))

    zero = jnp.zeros((t_pad,), jnp.float32)
    return jax.lax.fori_loop(0, n_blocks, block,
                             (zero, zero, zero, whi, wlo))


@jax.jit
def _errors(w2, wsum, inv_lam_n):
    """Per class: (|w|^2, |w(alpha)|^2, max |w - w(alpha)|, max |bf16(w) -
    w(alpha)|, |w(alpha)|_inf), each (T_pad,)."""
    w_ref = wsum * inv_lam_n
    return (jnp.sum(w2 * w2, axis=0), jnp.sum(w_ref * w_ref, axis=0),
            jnp.max(jnp.abs(w2 - w_ref), axis=0),
            jnp.max(jnp.abs(_as_bf16(w2) - w_ref), axis=0),
            jnp.max(jnp.abs(w_ref), axis=0))


def recompute(ds, w, alpha, lam: float, loss: str = "hinge",
              row_block: int = ROW_BLOCK) -> dict:
    """Every class's objectives and w_t(alpha_t) on ``ds`` (a dense
    ``ShardedDataset`` whose rows carry one class id, read as plain arrays)
    for the program's W (d, R, 128) and alpha (K, n_shard, R, 128).  Lists
    are by class id, T long."""
    if ds.layout != "dense" or ds.classes is None or ds.classes.ndim != 2:
        raise ValueError("the wide one-vs-rest reference reads dense rows "
                         "that carry one class id each")
    t_count, n, d = ds.num_classes, ds.n, ds.num_features
    w2 = jnp.asarray(w, jnp.float32).reshape(d, -1)
    t_pad = w2.shape[1]
    alpha = jnp.asarray(alpha, jnp.float32)
    rows = min(row_block, ds.n_shard)
    whi = jnp.zeros((d, t_pad), jnp.float32)
    wlo = jnp.zeros_like(whi)
    sums = np.zeros((3, t_pad))
    for s in range(ds.k):
        p, p16, a, whi, wlo = _shard_parts(loss, rows, whi, wlo, ds.X,
                                           ds.classes, ds.mask, w2, alpha, s)
        sums += np.asarray([p, p16, a], np.float64)
    errs = [np.asarray(v, np.float64)
            for v in _errors(w2, whi + wlo, 1.0 / (lam * n))]
    del whi, wlo
    psum, psum16, asum, w_sq, wref_sq, w_err, w_err16, w_inf = (
        v[:t_count] for v in (*sums, *errs))
    pad = np.concatenate([v[t_count:] for v in errs])
    reg = 0.5 * lam * w_sq
    dual = asum / n - 0.5 * lam * wref_sq
    primal = psum / n + reg
    scale = np.maximum(1.0, w_inf)
    return dict(
        primal=primal.tolist(), dual=dual.tolist(),
        gaps=(primal - dual).tolist(),
        gaps_bf16=(psum16 / n + reg - dual).tolist(),
        w_err=(w_err / scale).tolist(),
        w_err_bf16=(w_err16 / scale).tolist(), w_scale=w_inf.tolist(),
        # the lanes past T: no model there, W and w(alpha) both zero
        pad_lanes_max=float(pad.max(initial=0.0)),
        alpha_min=float(jnp.min(alpha)), alpha_max=float(jnp.max(alpha)),
        class_share=(np.bincount(
            np.asarray(ds.classes)[np.asarray(ds.mask) > 0],
            minlength=t_count) / n).tolist())
