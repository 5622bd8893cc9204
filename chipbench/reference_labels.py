"""The plain reference for one-vs-rest over LABEL SETS on sparse rows: T
L2-regularised linear classifiers over one set of padded-CSR rows, a row
carrying the set of the labels it has, written out.

Independent of ``cocoa_tpu.evals.objectives``, ``ops/losses.py``,
``ops/rows.py`` and every kernel: from the returned alpha and the rows'
label sets ALONE it recomputes, in straight ``jax.numpy`` float32,

    y_ti         = +1 where t is among row i's ids, else -1
    w_t(alpha_t) = (1/(lam n)) sum_i alpha_ti y_ti x_i
    P_t(w_t)     = (1/n) sum_i loss(y_ti x_i . w_t) + (lam/2) |w_t|^2
    D_t(alpha_t) = (1/n) sum_i -loss*(-alpha_ti) - (lam/2) |w_t(alpha_t)|^2

with P at the RETURNED w_t, as the other cells' references take it.  The
program holds the class axis as (R, 128) tiles — W (d, R, 128), alpha
(K, n_shard, R, 128), class t at [t // 128, t % 128] — and the reference
reads them so: one call is one shard and one ``CLASS_BLOCK`` = 128 classes
(one sublane row r of the tiles), a block of ``ROW_BLOCK`` rows at a time,
the block's slots ``SLOT_BLOCK`` at a time as far as its longest row
reaches; its largest temporary is the gather of (ROW_BLOCK, SLOT_BLOCK,
128) W values, 32 MB, and three (d, 128) sums of alpha y x, 104 MB each at
d = 203,882.  Row dots and the sums over rows are multiply-and-sum on the
vector unit, never a matmul (``jax.default_matmul_precision("highest")``
is set all the same).  The sum of alpha y x over the rows is float32 in
two levels: a block's ``ROW_BLOCK`` rows are scatter-added into a part
that starts at ZERO, and the part joins the running sum — over the blocks
of all K shards, class block by class block — as a two-float value (``hi``,
``lo``: Knuth's TwoSum, the rounding error of every add kept), so only
T-long results ever reach the host.  The levels are for the bias column
and the Zipf head: a column in every row, under a label that a tenth of
the rows carry, sums 1.19e6 terms of ONE sign to ~460 (w 3.9 at lambda n =
119), where a float32 running sum's half-ulp, 1.5e-5, is the size of the
terms it is given.  Against a float64 sum on the host (my chip run, PR 48,
class 0 of the batch from rank 4, ten rounds): the program's W is 2.9e-7
of |w|_inf off, one running float32 sum 1.76e-4, parts of eight blocks
8.9e-5, of four 2.1e-5 — the reference's own error, falling with the
square of the part's length.

Two counter-readings ride along, for the check's limits to sit between
(checks/certified_gap_labels.py), as ``reference_ovr.py`` carries them:
every class's gap with the margins as ONE bfloat16 pass would take them —
values and W rounded once to bfloat16, on the bits, products and sums in
float32 (``gaps_bf16``) — and the returned W rounded once to bfloat16
against w(alpha) (``w_err_bf16``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlogy

CLASS_BLOCK = 128               # classes a call: one sublane row of a tile
ROW_BLOCK = 2048                # rows a step of a call
SLOT_BLOCK = 32                 # slots of those rows gathered at a time


def _losses(loss: str, z, alpha):
    """(loss(z), -loss*(-alpha)) elementwise."""
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - z), alpha
    if loss == "logistic":
        a = jnp.clip(alpha, 0.0, 1.0)
        return jnp.logaddexp(0.0, -z), -(xlogy(a, a) + xlogy(1 - a, 1 - a))
    raise ValueError(f"the plain reference has no loss {loss!r}")


def _as_bf16(a):
    """float32 values rounded once to bfloat16 (to nearest, ties to even),
    kept as float32: on the bits, since a cast there and back inside a
    jitted program may be elided (reference_lasso.py)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _two_sum(hi, lo, x):
    """(hi, lo) + x as a two-float value: ``hi`` the rounded sum, ``lo``
    gaining what the rounding dropped (Knuth's TwoSum: exact in float32)."""
    s = hi + x
    v = s - hi
    return s, lo + ((hi - (s - v)) + (x - v))


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _shard_parts(loss, rows, whi, wlo, cols, vals, ids_t, mask, w, alpha, s,
                 r):
    """Shard ``s``, classes [128 r, 128 r + 128): (loss sums, the same
    through a bf16 pass, dual sums, (``whi``, ``wlo``) + sum_i alpha_ti
    y_ti x_i as a two-float value), the first three (128,), the last two
    (d, 128)."""
    n_shard, width = cols.shape[1:]
    slots = min(SLOT_BLOCK, width)
    w_r = jax.lax.dynamic_index_in_dim(w, r, 1, keepdims=False)   # (d, 128)
    w_r16 = _as_bf16(w_r)
    lanes = r * CLASS_BLOCK + jnp.arange(CLASS_BLOCK)
    n_blocks = -(-n_shard // rows)

    def block(b, carry):
        psum, psum16, asum, whi, wlo = carry
        start = jnp.minimum(b * rows, n_shard - rows)

        def take(a, trailing):
            return jax.lax.dynamic_slice(
                a, (s, start) + (0,) * len(trailing), (1, rows) + trailing)[0]

        c, v = take(cols, (width,)), take(vals, (width,))
        # the rows this block shares with the one before it are that one's
        own = take(mask, ()) * (start + jnp.arange(rows) >= b * rows)
        # (ids_t: the label sets seen (K, L, n_shard), as a TPU stores them)
        row_ids = jax.lax.dynamic_slice(
            ids_t, (s, 0, start), (1, ids_t.shape[1], rows))[0].T
        y = jnp.where((row_ids[:, :, None] == lanes[None, None, :]).any(1),
                      1.0, -1.0)
        a = jax.lax.dynamic_slice(
            alpha, (s, start, r, 0), (1, rows, 1, CLASS_BLOCK))[0, :, 0]
        coef = a * y * own[:, None]
        longest = jnp.max(jnp.sum(v != 0, axis=1))

        def chunk(j, acc):
            m, m16, part = acc
            cj = jax.lax.dynamic_slice_in_dim(c, j * slots, slots, 1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * slots, slots, 1)
            m = m + jnp.sum(w_r[cj] * vj[:, :, None], axis=1)
            m16 = m16 + jnp.sum(w_r16[cj] * _as_bf16(vj)[:, :, None], axis=1)
            part = part.at[cj].add(vj[:, :, None] * coef[:, None, :])
            return m, m16, part

        zero = jnp.zeros((rows, CLASS_BLOCK), jnp.float32)
        m, m16, part = jax.lax.fori_loop(
            0, (longest + slots - 1) // slots, chunk,
            (zero, zero, jnp.zeros_like(whi)))
        primal, dual = _losses(loss, y * m, a)
        primal16, _ = _losses(loss, y * m16, a)
        weigh = lambda x: jnp.sum(x * own[:, None], axis=0)  # noqa: E731
        return (psum + weigh(primal), psum16 + weigh(primal16),
                asum + weigh(dual), *_two_sum(whi, wlo, part))

    zero = jnp.zeros((CLASS_BLOCK,), jnp.float32)
    return jax.lax.fori_loop(0, n_blocks, block,
                             (zero, zero, zero, whi, wlo))


@jax.jit
def _class_block_errors(w, wsum, r, inv_lam_n):
    """Of classes [128 r, 128 r + 128): (|w|^2, |w(alpha)|^2, max |w -
    w(alpha)|, max |bf16(w) - w(alpha)|, |w(alpha)|_inf), each (128,)."""
    w_r = jax.lax.dynamic_index_in_dim(w, r, 1, keepdims=False)
    w_ref = wsum * inv_lam_n
    return (jnp.sum(w_r * w_r, axis=0), jnp.sum(w_ref * w_ref, axis=0),
            jnp.max(jnp.abs(w_r - w_ref), axis=0),
            jnp.max(jnp.abs(_as_bf16(w_r) - w_ref), axis=0),
            jnp.max(jnp.abs(w_ref), axis=0))


def label_sets(ds):
    """(K, n_shard, L) ids of a dataset's rows: one class id a row is the
    set of size one."""
    ids = ds.classes
    return ids if ids.ndim == 3 else ids[..., None]


def recompute(ds, w, alpha, lam: float, loss: str = "hinge",
              row_block: int = ROW_BLOCK) -> dict:
    """Every class's objectives and w_t(alpha_t) on ``ds`` (a padded-CSR
    ``ShardedDataset`` whose rows carry label sets, read as plain arrays)
    for the program's W (d, R, 128) and alpha (K, n_shard, R, 128).  Lists
    are by class id, T long."""
    if ds.layout != "sparse" or ds.classes is None or \
            ds.sp_row_ptr is not None:
        raise ValueError("the label-set reference reads padded-CSR rows "
                         "that carry class ids")
    t_count, n, d = ds.num_classes, ds.n, ds.num_features
    w32 = jnp.asarray(w, jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    tiles = w32.shape[1]
    ids = label_sets(ds)
    ids_t = jnp.swapaxes(ids, 1, 2)
    rows = min(row_block, ds.n_shard)
    parts = [np.zeros((tiles, CLASS_BLOCK)) for _ in range(8)]
    with jax.default_matmul_precision("highest"):
        for r in range(tiles):
            whi = jnp.zeros((d, CLASS_BLOCK), jnp.float32)
            wlo = jnp.zeros_like(whi)
            sums = np.zeros((3, CLASS_BLOCK))
            for s in range(ds.k):
                p, p16, a, whi, wlo = _shard_parts(
                    loss, rows, whi, wlo, ds.sp_indices, ds.sp_values,
                    ids_t, ds.mask, w32, alpha, s, r)
                sums += np.asarray([p, p16, a], np.float64)
            errs = _class_block_errors(w32, whi + wlo, r, 1.0 / (lam * n))
            for out, val in zip(parts, (*sums, *errs)):
                out[r] = np.asarray(val, np.float64)
            del whi, wlo
    psum, psum16, asum, w_sq, wref_sq, w_err, w_err16, w_inf = (
        p.reshape(-1)[:t_count] for p in parts)
    pad = np.concatenate([p.reshape(-1)[t_count:] for p in parts[3:]])
    reg = 0.5 * lam * w_sq
    dual = asum / n - 0.5 * lam * wref_sq
    primal = psum / n + reg
    scale = np.maximum(1.0, w_inf)
    live = np.asarray(ds.mask) > 0
    ids_host = np.asarray(ids)[live]
    return dict(
        primal=primal.tolist(), dual=dual.tolist(),
        gaps=(primal - dual).tolist(),
        gaps_bf16=(psum16 / n + reg - dual).tolist(),
        w_err=(w_err / scale).tolist(),
        w_err_bf16=(w_err16 / scale).tolist(), w_scale=w_inf.tolist(),
        # the lanes past T: no model there, W and w(alpha) both zero
        pad_lanes_max=float(pad.max(initial=0.0)),
        alpha_min=float(jnp.min(alpha)), alpha_max=float(jnp.max(alpha)),
        label_rows=np.bincount(ids_host[ids_host >= 0],
                               minlength=t_count).tolist(),
        labels_per_row=float((ids_host >= 0).sum() / max(1, n)))
