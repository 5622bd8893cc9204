"""The plain reference for one-vs-rest over LABEL SETS on sparse rows kept
as a STREAM: T L2-regularised linear classifiers over one set of rows whose
nonzeros lie end to end (``data/sharding.py``: ``sp_row_ptr`` a row's first
slot / 8, ``sp_row_len`` its nonzeros), a row carrying the set of the
labels it has, written out.  ``reference_labels.py``'s arithmetic (its own
copy here: the two files share nothing but their words) over another
storage.

Independent of ``cocoa_tpu.evals.objectives``, ``ops/losses.py``,
``ops/rows.py`` and every kernel: from the returned alpha and the rows'
label sets ALONE it recomputes, in straight ``jax.numpy`` float32,

    y_ti         = +1 where t is among row i's ids, else -1
    w_t(alpha_t) = (1/(lam n)) sum_i alpha_ti y_ti x_i
    P_t(w_t)     = (1/n) sum_i loss(y_ti x_i . w_t) + (lam/2) |w_t|^2
    D_t(alpha_t) = (1/n) sum_i -loss*(-alpha_ti) - (lam/2) |w_t(alpha_t)|^2

with P at the RETURNED w_t.  The program holds the class axis as (R, 128)
tiles — W (d, R, 128), alpha (K, n_shard, R, 128), class t at [t // 128,
t % 128] — and the reference reads them so: one call is one shard and
``TILES`` tiles of ``CLASS_BLOCK`` = 128 classes, so that a slot of the
stream moves ONE row of W and adds ONE row into the sum for all of them (a
gathered and scattered row costs ~73 ns on the v5e at 1, 2 and 4 KB alike:
at delicious200k the check reads 10 s + 114 s / TILES — 109 s of a 140 s
set-up at one tile a call with a second gather, 38 s at four, 28 s at
eight, which would hold 9.6 GB of sums; PERF.md section 6, PR 57).  It
does NOT walk the stream as the program
does (by sampled row through a ring, by blocks of neighbouring rows in the
certificate): it visits a shard's rows in order of LENGTH, ``ROW_BLOCK`` at
a time, and reads piece j of every row of the block at once — the stream
is stored in pieces of ``SLOT_BLOCK`` = 128 slots, a row starts
``row_ptr`` mod 16 groups into its first piece, and a slot of the piece
that is before the row's first or past its last is masked out — as far as
the block's longest row reaches (rows of one length share a block, so a
row of 8,192 nonzeros costs its own pieces, not 65 a row).  Its largest
temporary is the gather of (ROW_BLOCK, SLOT_BLOCK, TILES, 128) W values,
33 MB a tile, beside three (d, TILES, 128) sums of alpha y x, 0.4 GB a
tile each at d = 782,585.  The bfloat16 reading rounds the SAME gathered
values (one gather, not two).  Row
dots and the sums over rows are multiply-and-sum on the vector unit, never
a matmul.  The sum of alpha y x over the rows is float32 in two levels, as
``reference_labels.py`` learnt at PR 48: a block's 512 rows are scatter-added
into a part that starts at ZERO, and the part joins the running sum as a
two-float value (Knuth's TwoSum), so a column in every row (the bias)
under a label a sixth of the rows carry does not lose its small terms.

Two counter-readings ride along, for the check's limits to sit between
(checks/certified_gap_labelstream.py): every class's gap with the margins
as ONE bfloat16 pass would take them (``gaps_bf16``) and the returned W
rounded once to bfloat16 against w(alpha) (``w_err_bf16``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlogy

CLASS_BLOCK = 128               # classes a tile: one sublane row of the state
TILES = 4                       # tiles a call (fewer where R is smaller)
ROW_BLOCK = 512                 # rows a step of a call
SLOT_BLOCK = 128                # slots of those rows read at a time: a piece
GROUP = 8                       # slots a row's start is aligned to


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


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _shard_parts(loss, rows, tiles, whi, wlo, cols, vals, ptr, length, order,
                 ids_t, mask, w, alpha, s, r):
    """Shard ``s``, classes [128 r, 128 (r + tiles)): (loss sums, the same
    through a bf16 pass, dual sums, (``whi``, ``wlo``) + sum_i alpha_ti
    y_ti x_i as a two-float value), the first three (tiles, 128), the last
    two (d, tiles, 128).  ``order``: the shard's rows by length."""
    n_shard = ptr.shape[1]
    # the stream as it is stored, a piece of 128 slots a row of the array:
    # a row of the data is read a piece at a time (one gather of whole
    # array rows for the block; single slots gathered one by one cost 8 ms
    # a step here, and an 8-wide view of the stream is padded sixteen-fold)
    of = lambda a: jax.lax.dynamic_index_in_dim(a, s, 0, False)  # noqa: E731
    piece_c, piece_v = of(cols), of(vals)
    ptr_s, len_s, mask_s, order_s = of(ptr), of(length), of(mask), of(order)
    ids_s, alpha_s = of(ids_t), of(alpha)          # (L, n_shard), (n, R, 128)
    lanes = ((r + jnp.arange(tiles))[:, None] * CLASS_BLOCK
             + jnp.arange(CLASS_BLOCK))                        # (tiles, 128)
    n_blocks = -(-n_shard // rows)
    slot = jnp.arange(SLOT_BLOCK)
    per_piece = SLOT_BLOCK // GROUP

    def block(b, carry):
        psum, psum16, asum, whi, wlo = carry
        start = jnp.minimum(b * rows, n_shard - rows)
        which = jax.lax.dynamic_slice_in_dim(order_s, start, rows)
        # the rows this block shares with the one before it are that one's
        own = (mask_s[which]
               * (start + jnp.arange(rows) >= b * rows))[:, None, None]
        # a row starts in piece ``first``, ``lead`` slots into it
        first = ptr_s[which] // per_piece
        lead = (ptr_s[which] % per_piece) * GROUP
        count = len_s[which]
        y = jnp.where((ids_s[:, which].T[:, :, None, None]
                       == lanes[None, None]).any(1), 1.0, -1.0)
        a = jax.lax.dynamic_slice_in_dim(alpha_s[which], r, tiles, 1)
        coef = a * y * own                              # (rows, tiles, 128)
        longest = jnp.max(jnp.where(count > 0, lead + count, 0))

        def chunk(j, acc):
            m, m16, part = acc
            # piece j of every row of the block: the row's own slots of it
            at = jnp.minimum(first + j, piece_c.shape[0] - 1)
            pos = j * SLOT_BLOCK + slot[None, :] - lead[:, None]
            real = (pos >= 0) & (pos < count[:, None])
            cj = jnp.where(real, piece_c[at], 0)
            vj = jnp.where(real, piece_v[at], 0.0)[:, :, None, None]
            # whole (R, 128) rows of W as it is stored, then the call's
            # tiles of them: a gather of (tiles, 128) pieces of rows makes
            # the compiler relay all of W first, a 3.2 GB copy a call
            wj = jax.lax.dynamic_slice_in_dim(w[cj], r, tiles, 2)
            m = m + jnp.sum(wj * vj, axis=1)
            m16 = m16 + jnp.sum(_as_bf16(wj) * _as_bf16(vj), axis=1)
            part = part.at[cj].add(vj * coef[:, None])
            return m, m16, part

        zero = jnp.zeros((rows, tiles, CLASS_BLOCK), jnp.float32)
        m, m16, part = jax.lax.fori_loop(
            0, (longest + SLOT_BLOCK - 1) // SLOT_BLOCK, chunk,
            (zero, zero, jnp.zeros_like(whi)))
        primal, dual = _losses(loss, y * m, a)
        primal16, _ = _losses(loss, y * m16, a)
        weigh = lambda x: jnp.sum(x * own, axis=0)  # noqa: E731
        return (psum + weigh(primal), psum16 + weigh(primal16),
                asum + weigh(dual), *_two_sum(whi, wlo, part))

    zero = jnp.zeros((tiles, CLASS_BLOCK), jnp.float32)
    return jax.lax.fori_loop(0, n_blocks, block,
                             (zero, zero, zero, whi, wlo))


@jax.jit
def _class_block_errors(w, wsum, r, inv_lam_n):
    """Of the classes of ``wsum`` (d, tiles, 128), tiles [r, r + tiles) of
    W: (|w|^2, |w(alpha)|^2, max |w - w(alpha)|, max |bf16(w) - w(alpha)|,
    |w(alpha)|_inf), each (tiles, 128)."""
    w_r = jax.lax.dynamic_slice_in_dim(w, r, wsum.shape[1], 1)
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
              row_block: int = ROW_BLOCK, tiles: int = TILES) -> dict:
    """Every class's objectives and w_t(alpha_t) on ``ds`` (a stream
    ``ShardedDataset`` whose rows carry label sets, read as plain arrays)
    for the program's W (d, R, 128) and alpha (K, n_shard, R, 128).  Lists
    are by class id, T long."""
    if ds.layout != "sparse" or ds.classes is None or ds.sp_row_ptr is None:
        raise ValueError("the label-stream reference reads rows kept as a "
                         "stream that carry class ids")
    t_count, n, d = ds.num_classes, ds.n, ds.num_features
    w32 = jnp.asarray(w, jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    all_tiles = w32.shape[1]
    # (the largest count under ``tiles`` that divides R: a call is whole)
    tiles = max(t for t in range(1, min(tiles, all_tiles) + 1)
                if all_tiles % t == 0)
    ids = label_sets(ds)
    ids_t = jnp.swapaxes(ids, 1, 2)
    rows = min(row_block, ds.n_shard)
    # a shard's rows by length, the longest first (a padding row holds
    # nothing and sorts last)
    order = jnp.argsort(-ds.sp_row_len, axis=1, stable=True).astype(jnp.int32)
    parts = [np.zeros((all_tiles, CLASS_BLOCK)) for _ in range(8)]
    with jax.default_matmul_precision("highest"):
        for r in range(0, all_tiles, tiles):
            whi = jnp.zeros((d, tiles, CLASS_BLOCK), jnp.float32)
            wlo = jnp.zeros_like(whi)
            sums = np.zeros((3, tiles, CLASS_BLOCK))
            for s in range(ds.k):
                p, p16, a, whi, wlo = _shard_parts(
                    loss, rows, tiles, whi, wlo, ds.sp_indices,
                    ds.sp_values, ds.sp_row_ptr, ds.sp_row_len, order,
                    ids_t, ds.mask, w32, alpha, s, r)
                sums += np.asarray([p, p16, a], np.float64)
            errs = _class_block_errors(w32, whi + wlo, r, 1.0 / (lam * n))
            for out, val in zip(parts, (*sums, *errs)):
                out[r:r + tiles] = np.asarray(val, np.float64)
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
