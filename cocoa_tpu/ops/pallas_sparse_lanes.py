"""One-vs-rest SDCA round on sparse rows: the class axis on the LANES.

``ops/pallas_sparse_hbm.py`` runs ONE model's chain over padded-CSR rows
with w, α and the rows in HBM.  This module runs T of them side by side
over the same sampled rows (a multi-label set, one L2-regularised SVM a
label: ``ShardedDataset.classes`` holds a row's label SET): for the sampled
row i of shard k and every class t at once,

    margin_t = x_i·w_t + σ′·x_i·Δw_kt,   α_ti ← losses.alpha_step,
    Δw_kt += coef_t·x_i,                  y_ti = +1 iff t ∈ L_i,

T independent binary jobs that share every row fetch.  The class axis is
the LAST of the state and is held ``T_pad`` wide, T rounded up to whole
1,024-lane tiles (data/sharding.class_pad), AS tiles: W is (d, R, 128) and
α is (K, n_shard, R, 128), R = T_pad / 128 (``class_tile_shape``), so that
one column of W, one row's α's, a margin, a coefficient are each one
(R, 128) float32 tile, contiguous in HBM (the device tiles an array's last
two axes: a (d, T_pad) array would spread a column's values over 8-column
tiles), and a step is one multiply-add a nonzero a pass for all T models.
The lanes past T hold α = 0 and W = 0 and are never stepped.

At T = 1,000, d = 203,882, n = 1,186,239 (amazoncat13k) W is 0.84 GB and
α 4.86 GB: both stay in HBM, as kddb's w and α do.  What differs from the
T = 1 chain is what a step MOVES: every nonzero of the sampled row reads a
4 KB row of W and of the shard's Δw and writes the latter back, 0.9 MB a
step where kddb's step moves 0.6 KB.  ``hbm_plan`` bounds a ``sorted``
segment's local ids by S·W, and at (2·T_pad·4) B an id of [w | Δw] 88 MB
of VMEM hold 11,264 ids: 32 steps at W = 256, 3,700 segments a round, each
with its three sorts and two row gathers.  So this kernel takes the
``direct`` plan's shape with a DMA ring in the sort's place (PERF.md §6,
PR 48, has the probe's two readings): the local id IS the column, W and
Δw_k stay in HBM as (d, 8·tiles, 128) arrays, and the chain fetches the
rows a step touches itself, 8 slots a group, a group's reads in flight
while the group before is added up; the step's scatter writes Δw's rows
back the same way.  α rides the same ring: one row in, one row out a step,
in place (the array is aliased through the call).  A step ends when its
writes have landed, so the next step's reads see them: a row, or a column
(the bias column is in every row), touched twice in a row needs no link.

What is shared with ``pallas_sparse_hbm``: the plan's record (``HbmPlan``,
made here by ``lanes_plan``), the row fetch (``_fetch_rows``), the step
tables' form (a step's slots and then its scalars, ``CHUNK`` steps an SMEM
block), the rectangle's 8-slot groups and the step math (``losses.alpha_step``,
``mode_factors``, ``coef_divisor``).  Scopes: building the tables is
``cocoa_sparse_gather``, the chain ``cocoa_local_solve``, zeroing Δw_k and
adding it to the round's ΔW ``cocoa_dw_reduce``.

``sparse_lanes_round_fori`` is the same round in plain XLA (a
``fori_loop`` of row gathers and scatter-adds): the path off the TPU, and
the oracle the interpreted kernel is held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.data.sharding import class_signs, label_sets
from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.ops.pallas_sdca import LANES, check_dtype
from cocoa_tpu.ops.pallas_sparse import row_lengths
from cocoa_tpu.ops.pallas_sparse_hbm import (CHUNK, HBM_SMEM_BUDGET,
                                             HBM_VMEM_BUDGET, HBM_VMEM_LIMIT,
                                             HbmPlan, _fetch_rows, _w_round,
                                             rows_on_lanes)
from cocoa_tpu.ops.rows import SLOT_GROUP
from cocoa_tpu.telemetry.tracing import (SCOPE_DW_REDUCE, SCOPE_LOCAL_SOLVE,
                                         SCOPE_SPARSE_GATHER)

N_INT = 2                        # per-step integers ahead of the label ids:
                                 # (nnz, the row's position in its shard)
N_FLT = 1                        # per-step floats: σ′‖x‖²
LANES_VMEM_BUDGET = HBM_VMEM_BUDGET  # one step's [w | Δw] rows and its α
LANES_SMEM_BUDGET = HBM_SMEM_BUDGET  # the two double-buffered step tables


def lane_table_width(w_r: int, label_slots: int) -> int:
    """Words a step takes in each SMEM table (ops/pallas_sparse_hbm
    ``_table_width``): its W slots, then its scalars — here (nnz, row) and
    the row's ``label_slots`` class ids."""
    return -(-(w_r + N_INT + label_slots) // 16) * 16


def lanes_plan(max_nnz: int, h: int, itemsize: int, t_pad: int,
               label_slots: int):
    """The ``HbmPlan`` of the chain with a class axis on the lanes: one call
    a shard's round (``t`` = 1), a block of steps as large as SMEM holds
    its two tables (a step's slots, then nnz, row and ``label_slots`` class
    ids), and ``m`` = the ``w_r`` ids of one step, held as [w | Δw] rows of
    (2·t_pad·itemsize) B each; None where those outgrow the VMEM budget (a
    class axis of many tiles on very long rows)."""
    w_r = _w_round(max_nnz)
    smem = lambda chunk: 2 * 2 * 4 * chunk * lane_table_width(  # noqa: E731
        w_r, label_slots)
    chunk = CHUNK
    while chunk > 8 and smem(chunk) > LANES_SMEM_BUDGET:
        chunk //= 2
    if (smem(chunk) > LANES_SMEM_BUDGET
            or (2 * w_r + 2) * t_pad * itemsize > LANES_VMEM_BUDGET):
        return None
    return HbmPlan(t=1, s=-(-h // chunk) * chunk, m=w_r, w_r=w_r,
                   chunk=chunk, direct=True, t_pad=t_pad)


def lanes_fits(max_nnz: int, h: int, itemsize: int, t_pad: int,
               label_slots: int) -> bool:
    """The resolver's gate: a plan exists (a step fits the budgets)."""
    return lanes_plan(max_nnz, h, itemsize, t_pad, label_slots) is not None


def _lane_chain_kernel(shard_ref,  # SMEM (1,) int32: the shard
                       itab_ref,   # SMEM (1, CHUNK·wt): columns, nnz, row, ids
                       ftab_ref,   # SMEM (1, CHUNK·wt): values, q
                       w_hbm,      # ANY (d, R, 128): W, read only
                       dw_in,      # ANY (d, R, 128): Δw_k (aliased)
                       a_in,       # ANY (K, n_shard, R, 128): α (aliased)
                       dw_hbm,     # the same two arrays as outputs: the
                       a_hbm,      # chain reads and writes these
                       wbuf,       # VMEM (w_r, R, 128): the step's W rows
                       dbuf,       # VMEM (w_r, R, 128): its Δw rows
                       abuf,       # VMEM (2, R, 128): α in, α out
                       sem,        # DMA (2, 2): [reads | writes] by parity
                       asem,       # DMA (2,): α in, α out
                       *, lam_n: float, coef_div: float, sig_eff: float,
                       frozen: bool, w_r: int, chunk: int, loss: str,
                       smoothing: float, classes: int, label_slots: int):
    del dw_in, a_in
    wt = lane_table_width(w_r, label_slots)
    group = SLOT_GROUP
    shard = shard_ref[0]
    shape = abuf.shape[1:]
    dtype = abuf.dtype
    cls = (lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + lax.broadcasted_iota(jnp.int32, shape, 1))

    def step(s, carry):
        base = s * wt
        cnt = itab_ref[0, base + w_r]

        @pl.when(cnt >= 0)              # (-1: a step that pads the block)
        def _live():
            row = itab_ref[0, base + w_r + 1]
            groups = (cnt + (group - 1)) // group
            a_read = pltpu.make_async_copy(a_hbm.at[shard, row], abuf.at[0],
                                           asem.at[0])
            a_read.start()

            def reads(g):
                """The copies that bring group ``g``'s rows of W and Δw_k.
                A slot past the row's length (the last group's) holds
                column 0 and value 0: its rows are read, add nothing and
                are never written back."""
                out = []
                for u in range(group):
                    slot = g * group + u
                    c = itab_ref[0, base + slot]
                    out.append(pltpu.make_async_copy(
                        w_hbm.at[c], wbuf.at[slot], sem.at[0, g & 1]))
                    out.append(pltpu.make_async_copy(
                        dw_hbm.at[c], dbuf.at[slot], sem.at[0, g & 1]))
                return out

            for cp in reads(0):
                cp.start()
            a_read.wait()

            # margin_t = x·w_t + sig_eff·x·Δw_kt: one multiply-add a
            # nonzero on the (R, 128) tile; group g + 1's rows are in
            # flight while group g's are added up
            def margin_group(g, acc):
                @pl.when(g + 1 < groups)
                def _ahead():
                    for cp in reads(g + 1):
                        cp.start()

                for cp in reads(g):
                    cp.wait()
                for u in range(group):
                    slot = g * group + u
                    vj = ftab_ref[0, base + slot]
                    rows = wbuf[slot]
                    if not frozen:
                        rows = rows + sig_eff * dbuf[slot]
                    acc = acc + rows * vj
                return acc

            acc = lax.fori_loop(0, groups, margin_group,
                                jnp.zeros(shape, dtype))
            hit = cls < 0
            for l in range(label_slots):
                hit = hit | (cls == itab_ref[0, base + w_r + N_INT + l])
            y = jnp.where(hit, 1.0, -1.0).astype(dtype)
            qii = jnp.full(shape, ftab_ref[0, base + w_r], dtype)
            a = abuf[0]
            new_a = losses.alpha_step(loss, a, y * acc, qii, lam_n,
                                      smoothing=smoothing)
            new_a = jnp.where(cls < classes, new_a, a).astype(dtype)
            coef = y * (new_a - a) / coef_div
            abuf[1] = new_a
            a_write = pltpu.make_async_copy(abuf.at[1], a_hbm.at[shard, row],
                                            asem.at[1])
            a_write.start()

            # Δw_k += coef·x: the rows are in VMEM already; each goes back
            # as it is updated, a group's writes in flight while the next
            # group is updated.  A row has no column twice; the slots past
            # its length (in its last group) are not written: one of them
            # would put back the Δw_k[0] it read before this step's stores.
            full, rest = cnt // group, cnt % group

            def update(g):
                for u in range(group):
                    slot = g * group + u
                    dbuf[slot] = dbuf[slot] + coef * ftab_ref[0, base + slot]

            def writes(g, do, own=lambda u, f: f()):
                """``do`` (start or wait) group ``g``'s copies back to Δw_k,
                each under ``own(u, .)``: every slot of a whole group."""
                for u in range(group):
                    slot = g * group + u
                    cp = pltpu.make_async_copy(
                        dbuf.at[slot], dw_hbm.at[itab_ref[0, base + slot]],
                        sem.at[1, g & 1])
                    own(u, functools.partial(do, cp))

            def rows_own(u, f):         # the last group: the row's own slots
                pl.when(u < rest)(f)

            start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

            def scatter_group(g, carry_):
                update(g)
                writes(g, start)
                pl.when(g > 0)(lambda: writes(g - 1, wait))
                return carry_

            lax.fori_loop(0, full, scatter_group, jnp.int32(0))

            @pl.when(rest > 0)
            def _partial():
                update(full)
                writes(full, start, rows_own)

            pl.when(full > 0)(lambda: writes(full - 1, wait))
            pl.when(rest > 0)(lambda: writes(full, wait, rows_own))
            a_write.wait()

        return carry

    lax.fori_loop(0, chunk, step, jnp.int32(0))


def _lane_chain_call(plan: HbmPlan, k: int, n_shard: int, d: int, dtype,
                     interpret: bool, label_slots: int, **consts):
    """The ``pallas_call`` of one shard's round: (shard, itab, ftab, W,
    Δw_k, α) -> (Δw_k, α), the last two updated in place."""
    wt = lane_table_width(plan.w_r, label_slots)
    r = plan.t_pad // LANES
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_lane_chain_kernel, w_r=plan.w_r, chunk=plan.chunk,
                          label_slots=label_slots, **consts),
        grid=(plan.s // plan.chunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, plan.chunk * wt), lambda c: (0, c),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, plan.chunk * wt), lambda c: (0, c),
                         memory_space=pltpu.SMEM),
            any_, any_, any_,
        ],
        out_specs=[any_, any_],
        out_shape=[jax.ShapeDtypeStruct((d, r, LANES), dtype),
                   jax.ShapeDtypeStruct((k, n_shard, r, LANES), dtype)],
        input_output_aliases={4: 0, 5: 1},
        scratch_shapes=[pltpu.VMEM((plan.w_r, r, LANES), dtype),
                        pltpu.VMEM((plan.w_r, r, LANES), dtype),
                        pltpu.VMEM((2, r, LANES), dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=HBM_VMEM_LIMIT,
            has_side_effects=True,
        ),
        interpret=interpret,
        name="pallas_sparse_lanes_round",
    )


def _step_scalars(shards: dict, shard, idx, qii_factor, dtype):
    """(cnt, ids, q) of the sampled rows ``idx`` of shard ``shard``."""
    at = lambda a: lax.dynamic_index_in_dim(  # noqa: E731
        a, shard, 0, keepdims=False)[idx]
    row_len = shards.get("sp_row_len")
    if row_len is None:
        row_len = row_lengths(shards["sp_values"])
    # the label sets: a TPU stores (K, n_shard, L) ids with the row index on
    # the lanes (L = 8 on the lanes would pad 16-fold), and a gather of
    # whole rows makes it copy them all into that padded form first, 0.6 GB
    # a dispatch at amazoncat13k.  Read as stored, a shard's ids are L runs
    # of n_shard, and a row's are L single elements n_shard apart
    ids_t = jnp.swapaxes(label_sets(shards["classes"], 2), -1, -2)
    slots, n_shard = ids_t.shape[1:]
    flat = lax.dynamic_index_in_dim(ids_t.reshape(ids_t.shape[0], -1), shard,
                                    0, keepdims=False)
    ids = flat[idx[:, None] + jnp.arange(slots, dtype=idx.dtype) * n_shard]
    return (at(row_len), ids,
            (at(shards["sq_norms"]) * qii_factor).astype(dtype))


def _blend_rows(alpha, before, idxs, scaling: float):
    """α after a round whose chain advanced the sampled rows in place:
    α + scaling·(α_chain − α) on those rows (CoCoA's averaging; at
    ``scaling`` 1, CoCoA+ with γ = 1, the chain's α is the round's)."""
    k = alpha.shape[0]
    sh = jnp.arange(k)[:, None]
    return alpha.at[sh, idxs].set(
        before + scaling * (alpha[sh, idxs] - before))


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing", "plan", "classes", "scaling"),
)
def pallas_sparse_lanes_round(
    w: jax.Array,            # (d, R, 128) the round's primal vectors
    alpha: jax.Array,        # (K, n_shard, R, 128)
    shards: dict,            # sp_indices, sp_values, sq_norms, classes, ...
    idxs: jax.Array,         # (K, H) int32 sampled rows
    lam: float,
    n: int,
    classes: int,            # T
    plan: HbmPlan,           # lanes_plan(.., t_pad=T_pad, ..)
    mode: str = "plus",
    sigma: float = 1.0,
    scaling: float = 1.0,
    interpret: bool = False,
    loss: str = "hinge",
    smoothing: float = 1.0,
):
    """One one-vs-rest sparse SDCA round for K shards on this chip, one
    shard after another.  Returns ``(dw_sum (d, R, 128), alpha')``: the K
    shards' ΔW summed, and α after the round (``scaling`` applied)."""
    sp_indices, sp_values = shards["sp_indices"], shards["sp_values"]
    k, n_shard, w_nnz = sp_indices.shape
    h, (d, r, _), dtype = idxs.shape[1], w.shape, w.dtype
    check_dtype(dtype)
    assert plan.t_pad == r * LANES and plan.t == 1, (plan, w.shape)
    sig_eff, qii_factor = mode_factors(mode, sigma)
    s, w_r = plan.s, plan.w_r
    label_slots = label_sets(shards["classes"], 2).shape[-1]
    wt = lane_table_width(w_r, label_slots)
    chain = _lane_chain_call(
        plan, k, n_shard, d, dtype, interpret, label_slots,
        lam_n=float(lam * n), coef_div=float(coef_divisor(mode, lam * n)),
        sig_eff=float(sig_eff), frozen=mode == "frozen",
        loss=losses.validate(loss, smoothing), smoothing=float(smoothing),
        classes=int(classes))
    idxs = idxs.astype(jnp.int32)
    before = (None if scaling == 1.0
              else alpha[jnp.arange(k)[:, None], idxs])
    slot = jnp.arange(w_r)
    on_lanes = rows_on_lanes(n_shard, w_nnz)

    def one_shard(carry, xs):
        alpha, dw_sum = carry
        shard, idx = xs
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            if on_lanes:
                cols, vals = (a.T for a in _fetch_rows(
                    sp_indices, sp_values, shard, idx, interpret))
            else:
                # rows stored row-major (W = 256): a gather of whole rows,
                # straight from the (K, n_shard, W) arrays as they are
                at_rows = shard * n_shard + idx
                cols, vals = (a.reshape(k * n_shard, w_nnz)[at_rows]
                              for a in (sp_indices, sp_values))
            cnt, ids, q = _step_scalars(shards, shard, idx, qii_factor,
                                        dtype)
            widen = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0), (0, w_r - w_nnz)))
            used = slot[None, :] < cnt[:, None]
            fill = lambda n_, dt: jnp.zeros(  # noqa: E731
                (h, wt - w_r - n_), dt)
            itab = jnp.concatenate(
                [jnp.where(used, widen(cols), 0), cnt[:, None], idx[:, None],
                 ids, fill(N_INT + label_slots, jnp.int32)], axis=1
            ).astype(jnp.int32)
            ftab = jnp.concatenate(
                [jnp.where(used, widen(vals), 0), q[:, None],
                 fill(N_FLT, dtype)], axis=1).astype(dtype)
            # the steps that pad the last block: nnz -1, nothing runs
            itab = jnp.pad(itab, ((0, s - h), (0, 0)), constant_values=-1)
            ftab = jnp.pad(ftab, ((0, s - h), (0, 0)))
        with jax.named_scope(SCOPE_DW_REDUCE):
            dwk = jnp.zeros_like(w)
        with jax.named_scope(SCOPE_LOCAL_SOLVE):
            dwk, alpha = chain(jnp.reshape(shard, (1,)).astype(jnp.int32),
                               itab.reshape(1, s * wt),
                               ftab.reshape(1, s * wt), w, dwk, alpha)
        with jax.named_scope(SCOPE_DW_REDUCE):
            return (alpha, dw_sum + dwk), None

    init = (alpha, jnp.zeros((d, r, LANES), dtype))
    xs = (jnp.arange(k, dtype=jnp.int32), idxs)
    if k == 1:
        (alpha, dw_sum), _ = one_shard(init,
                                       jax.tree.map(lambda a: a[0], xs))
    else:
        (alpha, dw_sum), _ = lax.scan(one_shard, init, xs)
    if before is not None:
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            alpha = _blend_rows(alpha, before, idxs, scaling)
    return dw_sum, alpha


def sparse_lanes_round_fori(w, alpha, shards: dict, idxs, lam: float, n: int,
                            classes: int, mode: str = "plus",
                            sigma: float = 1.0, scaling: float = 1.0,
                            loss: str = "hinge", smoothing: float = 1.0):
    """:func:`pallas_sparse_lanes_round` in plain XLA: the same chain, a
    row gather and a scatter-add a step, one shard after another.  A row
    comes through ``ops/rows.get_row``: a rectangle's (W,) slots as they
    are, or a (W,) window of rows kept as a stream (the plain twin of
    ops/pallas_longrows_lanes.py's chain too)."""
    from cocoa_tpu.ops.rows import get_row

    k = shards["sp_indices"].shape[0]
    stored = {f: shards[f] for f in ("sp_indices", "sp_values", "sp_row_ptr",
                                     "sp_row_len", "sp_row_iota")
              if f in shards}
    h, tile, dtype = idxs.shape[1], w.shape[1:], w.dtype
    sig_eff, qii_factor = mode_factors(mode, sigma)
    lam_n, coef_div = lam * n, coef_divisor(mode, lam * n)
    ids_all = label_sets(shards["classes"], 2)
    live = jnp.arange(tile[0] * tile[1]).reshape(tile) < classes
    idxs = idxs.astype(jnp.int32)

    def one_shard(carry, xs):
        alpha, dw_sum = carry
        shard, idx = xs
        take = lambda a: lax.dynamic_index_in_dim(  # noqa: E731
            a, shard, 0, keepdims=False)
        rows_k = jax.tree.map(take, stored)
        ids_k, q_k = take(ids_all), take(shards["sq_norms"]) * qii_factor

        def step(j, state):
            alpha_k, dwk = state
            i = idx[j]
            row = get_row(rows_k, i)
            c, v = row.idx, row.val.astype(dtype)
            rows = w[c] if mode == "frozen" else w[c] + sig_eff * dwk[c]
            z = (rows * v[:, None, None]).sum(0)
            y = class_signs(ids_k[i], classes, dtype)
            a = alpha_k[i]
            new_a = losses.alpha_step(loss, a, y * z, q_k[i].astype(dtype),
                                      lam_n, smoothing=smoothing)
            new_a = jnp.where(live, new_a, a).astype(dtype)
            coef = y * (new_a - a) / coef_div
            return (alpha_k.at[i].set(new_a),
                    dwk.at[c].add(v[:, None, None] * coef[None]))

        alpha_k, dwk = lax.fori_loop(
            0, h, step, (take(alpha), jnp.zeros_like(w)))
        return (lax.dynamic_update_index_in_dim(alpha, alpha_k, shard, 0),
                dw_sum + dwk), None

    (alpha_new, dw_sum), _ = lax.scan(
        one_shard, (alpha, jnp.zeros_like(w)),
        (jnp.arange(k, dtype=jnp.int32), idxs))
    if scaling != 1.0:
        sh = jnp.arange(k)[:, None]
        alpha_new = _blend_rows(alpha_new, alpha[sh, idxs], idxs, scaling)
    return dw_sum, alpha_new
