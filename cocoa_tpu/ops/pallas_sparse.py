"""Pallas TPU kernel for the sequential SDCA inner loop — padded-CSR layout.

The XLA lowering of the sparse inner loop (ops/local_sdca.py with the
padded-CSR row accessors, ops/rows.py:46,53) serializes the per-nonzero
gather into ``Δw``/``w`` and the scatter-add back — measured ~44 µs per
coordinate step at rcv1 scale, plus a ~13 ms/round batched gather to
precompute the round's margins.  This kernel removes both:

- ``w`` and the Δw accumulator live **lane-blocked AND lane-concatenated**
  in VMEM as one (ceil(d/128), 2·128) array per shard (w in lanes [0,128),
  Δw in [128,256)), so a nonzero's margin contribution — which needs BOTH
  w[f] and Δw[f] — is ONE dynamic sublane slice + two 256-wide mask picks,
  and the scatter is a masked row update through the same slice.  Per
  nonzero: 2 dynamically-addressed VMEM accesses.  (What paces a step
  here has not been measured on the chip; at the dense kernel it was the
  step's 0-d values, each a trip to the scalar core and back, not the
  addresses: pallas_sdca._advance, PERF.md §6, PR 39.)
- margins are computed **in-kernel** from the VMEM-resident ``w``
  (``margin = x·w + sig_eff·(x·Δw)``, the same decomposition as
  ops/local_sdca.py ``mode_factors`` with margins0 evaluated on the fly),
  so the per-round whole-shard margins gather disappears.
- the per-shard scalars (y, ‖x‖², α) are lane-concatenated the same way —
  one (n/128, 3·128) array per shard, one dynamic read + one write per
  step.

**Shard interleaving.**  The grid is 1-D over steps; each iteration
advances EVERY shard's chain by one step, with SEPARATE scratch refs per
shard (shared refs make Mosaic serialize on aliasing) — the K independent
per-nonzero dependency chains overlap.  k=1 (the shard_map per-device
case) degenerates to the plain sequential kernel.

Addressing constraint: Mosaic has no vector→scalar extraction, so every
dynamic address must come from SMEM.  The sampled rows' **feature
indices** AND **values** are gathered device-side outside the kernel into
(K, H_seg, max_nnz) tables and scalar-prefetched (SMEM holds f32 scalars
fine).  Round 3 kept the values in VMEM and picked nonzero j's value with
a max_nnz-wide lane mask; at heavy-tailed widths that one pick was the
widest op in the loop AND scaled with the PADDED width — an SMEM scalar
read costs O(1) regardless of W and removed the kernel's value
blocks/DMAs entirely.

Padded nonzero slots carry index 0 / value 0 and contribute exactly 0 to
every pick and scatter — no masking needed (same inertness trick as the
XLA path, ops/rows.py:10-11).

**Heavy-tailed rows (round 4).**  The padded width W is the MAX row nnz
across the dataset; real rcv1-like data is heavy-tailed (log-normal
document lengths), so W (~550) is ~7x the mean (~73) — and a flat unroll
over W slots per step both wastes ~85% of the per-nonzero work on padding
and blows Mosaic compile time up superlinearly in the unrolled-slot count
(measured: W=548 flat → 7 min compile; a pl.when-group-early-exit variant
kept the unroll and still compiled for minutes).  The per-nonzero loop is
therefore a **dynamic-trip ``fori_loop`` over GROUP-slot bodies**: the
trip count is ceil(row_nnz / GROUP) from a scalar-prefetched per-row
count, the body unrolls GROUP slots (values and indices are SMEM scalar
reads at dynamic group offsets), and the round-3 dead-end — ~200 ns of
scalar-branch overhead per dynamic iteration (read 2026-07-31, in this
kernel, with every float of a step on the scalar core; ops/
pallas_sparse_hbm.py's docstring has the reading since, PR 47) — amortizes
to ~6 ns per nonzero at GROUP=32.  Per step the cost tracks ceil(nnz/32)·32 slots
instead of W, compile size is ONE group body per pass per shard, and any
padded width works with no special tail.

Size guards: the SMEM index table is K·H_seg·max_nnz ints and must stay
under ``SMEM_IDX_BUDGET`` (512 KB — the 712 KB full-round rcv1 table
fails Mosaic compilation, so rounds split into SMEM-sized segments with
the concatenated state carried between them); ``sparse_kernel_fits``
checks the VMEM working set.  Oversized configs keep the XLA fori_loop
path.

This module also carries the SPARSE BLOCK-CHAIN kernels (round 6):
``sparse_block_gram`` / ``sparse_block_apply`` compute the ``--blockSize``
path's (B, B) block Gram, margin base, and rank-B Δw apply from the same
SMEM-prefetched CSR layout — no (B, d) densify — feeding the lockstep
chain recurrence of ops/pallas_chain.py (see the section comment below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.ops.pallas_sdca import LANES, check_dtype

ROW_BLOCK = 8          # aligned sublane block for the per-step value row
SMEM_IDX_BUDGET = 512 << 10
VMEM_BUDGET = 12 << 20


def sparse_vmem_estimate(n_shard: int, d: int, max_nnz: int, itemsize: int,
                         k: int = 1, n_hot: int = 0) -> int:
    """All K shards resident (the interleaved grid): per shard the
    (n_dblk, 2·128) w|Δw array ×3 (input, scratch, output with
    double-buffer slack) + the (n_blocks, 3·128) scalar stack ×3, plus the
    double-buffered (8, max_nnz) value blocks.  The hybrid layout
    (``n_hot > 0``, the hot/cold split) adds per shard the (n_hot/128,
    128) hot-Δw array ×3 plus the shared w_hot operand and the per-step
    hot row's double buffer."""
    n_pad = -(-n_shard // LANES) * LANES
    d_pad = -(-d // LANES) * LANES
    del max_nnz  # values ride SMEM now (module docstring)
    return itemsize * (k * (6 * d_pad + 9 * n_pad)
                       + n_hot * (3 * k + 1) + 2 * k * n_hot)


def sparse_kernel_fits(k: int, n_shard: int, d: int, max_nnz: int, h: int,
                       itemsize: int, n_hot: int = 0) -> bool:
    """VMEM feasibility (the SMEM index-table limit is handled by splitting
    the round into segments — see :func:`pallas_sparse_sdca_round`)."""
    del h
    return (
        segment_len(k, max_nnz) >= 1
        and (n_hot == 0 or n_hot % LANES == 0)
        and sparse_vmem_estimate(n_shard, d, max_nnz, itemsize, k, n_hot)
        <= VMEM_BUDGET
    )


def segment_len(k: int, max_nnz: int) -> int:
    """Steps per kernel invocation so the two (K, H_seg, max_nnz) SMEM
    tables (int32 feature indices + f32 values) stay inside the budget."""
    return SMEM_IDX_BUDGET // (8 * k * max(1, max_nnz))


GROUP = 32             # slots per dynamic-loop body (one branch per GROUP)


def _kernel(
    idxs_ref,        # scalar-prefetch: (K, H_seg) int32 sampled rows
    gidx_ref,        # scalar-prefetch: (K, H_seg, W) int32 feature indices
    svals_ref,       # scalar-prefetch: (K, H_seg, W) f32 nonzero values
    cnts_ref,        # scalar-prefetch: (K, H_seg) int32 per-row nnz counts
    *refs,           # wd_in, st_in[, hot refs], outs, scratch
    lam_n: float,
    coef_div: float,
    sig_eff: float,
    qii_factor: float,
    frozen: bool,
    h: int,
    w_nnz: int,
    loss: str,
    smoothing: float,
    k: int,
    n_hblk: int = 0,
):
    # refs layout (see module docstring for the concatenated layouts):
    #   wd_in         (K, n_dblk, 2·LANES): [w | Δw_carried] per shard
    #   st_in         (K, n_blocks, 3·LANES): [labels | ‖x‖² | α] per shard
    #   hybrid (n_hblk > 0 — the hot/cold split, docs/DESIGN.md §3b-vi):
    #   hw_in         (n_hblk, LANES): w at the hot columns, read-only and
    #                    shared by all shards (the kernel never writes w)
    #   hd_in         (K, n_hblk, LANES): hot Δw carried between segments
    #   hrow_ref      (K, 1, n_hblk, LANES): THIS step's sampled rows' hot
    #                    panel slices (per-step BlockSpec — the pipeline
    #                    double-buffers the next step's rows automatically)
    #   wd_out, st_out[, hd_out] — flushed at segment end; Δw and α carry
    #                    to the next segment through them
    #   wd_scs[kk], st_scs[kk][, hd_scs[kk]] — per-shard scratch (separate
    #                    refs: chains must not alias)
    hot = n_hblk > 0
    if hot:
        wd_in, st_in, hw_in, hd_in, hrow_ref = refs[:5]
        wd_out, st_out, hd_out = refs[5:8]
        scs = refs[8:]
        wd_scs, st_scs = scs[:k], scs[k:2 * k]
        hd_scs = scs[2 * k:3 * k]
    else:
        wd_in, st_in, wd_out, st_out = refs[:4]
        wd_scs = refs[4:4 + k]
        st_scs = refs[4 + k:4 + 2 * k]
        hd_scs = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for kk in range(k):
            wd_scs[kk][...] = wd_in[kk]
            st_scs[kk][...] = st_in[kk]
            if hot:
                hd_scs[kk][...] = hd_in[kk]

    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * LANES), 1)
    lane3 = jax.lax.broadcasted_iota(jnp.int32, (1, 3 * LANES), 1)

    group = min(GROUP, w_nnz)

    for kk in range(k):
        idx = idxs_ref[kk, i]
        cnt = cnts_ref[kk, i]
        n_trips = (cnt + (group - 1)) // group
        blk = idx // LANES
        sub_lane = idx - blk * LANES
        srow = st_scs[kk][pl.ds(blk, 1)]          # (1, 3·LANES)
        y = jnp.sum(jnp.where(lane3 == sub_lane, srow, 0.0))
        sq = jnp.sum(jnp.where(lane3 == sub_lane + LANES, srow, 0.0))
        a = jnp.sum(jnp.where(lane3 == sub_lane + 2 * LANES, srow, 0.0))
        dtype = srow.dtype

        def slot_margin(j):
            # one nonzero's margin contribution: the value/index are O(1)
            # SMEM scalar reads, and ONE dynamic slice serves both the w
            # and Δw picks (they share the concatenated row); slots past
            # the row's count carry index 0 / value 0 and contribute
            # exactly 0 (the trip count rounds up to the group size)
            f = gidx_ref[kk, i, j]
            fb = f // LANES
            fls = f - fb * LANES
            vj = svals_ref[kk, i, j]
            wrow = wd_scs[kk][pl.ds(fb, 1)]       # (1, 2·LANES)
            coord = jnp.sum(jnp.where(lane2 == fls, wrow, 0.0))
            if not frozen:
                coord = coord + sig_eff * jnp.sum(
                    jnp.where(lane2 == fls + LANES, wrow, 0.0)
                )
            return vj * coord

        # margin = x·w + sig_eff·(x·Δw), ceil(cnt/GROUP) dynamic trips of
        # a GROUP-slot unrolled body (module docstring: the dynamic-loop
        # branch overhead amortizes over the group; padding groups never
        # run)
        def margin_body(g, acc):
            base = g * group
            for u in range(group):
                acc = acc + slot_margin(base + u)
            return acc

        margin = jax.lax.fori_loop(0, n_trips, margin_body,
                                   jnp.asarray(0.0, dtype))

        if hot:
            # hot-panel margin term: two whole-array VPU multiply-reduces
            # against the lane-blocked w_hot / Δw_hot — O(n_hot/128)
            # lane-rows where the stream loop pays ~6 scalar ops PER
            # nonzero; the cold stream above covered only the residual
            hrow = hrow_ref[kk, 0]                # (n_hblk, LANES)
            mh = jnp.sum(hrow * hw_in[...])
            if not frozen:
                mh = mh + sig_eff * jnp.sum(hrow * hd_scs[kk][...])
            margin = margin + mh

        new_a = losses.alpha_step(loss, a, y * margin, sq * qii_factor,
                                  lam_n, smoothing=smoothing)
        coef = y * (new_a - a) / coef_div

        def scatter_body(g, carry):
            # scatter-add coef·x into the Δw lanes: one masked row update
            # per nonzero (fresh read — nonzeros may share a lane block);
            # padded slots add exactly 0
            base = g * group
            for u in range(group):
                f = gidx_ref[kk, i, base + u]
                fb = f // LANES
                fls = f - fb * LANES
                vj = svals_ref[kk, i, base + u]
                wrow = wd_scs[kk][pl.ds(fb, 1)]
                wd_scs[kk][pl.ds(fb, 1)] = jnp.where(
                    lane2 == fls + LANES, wrow + coef * vj, wrow
                )
            return carry

        jax.lax.fori_loop(0, n_trips, scatter_body, jnp.int32(0))

        if hot:
            # hot-panel Δw axpy: one whole-array VPU op (vs a masked row
            # update per nonzero on the stream side).  Gated off on
            # padding steps — the stream loops self-gate through their
            # zero trip counts, but this is a full-array op
            @pl.when(cnt >= 0)
            def _hot_scatter():
                hd_scs[kk][...] = hd_scs[kk][...] + coef * hrow

        # cnt < 0 marks a padding step (the segment scan pads the round to
        # whole segments): its margin/scatter loops already ran 0 trips,
        # and the alpha write is gated off so the step is a true no-op
        @pl.when(cnt >= 0)
        def _write_alpha():
            st_scs[kk][pl.ds(blk, 1)] = jnp.where(
                lane3 == sub_lane + 2 * LANES, new_a, srow
            )

    @pl.when(i == h - 1)
    def _flush():
        for kk in range(k):
            wd_out[kk] = wd_scs[kk][...]
            st_out[kk] = st_scs[kk][...]
            if hot:
                hd_out[kk] = hd_scs[kk][...]


def row_lengths(sp_values: jax.Array) -> jax.Array:
    """(K, n_shard) int32 per-row nonzero-prefix lengths — 1 + the last
    slot holding a nonzero value (interior explicit zeros count; trailing
    padding does not).  Drives the kernel's group early exit; hot paths
    compute this ONCE per run (run_sdca_family attaches it to
    shard_arrays as ``sp_row_len``) — per round it would re-read the whole
    values array."""
    w = sp_values.shape[-1]
    iota = jnp.arange(1, w + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(sp_values != 0, iota, 0), axis=-1) \
        .astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing"),
)
def pallas_sparse_sdca_round(
    w: jax.Array,            # (d,) the round's primal vector (replicated)
    alpha: jax.Array,        # (K, n_shard)
    sp_indices: jax.Array,   # (K, n_shard, W) int32 padded-CSR columns
    sp_values: jax.Array,    # (K, n_shard, W) padded-CSR values
    labels: jax.Array,       # (K, n_shard)
    sq_norms: jax.Array,     # (K, n_shard)
    idxs: jax.Array,         # (K, H) int32
    lam: float,
    n: int,
    mode: str = "plus",
    sigma: float = 1.0,
    interpret: bool = False,
    loss: str = "hinge",
    smoothing: float = 1.0,
    row_len: jax.Array = None,   # (K, n_shard) int32, see row_lengths
    hot_cols: jax.Array = None,  # hybrid: (K, n_hot) int32 panel columns
    hot_panel: jax.Array = None,  # hybrid: (K, n_shard, n_hot) hot panel
):
    """One sparse SDCA round for K shards on this chip.  Returns
    (dw, alpha_inner): dw (K, d) unreduced per-shard updates (dense — Δw is
    dense in the reference too, CoCoA.scala:145); alpha_inner (K, n_shard)
    the locally-advanced alpha.  Unlike the dense kernel no margins input is
    needed: the kernel reads x·w from the VMEM-resident w.

    ``hot_panel``/``hot_cols`` select the HYBRID branch (the hot/cold
    column split, docs/DESIGN.md §3b-vi): ``sp_indices``/``sp_values``
    then hold only the cold residual (narrower W → shorter stream loops),
    and each step adds the sampled row's hot-panel slice — streamed
    through VMEM one step ahead by a per-step BlockSpec — against the
    lane-blocked [w_hot] operand and per-shard Δw_hot scratch as
    whole-array VPU ops.  Same math: columns partition, so hot + cold
    permutes the per-nonzero sums.

    When H exceeds the SMEM index-table budget the round is split into
    segments of :func:`segment_len` steps, each one ``pallas_call``; the
    concatenated (w|Δw, labels|‖x‖²|α) state carries between segments (a
    few MB of HBM traffic — the index table cannot be blocked,
    scalar-prefetch operands live whole in SMEM).  Same math regardless of
    segmentation.

    Requires n_shard % 8 == 0 (shard_dataset pads to 16).  Inside
    ``shard_map`` run with ``check_vma=False`` (as the chunked driver does).
    """
    k, n_shard, w_nnz = sp_indices.shape
    h = idxs.shape[1]
    d = w.shape[0]
    dtype = w.dtype
    check_dtype(dtype)
    if n_shard % ROW_BLOCK != 0:
        raise ValueError(
            f"n_shard must be a multiple of {ROW_BLOCK}, got {n_shard} "
            f"(shard_dataset pads to 16)"
        )
    sig_eff, qii_factor = mode_factors(mode, sigma)
    # segment sizing must use the GROUP-rounded width the SMEM tables are
    # actually padded to, or the budget overruns by up to one group
    w_round = -(-w_nnz // min(GROUP, w_nnz)) * min(GROUP, w_nnz)
    # capped at h: a small round must not pad up to a full budget-sized
    # grid of no-op steps
    h_seg = max(1, min(segment_len(k, w_round), h))

    # lane-block and lane-concatenate the state (module docstring layouts)
    n_pad = -(-n_shard // LANES) * LANES
    pad = [(0, 0), (0, n_pad - n_shard)]
    blocked = lambda v: jnp.pad(v, pad).reshape(k, n_pad // LANES, LANES)  # noqa: E731
    n_blocks = n_pad // LANES
    d_pad = -(-d // LANES) * LANES
    n_dblk = d_pad // LANES
    w_blocked = jnp.broadcast_to(
        jnp.pad(w, (0, d_pad - d)).reshape(1, n_dblk, LANES),
        (k, n_dblk, LANES),
    )
    wd = jnp.concatenate(
        [w_blocked, jnp.zeros((k, n_dblk, LANES), dtype)], axis=-1
    )
    st = jnp.concatenate(
        [blocked(labels), blocked(sq_norms), blocked(alpha)], axis=-1
    )
    idxs = idxs.astype(jnp.int32)
    if row_len is None:
        row_len = row_lengths(sp_values)
    hot = hot_panel is not None
    n_hot = int(hot_panel.shape[-1]) if hot else 0
    if hot and n_hot % LANES != 0:
        raise ValueError(f"hot panel width must be a multiple of {LANES}, "
                         f"got {n_hot} (data/hybrid.pad_panel owns this)")
    n_hblk = n_hot // LANES

    full_wd = pl.BlockSpec(
        (k, n_dblk, 2 * LANES),
        lambda i_, idxs_, gidx_, svals_, cnts_: (0, 0, 0)
    )
    full_st = pl.BlockSpec(
        (k, n_blocks, 3 * LANES),
        lambda i_, idxs_, gidx_, svals_, cnts_: (0, 0, 0)
    )

    # The round's per-step feature indices AND values, gathered into SMEM
    # prefetch tables (addresses must be scalars; Mosaic cannot read them
    # from VMEM — and an SMEM value read is O(1) in W where a VMEM
    # lane-mask pick is O(W)), plus the rows' nnz counts for the
    # dynamic-trip loop.  The round pads to whole segments (padding steps
    # carry cnt = -1 → a kernel no-op) and runs as ONE ``lax.scan`` over
    # segments with a single pallas_call in the body: with localIterFrac=1
    # the round spans ~200 segments, and the round-3 unrolled-segment form
    # built ~200 pallas call sites into the graph — minutes of
    # trace/compile before the first step ran.
    n_seg = -(-h // h_seg)
    h_pad = n_seg * h_seg
    idxs_p = jnp.pad(idxs, ((0, 0), (0, h_pad - h)))
    gidx = jnp.take_along_axis(sp_indices, idxs_p[:, :, None], axis=1)
    svals = jnp.take_along_axis(
        sp_values, idxs_p[:, :, None], axis=1).astype(dtype)
    cnts = jnp.pad(
        jnp.take_along_axis(row_len, idxs_p, axis=1)[:, :h],
        ((0, 0), (0, h_pad - h)), constant_values=-1,
    )
    # pad the slot axis to the GROUP-rounded width (computed once above):
    # the kernel's trip count rounds the row's nnz up to whole groups, and
    # the last group may read past W otherwise (zero slots are inert)
    if w_round != w_nnz:
        gidx = jnp.pad(gidx, ((0, 0), (0, 0), (0, w_round - w_nnz)))
        svals = jnp.pad(svals, ((0, 0), (0, 0), (0, w_round - w_nnz)))
    # (n_seg, K, h_seg[, W]) scan leaves
    seg_shape = lambda a: a.reshape(k, n_seg, h_seg, *a.shape[2:]) \
        .swapaxes(0, 1)  # noqa: E731
    xs = (seg_shape(idxs_p), seg_shape(gidx), seg_shape(svals),
          seg_shape(cnts))
    if hot:
        # the sampled rows' hot-panel slices, gathered per round like the
        # CSR streams and lane-blocked for the kernel's per-step BlockSpec
        hrows = jnp.take_along_axis(
            hot_panel, idxs_p[:, :, None], axis=1).astype(dtype) \
            .reshape(k, h_pad, n_hblk, LANES)
        xs = (*xs, seg_shape(hrows))
        hw = jnp.take(w, hot_cols[0]).reshape(n_hblk, LANES)
        hd = jnp.zeros((k, n_hblk, LANES), dtype)

    kernel = functools.partial(
        _kernel,
        lam_n=float(lam * n),
        coef_div=float(coef_divisor(mode, lam * n)),
        sig_eff=float(sig_eff),
        qii_factor=float(qii_factor),
        frozen=(mode == "frozen"),
        h=h_seg,
        w_nnz=w_nnz,
        loss=losses.validate(loss, smoothing),
        smoothing=float(smoothing),
        k=k,
        n_hblk=n_hblk,
    )
    in_specs = [
        full_wd,   # [w | Δw] (Δw carried between segments)
        full_st,   # [labels | ‖x‖² | α]
    ]
    out_specs = [full_wd, full_st]
    out_shape = [
        jax.ShapeDtypeStruct((k, n_dblk, 2 * LANES), dtype),
        jax.ShapeDtypeStruct((k, n_blocks, 3 * LANES), dtype),
    ]
    scratch = (
        [pltpu.VMEM((n_dblk, 2 * LANES), dtype)] * k
        + [pltpu.VMEM((n_blocks, 3 * LANES), dtype)] * k
    )
    if hot:
        full_hw = pl.BlockSpec((n_hblk, LANES), lambda i, *_: (0, 0))
        full_hd = pl.BlockSpec((k, n_hblk, LANES), lambda i, *_: (0, 0, 0))
        # ONE step's hot rows per grid iteration — the pipeline
        # double-buffers step i+1's block while step i runs
        step_hr = pl.BlockSpec((k, 1, n_hblk, LANES),
                               lambda i, *_: (0, i, 0, 0))
        in_specs += [full_hw, full_hd, step_hr]
        out_specs += [full_hd]
        out_shape += [jax.ShapeDtypeStruct((k, n_hblk, LANES), dtype)]
        scratch += [pltpu.VMEM((n_hblk, LANES), dtype)] * k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(h_seg,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="pallas_sparse_sdca_round",
    )

    if hot:
        def seg_body(carry, seg_xs):
            wd_c, st_c, hd_c = carry
            si, sg, sv, sc, hr = seg_xs
            wd_c, st_c, hd_c = call(si, sg, sv, sc, wd_c, st_c, hw, hd_c,
                                    hr)
            return (wd_c, st_c, hd_c), None

        carry0 = (wd, st, hd)
    else:
        def seg_body(carry, seg_xs):
            wd_c, st_c = carry
            si, sg, sv, sc = seg_xs
            wd_c, st_c = call(si, sg, sv, sc, wd_c, st_c)
            return (wd_c, st_c), None

        carry0 = (wd, st)

    if n_seg == 1:
        carry, _ = seg_body(carry0, jax.tree.map(lambda a: a[0], xs))
    else:
        carry, _ = jax.lax.scan(seg_body, carry0, xs)
    wd, st = carry[0], carry[1]

    dw = wd[:, :, LANES:].reshape(k, d_pad)[:, :d]
    if hot:
        # fold the hot Δw back into the full Δw at its column ids — hot
        # and cold columns are disjoint, and inert panel-padding lanes
        # carry value 0 at column 0, so the scatter-add is exact
        dw = dw.at[jnp.arange(k)[:, None], hot_cols].add(
            carry[2].reshape(k, n_hot))
    alpha_inner = st[:, :, 2 * LANES:].reshape(k, n_pad)[:, :n_shard]
    return dw, alpha_inner


# ---------------------------------------------------------------------------
# Sparse block-chain support: the (B, B) block Gram and the margin base
# computed IN-KERNEL from the SMEM-scalar-prefetched padded-CSR streams (no
# densify to (B, d)), plus the rank-B Δw apply as a sparse scatter.
# ---------------------------------------------------------------------------
#
# The dense block path (ops/local_sdca.local_sdca_block_batched) gathers each
# sampled block into a (K, B, d) dense tile before the Gram matmul; at rcv1
# scale (d≈47k, ~73 nnz/row) that is ~650x more HBM traffic than the rows'
# nonzeros, so the densified block path loses to the sequential sparse
# kernel there.  These kernels replace every
# O(B·d) dense tile with O(nnz) work over the same SMEM-scalar-prefetched
# padded-CSR layout the sequential kernel proved out:
#
# - ``sparse_block_gram``: Gram entry (i, j) = Σ_t v_j[t]·x_i[f_j[t]] is an
#   O(nnz_j) merge over SMEM index streams against a lane-blocked dense
#   expansion of row i ((d/128, 128) VMEM scratch, one masked-row scatter
#   per nonzero — O(nnz_i), amortized over the B-1 entries of Gram row i).
#   Only the strict upper triangle is computed: the chain multiplies
#   G[i, j] by the step-i coefficient, which is zero for i ≥ j.  The margin
#   base x_i·(w + σ′·Δw_blockstart) comes from the same streams against the
#   lane-concatenated [w | Δw] array (ONE dynamic slice serves both — the
#   sequential kernel's layout), so the block path needs no whole-shard
#   margins pass and no dense w.
# - ``sparse_block_apply``: Δw += Σ_j coef_j·x_j as a masked-row scatter
#   over the block's nonzeros — O(Σ_j nnz_j), not O(B·d).
#
# **SMEM segmentation.**  The scalar-prefetch tables must live whole in
# SMEM, and a (B, W) block at rcv1 scale (B=128, W≈550 GROUP-rounded) is
# ~590 KB — over the measured budget.  The Gram therefore computes in
# (S, S) row-segment tiles: a call for segment pair (s, u ≥ s) prefetches
# only the two segments' streams (2·S·W·8 bytes ≤ SMEM_IDX_BUDGET) and
# fills G[i ∈ s, j ∈ u]; scatters of segment-s rows are repeated per pair
# (O(nnz) each — noise against the merge work).  All (shard, pair) tiles
# run as ONE ``lax.scan`` over a single pallas_call site — the round-3
# many-call-sites compile blow-up does not recur.  The per-row GROUP-loop
# early exit (dynamic trip counts from prefetched per-row nnz) carries
# over unchanged, so heavy-tailed widths cost ceil(nnz/32)·32 slots, not W.


def seg_rows(b: int, w_nnz: int) -> int:
    """Rows per Gram-tile segment: the largest power-of-two divisor S of B
    (≥ 8, so output tiles stay sublane-aligned) such that a segment PAIR's
    scalar-prefetch tables — two (S, W_rounded) int32+f32 stream sets —
    fit the SMEM budget.  0 when even S=8 does not fit (the caller then
    keeps the densified path)."""
    group = min(GROUP, max(1, w_nnz))
    w_r = -(-w_nnz // group) * group
    s = b
    while s >= 8 and 16 * s * w_r > SMEM_IDX_BUDGET:
        s //= 2
    return s if s >= 8 and b % s == 0 else 0


def sparse_block_vmem(d: int, b: int, s: int, itemsize: int) -> int:
    """Working set of one Gram-tile call: the (d/128, 2·128) wd operand
    (double-buffered), the (d/128, 128) dense-row scratch, and the small
    (S, 128·⌈S/128⌉) gram / (1, ·) mb tiles."""
    d_pad = -(-d // LANES) * LANES
    lanes_out = -(-s // LANES) * LANES
    return itemsize * (5 * d_pad + 2 * s * lanes_out + 2 * lanes_out)


def sparse_chain_fits(k: int, n_shard: int, d: int, max_nnz: int, b: int,
                      itemsize: int) -> bool:
    """Feasibility of the sparse block-chain path: whole-lane-tile blocks
    (the chain kernel's contract), an SMEM-feasible segment size, the chain
    kernel's VMEM fit, and the Gram call's VMEM fit."""
    from cocoa_tpu.ops.pallas_chain import chain_fits

    s = seg_rows(b, max_nnz)
    del n_shard
    return (
        b % LANES == 0
        and s > 0
        and chain_fits(k, b, itemsize)
        and sparse_block_vmem(d, b, s, itemsize) <= VMEM_BUDGET
    )


def hybrid_fits(k: int, n_shard: int, d: int, max_nnz: int, b: int,
                n_hot: int, itemsize: int) -> bool:
    """Feasibility of the HYBRID block path (hot/cold split,
    docs/DESIGN.md §3b-vi): the cold residual runs through the exact
    CSR-stream machinery :func:`sparse_chain_fits` gates (``max_nnz`` is
    the RESIDUAL width — narrower than the unsplit streams, so the split
    only widens feasibility), and the hot panel must be lane-aligned; its
    Gram/margin/apply terms are XLA MXU einsum tiles, not VMEM-resident
    kernel state, so the panel adds no VMEM constraint here (the
    SEQUENTIAL kernel's panel accounting lives in
    :func:`sparse_kernel_fits` via ``n_hot``)."""
    return (
        n_hot > 0
        and n_hot % LANES == 0
        and sparse_chain_fits(k, n_shard, d, max_nnz, b, itemsize)
    )


def wd_stack(w: jax.Array, k: int) -> jax.Array:
    """(d,) replicated w -> the (K, d/128, 2·128) lane-blocked AND
    lane-concatenated [w | Δw=0] array the sparse kernels address (module
    docstring layout; Δw rides lanes [128, 256))."""
    d = w.shape[0]
    d_pad = -(-d // LANES) * LANES
    n_dblk = d_pad // LANES
    w_blocked = jnp.broadcast_to(
        jnp.pad(w, (0, d_pad - d)).reshape(1, n_dblk, LANES),
        (k, n_dblk, LANES),
    )
    return jnp.concatenate(
        [w_blocked, jnp.zeros((k, n_dblk, LANES), w.dtype)], axis=-1
    )


def wd_delta(wd: jax.Array, d: int) -> jax.Array:
    """Extract the accumulated (K, d) Δw from the concatenated layout."""
    k, n_dblk, _ = wd.shape
    return wd[:, :, LANES:].reshape(k, n_dblk * LANES)[:, :d]


def _gram_kernel(
    sidx_ref,    # scalar-prefetch: (S, W) int32 scatter-segment indices
    svals_ref,   # scalar-prefetch: (S, W) f32 scatter-segment values
    scnt_ref,    # scalar-prefetch: (S,) int32 scatter-row nnz (-1 = pad step)
    pidx_ref,    # scalar-prefetch: (S, W) int32 pick-segment indices
    pvals_ref,   # scalar-prefetch: (S, W) f32 pick-segment values
    pcnt_ref,    # scalar-prefetch: (S,) int32 pick-row nnz
    diag_ref,    # scalar-prefetch: (1,) int32, 1 when pick seg == scatter seg
    wd_ref,      # (n_dblk, 2·LANES) [w | Δw at block start], read-only
    gram_ref,    # out (S, lanes_out): gram_ref[j, i] = G[i, j], i < j only
    mb_ref,      # out (1, lanes_out): margin base (diagonal tiles only)
    xrow_ref,    # scratch (n_dblk, LANES): dense expansion of scatter row i
    *,
    s: int,
    w_nnz: int,
    sig_eff: float,
    frozen: bool,
    lanes_out: int,
):
    """Grid (S,) over scatter rows i.  Step i scatters row i densely into
    ``xrow`` (O(nnz_i) masked row updates), then merges every pick row j
    (j > i on diagonal tiles, all j off-diagonal) against it — each Gram
    entry an O(nnz_j) accumulate of SMEM scalar reads and (1, 128) dynamic
    slices, with the GROUP-loop trip counts skipping padding.  Diagonal
    tiles also emit the margin base from the [w | Δw] operand (one slice
    serves both coordinates — the concatenation trick)."""
    i = pl.program_id(0)
    group = min(GROUP, max(1, w_nnz))
    dtype = wd_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes_out), 1)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * LANES), 1)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros((s, lanes_out), dtype)
        mb_ref[...] = jnp.zeros((1, lanes_out), dtype)

    diag = diag_ref[0] == 1
    cnt_i = scnt_ref[i]
    trips_i = (jnp.maximum(cnt_i, 0) + (group - 1)) // group

    # dense lane-blocked expansion of scatter row i; padded slots add
    # exactly 0 at feature 0 (same inertness trick as the whole module)
    xrow_ref[...] = jnp.zeros(xrow_ref.shape, dtype)

    def scatter_body(g, c):
        base = g * group
        for u in range(group):
            f = sidx_ref[i, base + u]
            fb = f // LANES
            fls = f - fb * LANES
            v = svals_ref[i, base + u]
            row = xrow_ref[pl.ds(fb, 1)]
            xrow_ref[pl.ds(fb, 1)] = jnp.where(lane1 == fls, row + v, row)
        return c

    jax.lax.fori_loop(0, trips_i, scatter_body, jnp.int32(0))

    # margin base x_i·(w + σ′·Δw_blockstart), diagonal tiles only (each row
    # is a scatter row of exactly one diagonal tile)
    @pl.when(diag)
    def _margin():
        def m_body(g, acc):
            base = g * group
            for u in range(group):
                f = sidx_ref[i, base + u]
                fb = f // LANES
                fls = f - fb * LANES
                v = svals_ref[i, base + u]
                wrow = wd_ref[pl.ds(fb, 1)]
                coord = jnp.sum(jnp.where(lane2 == fls, wrow, 0.0))
                if not frozen:
                    coord = coord + sig_eff * jnp.sum(
                        jnp.where(lane2 == fls + LANES, wrow, 0.0)
                    )
                acc = acc + v * coord
            return acc

        m = jax.lax.fori_loop(0, trips_i, m_body, jnp.asarray(0.0, dtype))
        mb_ref[...] = jnp.where(lane == i, m, mb_ref[...])

    if frozen:
        return  # frozen margins never see Δw: no Gram coupling needed

    # Gram row i against every later pick row: G[i, j] = Σ_t v_j·xrow[f_j],
    # written at [j, i] so the chain's per-step read is ONE leading-dim
    # dynamic sublane slice (gram is assembled j-leading)
    j_start = jnp.where(diag, i + 1, 0)

    def j_body(j, c):
        cnt_j = pcnt_ref[j]
        trips_j = (jnp.maximum(cnt_j, 0) + (group - 1)) // group

        def p_body(g, acc):
            base = g * group
            for u in range(group):
                f = pidx_ref[j, base + u]
                fb = f // LANES
                fls = f - fb * LANES
                v = pvals_ref[j, base + u]
                xr = xrow_ref[pl.ds(fb, 1)]
                acc = acc + v * jnp.sum(jnp.where(lane1 == fls, xr, 0.0))
            return acc

        g_ij = jax.lax.fori_loop(0, trips_j, p_body, jnp.asarray(0.0, dtype))
        grow = gram_ref[pl.ds(j, 1)]
        gram_ref[pl.ds(j, 1)] = jnp.where(lane == i, g_ij, grow)
        return c

    jax.lax.fori_loop(j_start, s, j_body, jnp.int32(0))


def sparse_block_gram(
    wd: jax.Array,       # (K, n_dblk, 2·LANES) [w | Δw at block start]
    gidx: jax.Array,     # (K, B, W_r) int32 block CSR indices (GROUP-rounded)
    svals: jax.Array,    # (K, B, W_r) block CSR values
    cnts: jax.Array,     # (K, B) int32 per-row nnz; -1 marks padded steps
    sig_eff: float,
    frozen: bool,
    interpret: bool = False,
):
    """The block's Gram and margin base, in-kernel from the CSR streams.

    Returns ``(gram, mb)``: gram (B, K, B) j-leading with the strict upper
    triangle filled (``gram[j, k, i] = x_i·x_j`` of shard k for i < j,
    zeros elsewhere — exactly the entries the chain's coefficient dots can
    see; None in frozen mode), and mb (K, B) = x_j·(w + σ′·Δw_blockstart)
    (x_j·w for frozen).  All (shard, segment-pair) tiles run as one
    ``lax.scan`` over a single pallas_call site."""
    k, b, w_r = gidx.shape
    dtype = wd.dtype
    n_dblk = wd.shape[1]
    s = seg_rows(b, w_r)
    if s <= 0:
        raise ValueError(
            f"no SMEM-feasible Gram segment for B={b}, W={w_r} "
            f"(sparse_chain_fits should have rejected this config)"
        )
    ns = b // s
    lanes_out = -(-s // LANES) * LANES
    # (shard, scatter-seg, pick-seg) tiles; only u >= s segments (upper
    # triangle — earlier pick rows multiply zero coefficients)
    pairs = [(si, ui) for si in range(ns) for ui in range(si, ns)]
    np_ = len(pairs)
    si_t = jnp.tile(jnp.asarray([p[0] for p in pairs], jnp.int32), k)
    ui_t = jnp.tile(jnp.asarray([p[1] for p in pairs], jnp.int32), k)
    kk_t = jnp.repeat(jnp.arange(k, dtype=jnp.int32), np_)
    seg = lambda a: a.reshape(k, ns, s, *a.shape[2:])  # noqa: E731
    gi, sv, cn = seg(gidx), seg(svals), seg(cnts)
    xs = (
        gi[kk_t, si_t], sv[kk_t, si_t], cn[kk_t, si_t],
        gi[kk_t, ui_t], sv[kk_t, ui_t], cn[kk_t, ui_t],
        (si_t == ui_t).astype(jnp.int32)[:, None], kk_t,
    )

    kernel = functools.partial(
        _gram_kernel, s=s, w_nnz=w_r, sig_eff=float(sig_eff),
        frozen=frozen, lanes_out=lanes_out,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((n_dblk, 2 * LANES), lambda i, *_: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s, lanes_out), lambda i, *_: (0, 0)),
            pl.BlockSpec((1, lanes_out), lambda i, *_: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((n_dblk, LANES), dtype)],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, lanes_out), dtype),
            jax.ShapeDtypeStruct((1, lanes_out), dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="pallas_sparse_block_gram",
    )

    def body(carry, xs_p):
        si, sv_, sc, pi_, pv, pc, dg, kp = xs_p
        wd_k = jax.lax.dynamic_index_in_dim(wd, kp, axis=0, keepdims=False)
        g_tile, mb_tile = call(si, sv_, sc, pi_, pv, pc, dg, wd_k)
        return carry, (g_tile, mb_tile)

    _, (gtiles, mbtiles) = jax.lax.scan(body, jnp.int32(0), xs)
    gtiles = gtiles[..., :s].reshape(k, np_, s, s)
    mbtiles = mbtiles[:, 0, :s].reshape(k, np_, s)

    mb = jnp.zeros((k, b), dtype)
    gram = None if frozen else jnp.zeros((b, k, b), dtype)
    for p, (si, ui) in enumerate(pairs):
        if si == ui:
            mb = mb.at[:, si * s:(si + 1) * s].set(mbtiles[:, p])
        if not frozen:
            gram = gram.at[ui * s:(ui + 1) * s, :, si * s:(si + 1) * s].set(
                gtiles[:, p].transpose(1, 0, 2)
            )
    return gram, mb


def _apply_kernel(
    gidx_ref,    # scalar-prefetch: (S, W) int32 segment indices
    svals_ref,   # scalar-prefetch: (S, W) f32 segment values
    cnts_ref,    # scalar-prefetch: (S,) int32 per-row nnz (-1 = pad step)
    coefs_ref,   # scalar-prefetch: (S,) f32 chain Δw coefficients
    wd_in,       # (n_dblk, 2·LANES)
    wd_out,      # (n_dblk, 2·LANES)
    *,
    s: int,
    w_nnz: int,
):
    """Grid (S,) over the segment's rows: Δw lanes += coef_j·x_j as masked
    row updates over row j's nonzeros — the rank-B apply without the dense
    (B, d) tile.  Padded steps carry coef 0 AND cnt -1 (zero trips)."""
    j = pl.program_id(0)
    group = min(GROUP, max(1, w_nnz))
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * LANES), 1)

    @pl.when(j == 0)
    def _init():
        wd_out[...] = wd_in[...]

    cnt = cnts_ref[j]
    coef = coefs_ref[j]
    trips = (jnp.maximum(cnt, 0) + (group - 1)) // group

    def body(g, c):
        base = g * group
        for u in range(group):
            f = gidx_ref[j, base + u]
            fb = f // LANES
            fls = f - fb * LANES
            v = svals_ref[j, base + u]
            row = wd_out[pl.ds(fb, 1)]
            wd_out[pl.ds(fb, 1)] = jnp.where(
                lane2 == fls + LANES, row + coef * v, row
            )
        return c

    jax.lax.fori_loop(0, trips, body, jnp.int32(0))


def sparse_block_apply(
    wd: jax.Array,       # (K, n_dblk, 2·LANES)
    gidx: jax.Array,     # (K, B, W_r) int32
    svals: jax.Array,    # (K, B, W_r)
    cnts: jax.Array,     # (K, B) int32; -1 marks padded steps
    coefs: jax.Array,    # (K, B) chain Δw coefficients
    interpret: bool = False,
):
    """Apply the block's rank-B Δw update into the concatenated [w | Δw]
    array as a sparse scatter — one (shard, row-segment) pallas call per
    scan step, same SMEM segmentation as the Gram."""
    k, b, w_r = gidx.shape
    dtype = wd.dtype
    n_dblk = wd.shape[1]
    s = seg_rows(b, w_r)
    if s <= 0:
        raise ValueError(f"no SMEM-feasible apply segment for B={b}, W={w_r}")
    ns = b // s
    kk_t = jnp.repeat(jnp.arange(k, dtype=jnp.int32), ns)
    ss_t = jnp.tile(jnp.arange(ns, dtype=jnp.int32), k)
    seg = lambda a: a.reshape(k, ns, s, *a.shape[2:])  # noqa: E731
    xs = (
        seg(gidx)[kk_t, ss_t], seg(svals)[kk_t, ss_t],
        seg(cnts)[kk_t, ss_t], seg(coefs.astype(svals.dtype))[kk_t, ss_t],
        kk_t,
    )
    kernel = functools.partial(_apply_kernel, s=s, w_nnz=w_r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[pl.BlockSpec((n_dblk, 2 * LANES), lambda i, *_: (0, 0))],
        out_specs=[pl.BlockSpec((n_dblk, 2 * LANES), lambda i, *_: (0, 0))],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_dblk, 2 * LANES), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="pallas_sparse_block_apply",
    )

    def body(wd_c, xs_p):
        gi, sv, cn, cf, kp = xs_p
        wd_k = jax.lax.dynamic_index_in_dim(wd_c, kp, axis=0, keepdims=False)
        (wd_k2,) = call(gi, sv, cn, cf, wd_k)
        return jax.lax.dynamic_update_index_in_dim(wd_c, wd_k2, kp, 0), None

    wd, _ = jax.lax.scan(body, wd, xs)
    return wd
