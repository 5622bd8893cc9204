"""CoCoA / CoCoA+ outer driver (reference: CoCoA.scala:22-66).

One outer round = one jitted step: fan out the replicated w, run H local
SDCA coordinate steps per shard, psum the Δw, apply the scaling law —
γ for CoCoA+ (additive) or β/K for CoCoA (averaging) (CoCoA.scala:37).
The Python loop over rounds mirrors the reference's driver loop
(CoCoA.scala:39); per-``debugIter`` evaluation is gated off the hot path
exactly as the reference gates it (CoCoA.scala:51).

State lives device-side across rounds: w replicated, alpha (K, n_shard)
pinned per-shard — donated through the jitted step so XLA updates it in
place in HBM (the analogue of ``preservesPartitioning=true`` RDD reuse).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.analysis import sanitize as _sanitize
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import (CLASS_TILE, ShardedDataset, class_pad,
                                     class_tile_shape, order_rows_for_passes,
                                     passes_want_order, rows_as_built,
                                     rows_as_ordered, rows_of_one_length)
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops import local_sdca
from cocoa_tpu.ops import rows as _rows
from cocoa_tpu.solvers import base
from cocoa_tpu.telemetry import tracing as _tracing


# the module whose first import is a first job's ``resolve_path`` second
# (:func:`run_sdca_family`): the dense kernels', with Pallas behind it
_KERNELS = "cocoa_tpu.ops.pallas_sdca"


def _pallas_batched(w, alpha, idxs_kh, shards, params, mode, sigma,
                    interpret, state="vmem", hbm_plan=None):
    """One Pallas SDCA round over all K shards: dense kernel (margins
    precomputed as one MXU matvec, folded-row X) or sparse kernel (margins
    read in-kernel from the VMEM-resident w; ``state="hbm"``: the kernel
    whose w, Δw and α stay in HBM, ops/pallas_sparse_hbm.py, on the plan
    the resolver made for it, ``hbm_plan``; None: the shapes' own).  Returns
    (dw, alpha_inner (K, n_shard)): dw's rows add up to the K shards' Δw —
    (1, d), summed by the kernel, from the dense, the HBM-state and the
    stream kernels; (K, d) from the VMEM-resident sparse kernel."""
    common = dict(mode=mode, sigma=sigma, interpret=interpret,
                  loss=params.loss, smoothing=params.smoothing)
    if "sp_row_ptr" in shards:
        from cocoa_tpu.ops.pallas_longrows import pallas_longrows_round

        # rows kept as a stream: the kernels paced by nonzeros; dw arrives
        # summed, as from the HBM-state kernel below
        dw_sum, alpha_inner = pallas_longrows_round(
            w, alpha, shards["sp_indices"], shards["sp_values"],
            shards["sp_row_ptr"], shards["sp_row_len"], shards["labels"],
            shards["sq_norms"], idxs_kh, params.lam, params.n, **common,
        )
        return dw_sum[None], alpha_inner
    if "sp_indices" in shards and state == "hbm":
        from cocoa_tpu.ops.pallas_sparse_hbm import pallas_sparse_hbm_round

        # the shards' dw arrives summed (one (d,) target): as the one row
        # of a (1, d) "per-shard" result it takes the callers' shard sum
        dw_sum, alpha_inner = pallas_sparse_hbm_round(
            w, alpha, shards["sp_indices"], shards["sp_values"],
            shards["labels"], shards["sq_norms"], idxs_kh,
            params.lam, params.n, row_len=shards.get("sp_row_len"),
            plan=hbm_plan, **common,
        )
        return dw_sum[None], alpha_inner
    if "sp_indices" in shards:
        from cocoa_tpu.ops.pallas_sparse import pallas_sparse_sdca_round

        # hybrid layouts (--hotCols) pass the hot panel through: the
        # kernel then streams each step's panel slice through VMEM and
        # merges only the cold residual (docs/DESIGN.md §3b-vi)
        return pallas_sparse_sdca_round(
            w, alpha, shards["sp_indices"], shards["sp_values"],
            shards["labels"], shards["sq_norms"], idxs_kh,
            params.lam, params.n, row_len=shards.get("sp_row_len"),
            hot_cols=shards.get("hot_cols"), hot_panel=shards.get("X_hot"),
            **common,
        )
    from cocoa_tpu.ops.pallas_sdca import pallas_sdca_round

    # (a one-vs-rest job's round is :func:`_class_round`'s)
    # margins are computed in-kernel against the VMEM-resident w (round 4;
    # the sampled row is DMA'd for the axpy anyway — precomputing X·w read
    # ALL of X per round, ~10x the rows the round touches at
    # localIterFrac=0.1)
    Xf = shards.get("X_folded", shards["X"])
    return pallas_sdca_round(
        w, alpha, Xf, shards["labels"], shards["sq_norms"], idxs_kh,
        params.lam, params.n, **common,
    )


def auto_block_size(ds: ShardedDataset, m_local: int, dtype) -> int:
    """Resolve ``--blockSize=auto`` per data layout, mirroring EXACTLY the
    path local_sdca_block_batched would dispatch to.

    Candidates are walked in the ranking of
    pallas_chain.BLOCK_SIZE_PREFERENCE — the first candidate that passes
    the same fit accounting the dispatch layer uses wins, so auto picks
    the preferred tile, not just the largest that fits:

    - dense: a candidate fits when the lockstep chain kernel fits VMEM
      (chain_fits);
    - sparse: a candidate needs a WINNING block kernel — the fused kernel
      holding the (small-d) densified tile, or otherwise the in-kernel CSR
      Gram path (ops/pallas_sparse.sparse_chain_fits).  When neither fits
      any candidate, 0: a SPLIT-path densified sparse block loses to the
      sequential sparse kernel, so those configs keep the sequential
      default;
    - anything the f32 chain kernel cannot serve (2/8-byte dtypes,
      oversized VMEM at every candidate): 0, the sequential path.
    """
    from cocoa_tpu.ops.pallas_chain import (
        BLOCK_SIZE_PREFERENCE, chain_fits, fused_fits,
    )
    from cocoa_tpu.ops.pallas_sparse import hybrid_fits, sparse_chain_fits

    itemsize = jnp.dtype(dtype).itemsize
    if itemsize != 4 or ds.sp_row_ptr is not None:
        return 0                # (rows kept as a stream: sequential only)
    for b in BLOCK_SIZE_PREFERENCE:
        if not chain_fits(m_local, b, itemsize):
            continue
        if ds.layout == "sparse":
            # same precedence as the block dispatch: the fused kernel
            # first (densify is cheap when the half-tile fits), the CSR
            # Gram path when it cannot (the rcv1 regime); hybrid layouts
            # gate on the RESIDUAL streams + panel alignment
            # (hybrid_fits), which the narrower residual only loosens
            width = int(ds.sp_indices.shape[-1])
            stream_ok = (
                hybrid_fits(m_local, ds.n_shard, ds.num_features, width,
                            b, ds.n_hot, itemsize)
                if ds.n_hot else
                sparse_chain_fits(m_local, ds.n_shard, ds.num_features,
                                  width, b, itemsize)
            )
            if not (
                fused_fits(m_local, b, ds.num_features, itemsize,
                           ds.n_shard)
                or stream_ok
            ):
                continue
        return b
    return 0


@dataclasses.dataclass(frozen=True)
class SolverPath:
    """What the local solver of one run resolved to — decided once by
    :func:`resolve_solver_path`, consumed by :func:`run_sdca_family`, and
    reported on every surface a run has (the console line, the
    ``run_start`` manifest, ``Trajectory.meta``) so a run that stayed on
    the XLA fallback can never read as one that used a Pallas kernel.

    ``inner``: ``sequential`` | ``block``.  ``kernel``: the sequential
    path's ``pallas`` | ``fori``; the block path's form ``fused`` |
    ``split`` | ``sparse_gram`` | ``hybrid`` (the batched Pallas round,
    ops/local_sdca.resolve_block_form) or ``xla`` (the portable per-shard
    block kernel).  ``chain`` (block only): ``pallas`` | ``xla``.
    ``interpret``: the Pallas kernels run in interpret mode (CPU tests),
    not compiled by Mosaic.  ``devices`` is OBSERVED from the dataset's
    placement, not taken from the mesh that was asked for.  ``rows``: how
    the dense kernel's folded rows are stored — ``row_major``: lane-padded
    so that the device keeps them as the device loop reads them (no
    relayout per dispatch); ``device_default``: everything else (on a TPU
    that can be the row index on the lanes, which every dispatch relays
    first — ``row_align``, ops/pallas_sdca.lane_aligned).  ``state``: where
    w, Δw and α live while the local solve runs — ``vmem``: resident on the chip
    for the round (the Pallas kernels of ops/pallas_sdca.py and
    ops/pallas_sparse.py); ``hbm``: in HBM, only a segment's touched part
    of them on the chip (ops/pallas_sparse_hbm.py, the sparse kernel for
    sets whose d or n_shard outgrow VMEM; and every ``fori`` path).
    ``step_solve``: where a coordinate step's values are while its new α
    is solved — ``lanes``: the dense Pallas kernel under a loss whose step
    iterates (ops/losses.step_is_iterative: logistic's Newton), the K
    shards it advances in lockstep solved as one vector, a shard a lane
    (and the class kernel, a class a sublane); ``vector``: the dense
    Pallas kernel under a closed-form step (hinge, smoothed hinge, the
    lasso's soft threshold), solved chain by chain on (1, 1) vectors — y,
    ‖x‖², α and the margin come from reduces that keep their axes, so no
    value of a step crosses to the scalar core (ops/pallas_sdca._advance;
    ``lanes`` runs the same read and write), and the sparse rectangle's
    HBM-state kernel under ANY loss (ops/pallas_sparse_hbm._chain_kernel
    runs one chain at a time, so logistic's Newton iterations too run on
    that chain's (1, 1) values; y, σ′‖x‖² and α enter as splats of its
    SMEM table, the margin's total and a repeated row's α as reduces that
    keep their axes), and since PR 49 a stream's kernels under any loss
    (ops/pallas_longrows._kernel, one chain at a time too: the chain's y,
    σ′‖x‖², α and, ``split``, the table's x·w are splats of SMEM loads,
    its margin's total and a repeated row's α reduces that keep their
    axes, ``alpha_step`` elementwise on (1, 1), coef a (1, 1) vector into
    the update's multiply-add; the all-rows passes alike: a ``dots``
    row's total goes to its lane of the output without leaving the
    vector side, an ``axpy`` row's coefficient is one splat);
    ``scalar``: everything else — wherever a kernel still solves a step
    on one coordinate's 0-d values (``fori``, the VMEM-resident sparse
    kernel and the block kernels).
    ``pass_slot_share``: of a sparse set's padded slots, the share one
    all-rows pass (the certificate's margins, the ``--accel`` jump) touches:
    1.0 where one block holds a shard or the rows' lengths are not known;
    less where the pass runs in row blocks and stops at each block's longest
    row (ops/rows.pass_slots, counted from the lengths: about a half for
    rows in length order, data/sharding.order_rows_by_length).
    ``storage`` (sparse sets): ``rectangle``: rows padded to the longest,
    (K, n_shard, W); ``stream``: rows of a hundred nonzeros or of
    thousands, the longest a few times the mean, kept end to end
    (data/sharding.stream_suits), solved by the kernels of
    ops/pallas_longrows.py, which hold ONE d-vector in VMEM at a time
    (``state`` reads ``vmem``): w during the passes over rows, and during a
    shard's chain what ``margin`` says.  ``chunk_pieces`` (the stream
    only; None anywhere else): the 128-slot pieces one DMA of those
    kernels' ring brings to SMEM (their constant CHUNK_PIECES);
    ``chunk_fill``: of the slots the ring moves in one pass over every row,
    the share that hold a nonzero (pallas_longrows.chunk_fill, counted on
    the host from the rows' starts and lengths; None where they are not
    known): webspam's rows 0.87, url's 0.11.  ``margin`` (the stream's Pallas
    kernels, once :meth:`for_mode` knows the algorithm; None anywhere
    else): ``combined``: the chain holds v = w + sigma' dw_k and a step's
    margin is one dot against it; ``split``: it holds dw_k, and x . w of
    the round's rows is a pass of its own before it (mini-batch CD, whose
    margin never reads dw_k — ops/pallas_longrows.margin_form).
    ``slot_fill``: nonzeros / stored slots, counted on
    the host from the row lengths (None where they are not known);
    ``longest_row``: the longest row's nonzeros (the rectangle's width W
    where the lengths are not known).  ``refused``: why a sparse set that
    no Pallas kernel takes runs ``fori``, with the numbers.
    ``objective``: ``svm`` (the dual family: hinge, smoothed hinge,
    logistic) | ``lasso`` | ``elastic_net`` (the prox family,
    solvers/prox_cocoa.py, whose shards are A's columns and whose shared
    vector is the residual), filled in by :meth:`for_mode` from the
    algorithm and the loss the run was handed; it chooses no kernel.
    ``form`` (the dense Pallas kernel; None anywhere else): which of its
    two kernels runs, chosen from the fit alone
    (ops/pallas_sdca.dense_form) — ``interleaved``: every shard's state in
    VMEM at once, the K chains advanced in lockstep; ``shard_major``: a
    shard at a time.  ``classes``: T, the class models the job trains
    one-vs-rest over the one set of rows (``ShardedDataset.num_classes``;
    1 for every binary set: w (d,), alpha (K, n_shard), today's program).
    At T > 1 w is (T, d), alpha (T, K, n_shard), every class takes the
    job's one table of sampled rows, and on the dense Pallas path
    (ops/pallas_sdca.pallas_sdca_round_classes, ``form`` ``interleaved``)
    a chain's T coordinate steps are solved side by side (``step_solve``
    ``lanes``) as one column of a state tile.  ``lane_fill`` (that path
    only): of the vector positions a lockstep step's solve holds — K
    chains x the tile's sublane rows (ops/pallas_sdca.class_rows) — the
    share that are class models, K T / (K R).  ``row_fetch`` (the dense
    Pallas kernel; None anywhere else): how a sampled row reaches VMEM —
    ``ring``: by the kernel's own DMA ring, ``ring_depth`` lockstep steps
    of K rows deep, the depth read from the VMEM fit
    (ops/pallas_sdca._ring_steps; the interleaved and the class kernel);
    ``pipelined``: as a BlockSpec operand of Pallas's grid pipeline, one
    step ahead (the shard-major kernel; ``ring_depth`` None).
    ``local_ids``, ``segments``, ``table_width`` (the HBM-state sparse
    kernel only, ops/pallas_sparse_hbm.HbmPlan; None anywhere else): which
    plan a shard's round runs — ``direct``: the local id IS the column, [w
    | Δw] whole in VMEM for the round, no sorts and no per-column gathers
    (M = d fits the budget: d = 10⁶); ``sorted``: a segment's distinct
    columns ranked by two sorts, w and Δw gathered and scattered once a
    column (kddb); ``segments`` the pieces a shard's round is cut into
    (``plan.t``), ``table_width`` the slots a step takes in the chain's
    SMEM tables (``plan.w_r``: whole 8-slot groups, the rectangle's own: 40
    for rows of 39).  ``slot_walk``, ``slots_walked`` (the same kernel):
    how a step's two passes cover them — ``unrolled``: all ``table_width``
    slots written out, no dynamic trip (the loader saw rows of one length,
    data/sharding.rows_of_one_length: criteo walks 40.0 slots for 39
    nonzeros); ``grouped``: the first 32 written out whatever the row's
    length, and past them the row's whole 32-slot groups, then its 8-slot
    groups, as far as its length reaches — ``slots_walked`` the mean over
    the real rows, counted on the host from their lengths (None where they
    are not known; kddb's 37.7 for 29.4 nonzeros, 42.9 in whole 32-slot
    groups).  ``class_axis`` (None at T = 1): which axis of the kernels'
    tiles carries the T class models — ``sublanes``: dense rows, w (T, d)
    and alpha (T, K, n_shard), a class a sublane of the dense class
    kernel's state tile (T + 2 <= 16); ``lanes``: sparse rows that carry
    label SETS (``label_slots`` ids a row; 1: one class id a row), W (d, R,
    128) and alpha (K, n_shard, R, 128) with the T models on the lanes of
    ``class_tiles`` = T_pad / 1,024 whole (8, 128) tiles a column, the
    HBM-state chain fetching a step's W and dw rows itself
    (ops/pallas_sparse_lanes.py: ``local_ids`` ``direct``, one call a
    shard's round, ``ids_per_segment`` the [w | dw] rows VMEM holds at a
    time, (2 T_pad 4) B each; ``lane_fill`` T / T_pad; ``slots_walked``
    in the rectangle's 8-slot groups); on rows kept as a stream
    (``storage`` ``stream``) the same state, the chain of
    ops/pallas_longrows_lanes.py walking a sampled row's slots out of the
    stream by the DMA ring across rows and moving a row of W and of dw_k a
    nonzero through a VMEM ring — ``step_solve`` ``lanes``, ``margin``
    ``split`` under every mode (W and dw_k are two arrays), ``plan`` its
    StreamLanesPlan (``ring`` slots, ``row_block`` steps an SMEM block,
    ``steps`` a shard's round padded to whole blocks), ``pass_slot_share``
    the share of the stored slots the certificate's whole reads touch
    (pallas_longrows_lanes.pass_slot_share).  The rule is the layout's:
    dense rows take the sublanes, sparse rows the lanes.  ``class_state``
    (the dense class kernel; None anywhere else): the form alpha has
    between the rounds of a chunk — ``tiles``: the kernel's own state
    tiles (K, n_blocks, R, 128), packed from the loop's alpha (T, K,
    n_shard) once a chunk and taken back out for the eval behind it, a
    round handing the kernel the bytes it wrote the round before
    (:func:`_class_round`; ``fori`` and the lanes path carry alpha as the
    loop's state holds it).
    ``row_align`` (None where there is no fold cache: ``fori`` and every
    sparse path): how the fold cache comes to be the row-major rows of
    whole lane tiles those kernels read — ``stored``: it is kept so
    (``rows`` ``row_major``), and a dispatch does nothing; ``kernel``: a
    dispatch opens with one Pallas kernel that reads the cache in the
    order the device stores it and writes the aligned rows
    (ops/pallas_sdca.lane_aligned, the ``cocoa_row_align`` scope).
    ``plan`` (None but where said): the resolved kernel's own record of
    what a round runs, in place of more flat fields here.  A one-vs-rest
    job over DENSE rows whose T models outgrow the sublane kernel's state
    tiles (ops/pallas_sdca.classes_fit says no: T = 1,000 at any size)
    keeps them on the LANES too — W (d, R, 128), alpha (K, n_shard, R,
    128), as on sparse rows — and solves a BLOCK of rows a step
    (ops/block_lanes.py; ``inner`` ``block``, ``kernel`` ``products``: the
    block's margins, ONE Gram matrix for all T classes and the update are
    matrix products, XLA's; ``chain`` is what replays the block's steps in
    order on lane vectors, ``pallas`` on a TPU, ``xla`` anywhere else;
    ``step_solve`` ``lanes``, ``lane_fill`` T / T_pad): its ``plan`` is
    ops/block_lanes.BlockLanesPlan — ``block`` = B rows a step and
    ``blocks`` a shard's round, from the shapes and the replay kernel's
    fit, ``sub`` = b steps a sub-block of the two-level replay (a step's
    margin sums its own sub-block's earlier steps in the chain; what the
    earlier sub-blocks owe is a matrix product), and the precision of each
    of the four products (``margins``, ``gram``, ``cross``, ``update``) by
    name."""
    inner: str
    kernel: str
    chain: Optional[str]
    interpret: bool
    layout: str             # dense | sparse | hybrid (sparse + hot panel)
    platform: str
    devices: int
    shards_per_device: int
    rows: str = "device_default"
    state: str = "hbm"
    step_solve: str = "scalar"
    pass_slot_share: float = 1.0
    storage: str = "rectangle"
    margin: Optional[str] = None
    slot_fill: Optional[float] = None
    longest_row: int = 0
    chunk_pieces: Optional[int] = None
    chunk_fill: Optional[float] = None
    refused: str = ""
    objective: str = "svm"
    form: Optional[str] = None
    classes: int = 1
    lane_fill: Optional[float] = None
    row_fetch: Optional[str] = None
    ring_depth: Optional[int] = None
    row_align: Optional[str] = None
    local_ids: Optional[str] = None
    segments: Optional[int] = None
    table_width: Optional[int] = None
    slots_walked: Optional[float] = None
    slot_walk: Optional[str] = None
    class_axis: Optional[str] = None
    class_state: Optional[str] = None
    class_tiles: Optional[int] = None
    label_slots: Optional[int] = None
    ids_per_segment: Optional[int] = None
    plan: Optional[object] = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def for_mode(self, mode: str, smoothing: float = 0.0) -> "SolverPath":
        """This path as the algorithm of ``mode`` (ops/local_sdca.MODES)
        runs it: ``objective`` from the mode (and, in the prox family,
        from whether the l2 weight ``smoothing`` is there), ``margin``
        filled in where the stream's kernels run."""
        path = self
        if mode == "prox":
            path = dataclasses.replace(
                path, objective="elastic_net" if smoothing > 0 else "lasso")
        if not (path.pallas and path.storage == "stream"):
            return path
        if path.class_axis == "lanes":
            # W and dw_k are two arrays in HBM under every mode
            return dataclasses.replace(path, margin="split")
        from cocoa_tpu.ops.pallas_longrows import margin_form

        return dataclasses.replace(path, margin=margin_form(mode))

    @property
    def pallas(self) -> bool:
        """The sequential Pallas kernel runs the inner loop."""
        return self.inner == "sequential" and self.kernel == "pallas"

    @property
    def block_chain(self) -> str:
        """The ``block_chain`` value the round builders take."""
        if self.chain == "pallas":
            return "pallas_interpret" if self.interpret else "pallas"
        return "xla"

    def _walk(self) -> str:
        """The clause of :meth:`describe` on the HBM-state chain's walk."""
        from cocoa_tpu.ops.pallas_sparse_hbm import (GROUP, TAIL_GROUP,
                                                     walk_head)

        if self.class_axis == "lanes":
            return (f", a step's W and dw rows fetched by the chain, "
                    f"{self.ids_per_segment} ids in VMEM at a time"
                    + ("" if self.slots_walked is None else
                       f", {self.slots_walked:.1f} slots a step in groups "
                       f"of {_rows.SLOT_GROUP}"))
        how = (", unrolled" if self.slot_walk == "unrolled" else
               f", the first {walk_head(self.table_width, False)} written "
               f"out, then in groups of {GROUP} and {TAIL_GROUP}")
        if self.slots_walked is None:       # the lengths are not known
            return f", a row's slots walked{how}"
        return (f", walks {self.slots_walked:.1f} of {self.table_width} "
                f"slots a step{how}")

    def describe(self) -> str:
        how = "interpreted" if self.interpret else "compiled"
        if self.kernel == "products":
            what = (f"block of {self.plan.block} rows a step "
                    f"({self.plan.blocks} a round) in sub-blocks of "
                    f"{self.plan.sub}, margins ({self.plan.margins}), Gram "
                    f"({self.plan.gram}), what a sub-block is owed "
                    f"({self.plan.cross}) and update ({self.plan.update}) "
                    f"as matrix products, {self.chain} replay"
                    + (f" ({how})" if self.chain == "pallas" else ""))
        elif self.chain == "xla":
            what = "block, xla chain"
        elif self.inner == "block":
            what = f"block {self.kernel}, pallas chain ({how})"
        else:
            what = (f"sequential {self.kernel}"
                    + (f" ({how})" if self.kernel == "pallas" else "")
                    + (", state in HBM" if self.pallas
                       and self.state == "hbm" else "")
                    + (f" ({self.local_ids} local ids, {self.segments} "
                       f"segment(s) a shard, tables {self.table_width} "
                       f"slots wide{self._walk()})" if self.local_ids
                       else "")
                    + (f" [{self.refused}]" if self.refused else ""))
        rows = (", rows stored row-major" if self.rows == "row_major"
                else ", rows relaid by a kernel once a dispatch"
                if self.row_align == "kernel" else "")
        if self.form:
            what += f" {self.form}"
        if self.row_fetch == "ring":
            what += f", rows by a ring {self.ring_depth} steps deep"
        if self.objective != "svm":
            rows += f", objective {self.objective}"
        solve = (", the shards' steps solved in lanes"
                 if self.step_solve == "lanes" and self.classes == 1
                 else ", each step solved on the vector unit"
                 if self.step_solve == "vector" else "")
        if self.classes > 1:
            solve += (f", {self.classes} class models one-vs-rest over the "
                      f"one sampled row"
                      + ("" if self.lane_fill is None else
                         f" (solved side by side, lane fill "
                         f"{self.lane_fill:.3f})")
                      + (f", the class axis on the {self.class_axis}"
                         + (f" ({self.class_tiles} tile(s) of 1,024, "
                            f"{self.label_slots} label id(s) a row)"
                            if self.class_axis == "lanes" else "")
                         + (f", alpha kept as {self.class_state} across a "
                            f"chunk's rounds" if self.class_state else "")))
        if self.pass_slot_share < 1.0:
            solve += (f", all-rows passes touch {self.pass_slot_share:.3f} "
                      f"of the padded slots")
        if self.storage == "stream":
            fill = ("" if self.slot_fill is None
                    else f" (slot fill {self.slot_fill:.3f})")
            solve += (f", rows kept as a stream{fill}, the longest "
                      f"{self.longest_row} nonzeros, fetched "
                      f"{self.chunk_pieces} pieces a chunk"
                      + ("" if self.chunk_fill is None
                         else f" (chunk fill {self.chunk_fill:.3f})"))
            if self.margin:
                solve += f", margin {self.margin}"
            if self.class_axis == "lanes" and self.plan is not None:
                solve += (f", a nonzero's W and dw rows fetched by the "
                          f"chain through a ring of {self.plan.ring}")
        return (f"{what}, {self.layout} layout{rows}{solve}, on "
                f"{self.platform} x "
                f"{self.devices} ({self.shards_per_device} shard(s) per "
                f"device)")


def _pass_slot_share(ds: ShardedDataset, together: int) -> float:
    """:attr:`SolverPath.pass_slot_share` of a sparse dataset, counted once
    from the row lengths it carries (``_row_len_cache``: attached by the
    ordering or by an earlier run) and kept on it beside them."""
    if (ds.sp_row_ptr is not None and getattr(ds, "num_classes", 1) > 1
            and isinstance(ds.sp_row_ptr, jax.Array)):
        # a stream with a class axis: the certificate's pass reads each
        # block of rows' run of the stream in whole reads
        cached = getattr(ds, "_pass_slot_share_cache", None)
        if cached is None:
            from cocoa_tpu.ops.pallas_longrows_lanes import pass_slot_share

            cached = ds._pass_slot_share_cache = (0, pass_slot_share(
                ds.sp_row_ptr, ds.sp_row_len, int(ds.sp_indices.shape[1])))
        return cached[1]
    if ds.sp_row_ptr is not None or getattr(ds, "row_order", None) is None:
        return 1.0              # (rows as built: the passes see no lengths)
    width = int(ds.sp_indices.shape[-1])
    row_len = getattr(ds, "_row_len_cache", None)
    if (not isinstance(row_len, jax.Array)      # none, or a shape alone
            or _rows.row_block(ds.n_shard, width) >= ds.n_shard):
        return 1.0
    cached = getattr(ds, "_pass_slot_share_cache", None)
    if cached is None or cached[0] != together:
        cached = (together,
                  _rows.pass_slots(np.asarray(row_len), width, together)
                  / (ds.k * ds.n_shard * width))
        ds._pass_slot_share_cache = cached
    return cached[1]


def _slot_stats(ds: ShardedDataset) -> tuple:
    """``(slot_fill, longest_row, chunk_fill, slots_walked)`` of a sparse
    dataset (:class:`SolverPath`; ``chunk_fill`` None off the stream,
    ``slots_walked`` — the mean slots a pass of the HBM-state chain walks
    for a real row, ops/pallas_sparse_hbm.walk_slots — None on it),
    counted once on the host from the row lengths it carries and kept on
    it."""
    stream = ds.sp_row_ptr is not None
    row_len = ds.sp_row_len if stream else getattr(ds, "_row_len_cache",
                                                   None)
    widest = int(ds.sp_row_iota.shape[-1] if stream
                 else ds.sp_indices.shape[-1])
    if not isinstance(row_len, jax.Array):      # none, or a shape alone:
        # what the loader saw (data/sharding.note_row_lengths), else W
        return None, int(getattr(ds, "_longest_row", widest)), None, None
    cached = getattr(ds, "_slot_stats_cache", None)
    if cached is None:
        lens = np.asarray(row_len, np.int64)
        fill = walked = None
        if stream:
            from cocoa_tpu.ops.pallas_longrows import chunk_fill

            fill = chunk_fill(ds.sp_row_ptr, lens)
        else:
            from cocoa_tpu.ops.pallas_sparse_hbm import walk_slots

            # (the rows that pad a shard have no nonzero and no step; with
            # a class axis on the lanes a step fetches whole slot groups)
            real = lens[lens > 0]
            walked = float(
                (-(-real // _rows.SLOT_GROUP) * _rows.SLOT_GROUP
                 if ds.num_classes > 1 else
                 walk_slots(real, widest, rows_of_one_length(ds))).sum()
                / max(1, ds.n))
        cached = (float(lens.sum() / max(1, ds.sp_indices.size)),
                  int(lens.max(initial=0)), fill, walked)
        ds._slot_stats_cache = cached
    return cached


def _hbm_plan(ds: ShardedDataset, local_iters: int):
    """The plan of the HBM-state sparse kernel for a round of
    ``local_iters`` steps on ``ds``: from the shapes, and at T = 1 from
    whether the loader saw rows of one length
    (ops/pallas_sparse_hbm.hbm_plan); with a class axis, the lane chain's
    (ops/pallas_sparse_lanes.lanes_plan)."""
    width = int(ds.sp_indices.shape[-1])
    itemsize = jnp.dtype(ds.labels.dtype).itemsize
    if getattr(ds, "num_classes", 1) > 1 and ds.sp_row_ptr is not None:
        from cocoa_tpu.ops.pallas_longrows_lanes import stream_lanes_plan

        return stream_lanes_plan(local_iters, itemsize,
                                 class_pad(ds.num_classes),
                                 getattr(ds, "label_slots", None) or 1)
    if getattr(ds, "num_classes", 1) > 1:
        from cocoa_tpu.ops.pallas_sparse_lanes import lanes_plan

        return lanes_plan(width, local_iters, itemsize,
                          class_pad(ds.num_classes),
                          getattr(ds, "label_slots", None) or 1)
    from cocoa_tpu.ops.pallas_sparse_hbm import hbm_plan

    return hbm_plan(ds.num_features, width, local_iters, itemsize,
                    one_length=rows_of_one_length(ds))


def class_state_on_lanes(ds: ShardedDataset, mesh=None, *,
                         math: str = "exact", pallas=None,
                         block_size: int = 0) -> bool:
    """Whether a job on ``ds`` keeps its T class models on the LANES: W
    (d, R, 128), alpha (K, n_shard, R, 128) (data/sharding.class_tile_shape).
    Read off what the dataset is, never off a flag: sparse rows always — a
    padded-CSR rectangle (ops/pallas_sparse_lanes.py) and rows kept as a
    stream (ops/pallas_longrows_lanes.py) alike; dense rows where the
    sublane kernel's state tiles do not fit (ops/pallas_sdca.classes_fit
    says no) and the block solve can take them (ops/block_lanes.py: fast
    math, float32, one
    class id a row, one chip, no kernel forced by the caller).  Everything
    else — T = 1, and every dense set the sublane kernel holds — keeps w
    (T, d), alpha (T, K, n_shard).  :func:`_start_state` shapes the leaves
    by it and :func:`resolve_solver_path` the path."""
    classes = int(getattr(ds, "num_classes", 1))
    if classes <= 1:
        return False
    if ds.layout != "dense":
        return True
    if (math != "fast" or pallas or block_size > 0 or mesh is not None
            or jnp.dtype(ds.labels.dtype).itemsize != 4
            or (getattr(ds, "label_slots", None) or 1) > 1):
        return False
    from cocoa_tpu.ops.pallas_sdca import classes_fit

    return not classes_fit(ds.k, ds.n_shard, ds.num_features, classes, 4)


def resolve_solver_path(ds: ShardedDataset, local_iters: int, mesh=None, *,
                        math: str = "exact", pallas=None,
                        block_size: int = 0, block_chain=None,
                        block_sparse_gram=None,
                        loss: str = "hinge") -> SolverPath:
    """Pick the inner solver for a run — the ONE place that decides (see
    :class:`SolverPath`).  ``pallas`` / ``block_chain`` /
    ``block_sparse_gram`` None = auto; explicit values override (tests
    use ``block_chain="pallas_interpret"`` and ``pallas=True`` on CPU to
    exercise the driver-integrated kernels in interpret mode).  ``loss``
    chooses no kernel: it is what ``step_solve`` reports on."""
    from cocoa_tpu.ops.local_sdca import resolve_block_form
    from cocoa_tpu.parallel.fanout import shards_per_device
    from cocoa_tpu.parallel.mesh import has_fp

    k = ds.k
    dtype = ds.labels.dtype
    itemsize = jnp.dtype(dtype).itemsize
    classes = int(getattr(ds, "num_classes", 1))
    if classes > 1:
        # what carries the class axis today, said once where the path is
        # decided: dense rows (a class a sublane of the sequential solve
        # where the state tiles fit VMEM, else the classes on the lanes of
        # the block solve) or sparse rows, a padded-CSR rectangle or a
        # stream (the classes on the lanes of the sequential solve), one
        # chip
        if ds.layout != "dense" and ds.n_hot:
            raise ValueError(
                f"a set of {classes} classes trains one-vs-rest on dense "
                f"rows, on a padded-CSR rectangle or on rows kept as a "
                f"stream: no kernel carries the class axis on the hybrid "
                f"layout (--hotCols) yet (drop --hotCols, or drop --classes "
                f"to train class 1 against the rest)")
        label_slots = getattr(ds, "label_slots", None) or 1
        if ds.layout == "dense" and label_slots > 1:
            raise ValueError(
                f"label sets ({label_slots} ids a row) train on sparse "
                f"rows, the class axis on the lanes: the dense class "
                f"kernels, the sublanes' and the block solve, read one "
                f"class id a row (load it with --layout=sparse)")
        if mesh is not None:
            raise ValueError(
                f"a set of {classes} classes trains one-vs-rest on one chip "
                f"(--mesh=1): the class axis is not carried across a mesh")
        if block_size > 0:
            raise ValueError(
                "block_size picks the tile of the T = 1 block-coordinate "
                "kernels, which carry no class axis; a multi-class set "
                "whose models ride the lanes of dense rows runs the block "
                "solve on a block derived from its shapes "
                "(ops/block_lanes.block_lanes_plan): block_size=0 with a "
                "multi-class set")
    # logical shards resident per device: k on the single-chip path, K/D on
    # a (possibly multiplexed) dp mesh — the unit the VMEM fit checks see
    m_local = shards_per_device(mesh, k) if mesh is not None else k
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.devices()[0].platform)
    sparse = ds.layout == "sparse"
    layout = "hybrid" if sparse and ds.n_hot else ds.layout
    stream = sparse and ds.sp_row_ptr is not None
    width = int(ds.sp_indices.shape[-1]) if sparse else 0
    vmem_fits = hbm_state = False
    refused = ""
    if stream:
        # rows kept as a stream: one d-vector at a time in VMEM
        from cocoa_tpu.ops.pallas_longrows import (VEC_VMEM_BUDGET,
                                                   longrows_fits)

        if block_size > 0:
            raise ValueError("the block-coordinate kernels read padded-CSR "
                             "rectangles; rows kept as a stream "
                             "(data/sharding.stream_suits) run the "
                             "sequential solve: block_size=0")
        if classes > 1:
            # (T models ride the lanes of the stream's own HBM-state chain,
            # ops/pallas_longrows_lanes.py: no d-vector is held in VMEM)
            from cocoa_tpu.ops.pallas_longrows_lanes import stream_lanes_fits

            hbm_state = stream_lanes_fits(local_iters, itemsize,
                                          class_pad(classes), label_slots)
            if not hbm_state:
                refused = (f"a ring of [w | dw] rows of {classes} classes "
                           f"outgrows the stream chain's VMEM")
        else:
            vmem_fits = longrows_fits(ds.num_features, itemsize)
            if not vmem_fits:
                refused = (f"rows kept as a stream need one float32 "
                           f"d-vector in VMEM: d = {ds.num_features} x "
                           f"{itemsize} B against {VEC_VMEM_BUDGET} B")
    elif sparse:
        # which sequential sparse kernel could hold the set: the
        # VMEM-resident one (the SMEM feature-index table and the
        # lane-blocked d-vectors must fit — pallas_sparse docstring; hybrid
        # layouts additionally account the hot panel's VMEM), or, past
        # VMEM, the one whose state stays in HBM (no hot panel: the hybrid
        # layouts keep the fori path there)
        from cocoa_tpu.ops.pallas_sparse import sparse_kernel_fits
        from cocoa_tpu.ops.pallas_sparse_hbm import sparse_hbm_fits

        vmem_fits = classes == 1 and sparse_kernel_fits(
            m_local, ds.n_shard, ds.num_features, width, local_iters,
            itemsize, n_hot=ds.n_hot)
        # (a class axis rides the lanes of the HBM-state chain alone: W
        # (d, T_pad) is not a VMEM-resident shape)
        if classes > 1:
            from cocoa_tpu.ops.pallas_sparse_lanes import lanes_fits

            hbm_state = lanes_fits(width, local_iters, itemsize,
                                   class_pad(classes), label_slots)
        else:
            hbm_state = (not vmem_fits and not ds.n_hot and sparse_hbm_fits(
                ds.num_features, width, local_iters, itemsize))
        if classes > 1 and not hbm_state:
            refused = (f"a step's {width} [w | dw] rows of {classes} "
                       f"classes outgrow the chain's VMEM")
        elif not vmem_fits and not hbm_state and not ds.n_hot:
            from cocoa_tpu.ops.pallas_sparse_hbm import hbm_refusal

            refused = hbm_refusal(ds.num_features, width, local_iters,
                                  itemsize)
    if class_state_on_lanes(ds, mesh, math=math, pallas=pallas,
                            block_size=block_size) and not sparse:
        # T models over dense rows that the sublane kernel does not hold:
        # a block of rows a step, the class axis on the lanes
        # (ops/block_lanes.py).  Its replay is the Pallas kernel on a TPU
        # and XLA's loop anywhere else; ``block_chain`` overrides (tests)
        from cocoa_tpu.ops.block_lanes import block_fits, block_lanes_plan

        t_pad = class_pad(classes)
        plan = block_lanes_plan(local_iters, t_pad, itemsize)
        if block_chain is None:
            # (the kernel where even its smallest block fits: T_pad past
            # ~190,000 lanes outgrows eight rows of it)
            block_chain = ("pallas" if platform == "tpu" and block_fits(
                plan.block, t_pad, itemsize) else "xla")
        elif block_chain not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(f"block_chain must be xla|pallas|"
                             f"pallas_interpret, got {block_chain!r}")
        return SolverPath(
            inner="block", kernel="products",
            chain="xla" if block_chain == "xla" else "pallas",
            interpret=block_chain == "pallas_interpret",
            layout=layout, platform=platform,
            devices=len(ds.labels.sharding.device_set),
            shards_per_device=m_local, step_solve="lanes", classes=classes,
            class_axis="lanes", class_tiles=t_pad // CLASS_TILE,
            label_slots=1, lane_fill=classes / t_pad, plan=plan)
    if block_size > 0:
        # the block-coordinate kernel is an alternative inner loop — it and
        # the Pallas sequential kernels are mutually exclusive by design
        if pallas:
            raise ValueError("block-coordinate mode replaces the Pallas "
                             "kernel; pass pallas=False with block > 0")
        pallas = False
    if pallas is None:
        # auto: the Pallas kernels need fast math + f32 + a real TPU
        # backend (measured vs the fori_loop path: ~4x faster rounds at
        # epsilon scale dense — folded rows run the O(d) work at full VPU
        # width; ~25x faster steps at rcv1 scale sparse — lane-blocked
        # w/Δw make a nonzero's access O(128) and margins never leave
        # VMEM) — AND the kernel's VMEM-resident
        # working set must fit (pallas_sdca.vmem_estimate/pick_unroll own
        # that accounting — pick_unroll also chooses how many row DMAs to
        # batch per grid step).  Oversized runs keep the fori_loop fast path
        # (explicit pallas=True overrides, and Mosaic then reports the
        # allocation failure itself).
        from cocoa_tpu.ops.pallas_sdca import classes_fit, pick_unroll

        if classes > 1 and not sparse:
            fits = classes_fit(m_local, ds.n_shard, ds.num_features,
                               classes, itemsize)
        else:
            fits = (vmem_fits or hbm_state) if sparse else pick_unroll(
                ds.n_shard, ds.num_features, itemsize, local_iters) > 0
        pallas = (
            math == "fast"
            and itemsize == 4
            and platform == "tpu"
            and fits
            # the kernels' VMEM blocks assume the full d per device;
            # feature-parallel runs keep the fori_loop fast path
            and not has_fp(mesh)
        )
    if pallas and has_fp(mesh):
        raise ValueError(
            "the Pallas SDCA kernel does not support feature-parallel (fp) "
            "meshes; use pallas=False"
        )
    if pallas and math != "fast":
        raise ValueError("pallas=True requires math='fast'")
    if pallas and platform not in ("tpu", "cpu"):
        raise ValueError(
            f"the Pallas SDCA kernel needs a TPU backend (or CPU interpret "
            f"mode); current platform is {platform!r}"
        )
    # the block recurrence rides its own Pallas kernel when it can (TPU,
    # f32, whole lane tiles, no feature-parallel axis, fits VMEM —
    # ops/pallas_chain.py); otherwise the portable XLA fori_loop chain
    # (also what the x64 CPU validation tests compare).  ``block_chain``
    # overrides the auto choice (tests use "pallas_interpret" to exercise
    # the driver-integrated kernel path on CPU).
    if block_chain is not None:
        if block_chain not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(f"block_chain must be xla|pallas|"
                             f"pallas_interpret, got {block_chain!r}")
        if block_chain != "xla" and has_fp(mesh):
            raise ValueError("the Pallas block-chain kernel does not "
                             "support feature-parallel (fp) meshes")
    else:
        from cocoa_tpu.ops.pallas_chain import chain_fits

        block_chain = "xla"
        if (
            block_size > 0
            and block_size % 128 == 0
            and itemsize == 4
            and platform == "tpu"
            # the kernel assumes the full d per device
            and not has_fp(mesh)
            # VMEM working set: K/D shards per device on the mesh path
            # (1 when 1:1), all K logical shards on the single-chip path
            and chain_fits(m_local, block_size, 4)
        ):
            block_chain = "pallas"
    placement = dict(
        layout=layout, platform=platform,
        devices=len(ds.labels.sharding.device_set),
        shards_per_device=m_local,
        pass_slot_share=_pass_slot_share(ds, m_local) if sparse else 1.0,
        storage="stream" if stream else "rectangle",
    )
    if sparse:
        (placement["slot_fill"], placement["longest_row"],
         placement["chunk_fill"]) = _slot_stats(ds)[:3]
    if stream:
        from cocoa_tpu.ops.pallas_longrows import CHUNK_PIECES

        placement["chunk_pieces"] = CHUNK_PIECES
    if block_size <= 0:
        from cocoa_tpu.ops import losses
        from cocoa_tpu.ops.pallas_sdca import (class_ring_depth, class_rows,
                                               pick_interleave,
                                               stores_row_major)

        if classes > 1:
            placement.update(
                classes=classes,
                class_axis="lanes" if sparse else "sublanes",
                class_state="tiles" if pallas and not sparse else None,
                lane_fill=(classes / class_pad(classes) if sparse else
                           classes / class_rows(classes) if pallas
                           else None))
            if sparse:
                placement.update(
                    class_tiles=class_pad(classes) // CLASS_TILE,
                    label_slots=label_slots)
        if pallas and hbm_state and stream:
            # the stream's lane chain: its own record says what a round runs
            placement.update(plan=_hbm_plan(ds, local_iters))
        elif pallas and hbm_state:
            plan = _hbm_plan(ds, local_iters)
            placement.update(
                local_ids="direct" if plan.direct else "sorted",
                segments=plan.t, table_width=plan.w_r,
                slots_walked=(float(plan.w_r) if plan.unrolled
                              else _slot_stats(ds)[3]))
            if plan.t_pad:
                placement.update(ids_per_segment=plan.m)
            else:
                placement.update(
                    slot_walk="unrolled" if plan.unrolled else "grouped")
        form = depth = None
        if pallas and not sparse:
            # the dense kernel's form and its ring's depth, from the VMEM
            # fit alone (ops/pallas_sdca.dense_form); a class kernel forced
            # past its fit runs the shallowest ring and Mosaic reports the
            # allocation itself
            depth = ((class_ring_depth(m_local, ds.n_shard, ds.num_features,
                                       classes, itemsize, local_iters) or 2)
                     if classes > 1
                     else pick_interleave(m_local, ds.n_shard,
                                          ds.num_features, itemsize,
                                          local_iters) or None)
            form = "interleaved" if depth else "shard_major"
            row_major = stores_row_major(ds.num_features)
            placement.update(row_fetch="ring" if depth else "pipelined",
                             ring_depth=depth,
                             rows="row_major" if row_major
                             else "device_default",
                             row_align="stored" if row_major else "kernel")
        return SolverPath(
            inner="sequential", kernel="pallas" if pallas else "fori",
            chain=None, interpret=bool(pallas and platform == "cpu"),
            form=form,
            state="vmem" if pallas and not hbm_state else "hbm",
            step_solve=("scalar" if not pallas
                        or sparse and not (hbm_state or stream)
                        else "lanes" if (stream and classes > 1
                                         or not sparse and (
                            classes > 1 or losses.step_is_iterative(loss)))
                        else "vector"),
            refused="" if pallas else refused,
            **placement)
    if block_chain == "xla":
        return SolverPath(inner="block", kernel="xla", chain="xla",
                          interpret=False, **placement)
    form = resolve_block_form(
        sparse=sparse, hybrid=bool(ds.n_hot), k=m_local, block=block_size,
        d=ds.num_features, n_shard=ds.n_shard, width=width,
        itemsize=itemsize, sparse_gram=block_sparse_gram,
    )
    return SolverPath(inner="block", kernel=form, chain="pallas",
                      interpret=block_chain == "pallas_interpret",
                      **placement)


def _alg_config(params: Params, k: int, plus: Optional[bool], mode=None):
    """(mode, scaling, sigma) for the three SDCA-family algorithms.

    scaling law: γ (CoCoA+, additive) | β/K (CoCoA, averaging) —
    CoCoA.scala:37, with σ′ = K·γ (CoCoA.scala:45); β/(K·H) for
    mini-batch CD (MinibatchCD.scala:32, w frozen so σ is unused).

    ``params.sigma`` overrides σ′ (extension, --sigma): K·γ is the paper's
    safe bound for ADVERSARIAL shard coherence; randomly-partitioned data
    tolerates a smaller σ′ = bigger effective local steps, and the exact
    duality-gap certificate reports divergence if pushed too far
    (measured: σ′=K/2 halves rcv1's certified comm-rounds; anything below
    K/2 — already σ′=3.5 at K=8 — diverges visibly)."""
    if mode == "frozen":
        # σ is unused by the frozen subproblem (MinibatchCD.scala:104 reads
        # only the frozen w), so even sigma="auto" is fine to ignore here —
        # the reference driver runs mini-batch CD from the same flag set
        return "frozen", params.beta / (k * params.local_iters), 1.0
    if params.sigma == "auto":
        raise ValueError("sigma='auto' is resolved by run_cocoa (it needs "
                         "the retry loop); it cannot reach _alg_config")
    sig = k * params.gamma if params.sigma is None else float(params.sigma)
    if plus:
        return "plus", params.gamma, sig
    return "cocoa", params.beta / k, sig


def _sdca_round_parts(
    params: Params,
    k: int,
    mode: str,
    scaling: float,
    sigma: float,
    math: str = "exact",
    pallas: bool = False,
    pallas_interpret: bool = False,
    pallas_state: str = "vmem",
    hbm_plan=None,
    block: int = 0,
    block_chain: str = "xla",
    block_distinct: bool = False,
    block_sparse_gram=None,
    classes: int = 1,
):
    """The per-shard local update and driver-side apply shared by the
    per-round and chunked builders (so the two paths cannot diverge), for
    all three SDCA-family algorithms (CoCoA, CoCoA+, mini-batch CD — see
    :func:`_alg_config` for the scaling laws).

    ``math="fast"`` uses the margins decomposition (ops/local_sdca.py
    ``mode_factors``): one MXU matvec per round + an incremental Δw dot per
    step — equal in real arithmetic, rounds differently than the reference
    order.  ``pallas=True`` further runs the inner loop as a Pallas TPU
    kernel — ops/pallas_sdca.py for the dense layout, ops/pallas_sparse.py
    for padded-CSR (``pallas_state="hbm"``: ops/pallas_sparse_hbm.py, on
    ``hbm_plan``, the plan the resolver reported).  ``block > 0`` runs the
    fast inner loop as the block-coordinate MXU kernel
    (ops/local_sdca.local_sdca_block) with that block size (a round of more
    than one block takes the two-phase software-pipelined scan, see
    local_sdca_block_batched).  Returns
    (per_shard, per_round_batched | None, apply_fn, carry_form | None).
    ``carry_form(shards)`` (None but on a one-vs-rest job): None where the
    carry ``per_round_batched`` advances is alpha as the loop's state holds
    it, else the pair ``(pack, unpack)`` between that alpha and the carry,
    which a chunk applies once around its scan (:func:`_class_round`).

    ``classes`` = T > 1 (a one-vs-rest job, one chip): the state is w
    (T, d), alpha (T, K, n_shard) and ``per_round_batched`` is always
    there — the Pallas kernel with its class axis, or, on the ``fori``
    path, the T = 1 ``per_shard`` under a vmap over classes and shards,
    class t's labels derived from the class ids; every class takes the
    round's one (K, H) table."""
    if classes > 1:
        one = _sdca_round_parts(
            params, k, mode, scaling, sigma, math=math,
            pallas_interpret=pallas_interpret, pallas_state=pallas_state)
        per_round, carry_form = _class_round(
            params, mode, scaling, sigma, classes, one[0], pallas,
            pallas_interpret, hbm_plan, block_chain)
        return one[0], per_round, one[2], carry_form
    if math not in ("exact", "fast"):
        raise ValueError(f"math must be 'exact' or 'fast', got {math!r}")
    if block and pallas:
        raise ValueError("block-coordinate mode replaces the Pallas kernel; "
                         "pass pallas=False with block > 0")
    if block and math == "exact":
        raise ValueError("block > 0 requires math='fast' (the block kernel "
                         "is a margins-decomposition variant)")

    def apply_fn(w, dw_sum, x=None):
        # CoCoA.scala:47-48 / MinibatchCD.scala:42-43 (x unused: no η(t))
        return w + scaling * dw_sum

    if math == "exact":
        if pallas:
            raise ValueError("the Pallas kernel implies math='fast'")

        @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
        def per_shard(w, alpha_k, idxs_k, shard_k):
            da, dw = local_sdca(
                w, alpha_k, shard_k, idxs_k, params.lam, params.n,
                mode=mode, sigma=sigma,
                loss=params.loss, smoothing=params.smoothing,
            )
            # CoCoA.scala:101 / MinibatchCD.scala:127-128
            return dw, alpha_k + scaling * da

        return per_shard, None, apply_fn, None

    from cocoa_tpu.ops.local_sdca import (
        local_sdca_block, local_sdca_block_batched, local_sdca_fast,
    )
    from cocoa_tpu.ops.rows import shard_margins

    def block_round(w, alpha, idxs_kh, shards):
        """The batched block kernel with this algorithm's parameters — the
        one call site per_shard (mesh) and per_round_batched (single chip)
        share."""
        return local_sdca_block_batched(
            w, alpha, shards, idxs_kh, params.lam, params.n, mode=mode,
            sigma=sigma, loss=params.loss, smoothing=params.smoothing,
            block=block, interpret=(block_chain == "pallas_interpret"),
            distinct=block_distinct, sparse_gram=block_sparse_gram,
        )

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_shard(w, alpha_k, idxs_k, shard_k):
        if pallas:
            # only reached inside the chunked mesh driver, which runs its
            # shard_map with check_vma=False (pallas_call's internal slices
            # confuse the VMA checker)
            batched = jax.tree.map(lambda a: a[None], shard_k)
            dw, a_inner = _pallas_batched(
                w, alpha_k[None], idxs_k[None], batched, params, mode,
                sigma, pallas_interpret, pallas_state, hbm_plan,
            )
            da = a_inner[0] - alpha_k
            return dw[0], alpha_k + scaling * da
        if block and block_chain != "xla":
            # single-shard view of the batched block kernel (the mesh path:
            # one shard per device under shard_map, check_vma=False)
            da, dw = block_round(
                w, alpha_k[None], idxs_k[None],
                jax.tree.map(lambda a: a[None], shard_k),
            )
            return dw[0], alpha_k + scaling * da[0]
        m0 = shard_margins(w, shard_k)
        inner = local_sdca_fast if not block else functools.partial(
            local_sdca_block, block=block
        )
        da, dw = inner(
            m0, alpha_k, shard_k, idxs_k, params.lam, params.n,
            jnp.zeros_like(w), mode=mode, sigma=sigma,
            loss=params.loss, smoothing=params.smoothing,
        )
        return dw, alpha_k + scaling * da

    per_round_batched = None
    if pallas:
        # the Pallas kernels own the shard axis via their (K, H) grids —
        # used on the single-chip path instead of vmap(per_shard)
        @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
        def per_round_batched(w, alpha, idxs_kh, shards):
            dw, a_inner = _pallas_batched(
                w, alpha, idxs_kh, shards, params, mode, sigma,
                pallas_interpret, pallas_state, hbm_plan,
            )
            alpha_new = alpha + scaling * (a_inner - alpha)
            return dw.sum(axis=0), alpha_new
    elif block and block_chain != "xla":
        # the batched block kernel advances every shard's chain inside one
        # Pallas instance — vmap(per_shard) would serialize K kernel
        # instances through the grid instead
        @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
        def per_round_batched(w, alpha, idxs_kh, shards):
            da, dw = block_round(w, alpha, idxs_kh, shards)
            return dw.sum(axis=0), alpha + scaling * da

    return per_shard, per_round_batched, apply_fn, None


def _class_round(params: Params, mode: str, scaling: float, sigma: float,
                 classes: int, per_shard, pallas: bool, interpret: bool,
                 lanes_plan=None, block_chain: str = "xla"):
    """``(per_round_batched, carry_form)`` of a one-vs-rest round
    (:func:`_sdca_round_parts`): ``per_round_batched(w, carry, idxs (K, H),
    shards) -> (dw, carry')``, the scaling law applied.  What the carry is
    follows the layout's rule (``SolverPath.class_axis``), read off the
    arrays:

    - padded-CSR rows: the class axis rides the lanes — w (d, R, 128), the
      carry alpha (K, n_shard, R, 128) as the loop's state holds it,
      ops/pallas_sparse_lanes.py: its HBM-state chain on ``lanes_plan``, or
      the same round in plain XLA — and the round names its own scopes;
    - dense rows on the block solve (``lanes_plan`` an
      ops/block_lanes.BlockLanesPlan: the T models outgrew the sublane
      kernel): the same w and carry, a block of rows a step, its replay
      ``block_chain``'s; the whole round under the solve's scope, the
      products and the replay under scopes of their own inside it;
    - dense rows on the class kernel (``SolverPath.class_state``
      ``tiles``): w (T, d), the carry the kernel's own state tiles (K,
      n_blocks, R, 128) (ops/pallas_sdca.class_state_pack): the round is
      one call that takes the tiles and gives them back, the law applied
      to them in its epilogue, and ``carry_form`` hands the chunk the
      pack and its inverse, so that alpha (T, K, n_shard) is relaid once a
      chunk of rounds and not once a round;
    - dense rows on ``fori``: the carry alpha (T, K, n_shard), the T = 1
      ``per_shard`` under a vmap over classes and shards."""
    from cocoa_tpu.data.sharding import class_labels

    def per_round_lanes(w, alpha, idxs_kh, shards):
        common = dict(lam=params.lam, n=params.n, classes=classes,
                      mode=mode, sigma=sigma, scaling=scaling,
                      loss=params.loss, smoothing=params.smoothing)
        if pallas and "sp_row_ptr" in shards:
            # rows kept as a stream: the chain walks a row's nonzeros out
            # of the stream itself
            from cocoa_tpu.ops.pallas_longrows_lanes import (
                pallas_stream_lanes_round)

            return pallas_stream_lanes_round(
                w, alpha, shards, idxs_kh, plan=lanes_plan,
                interpret=interpret, **common)
        from cocoa_tpu.ops import pallas_sparse_lanes as lanes

        if pallas:
            return lanes.pallas_sparse_lanes_round(
                w, alpha, shards, idxs_kh, plan=lanes_plan,
                interpret=interpret, **common)
        with jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE):
            return lanes.sparse_lanes_round_fori(w, alpha, shards, idxs_kh,
                                                 **common)

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_round_block(w, alpha, idxs_kh, shards):
        from cocoa_tpu.ops.block_lanes import block_lanes_round

        return block_lanes_round(
            w, alpha, shards, idxs_kh, params.lam, params.n, classes,
            lanes_plan, mode=mode, sigma=sigma, scaling=scaling,
            loss=params.loss, smoothing=params.smoothing,
            replay=block_chain)

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_round_tiles(w, state, idxs_kh, shards):
        from cocoa_tpu.ops.pallas_sdca import pallas_sdca_round_classes_tiles

        # every class takes the round's one table of sampled rows, and its
        # labels are derived from the class ids in the step; dw arrives
        # (T, d), summed over the shards
        return pallas_sdca_round_classes_tiles(
            w, state, shards.get("X_folded", shards["X"]), idxs_kh,
            params.lam, params.n, mode=mode, sigma=sigma,
            interpret=interpret, loss=params.loss,
            smoothing=params.smoothing, scaling=scaling)

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_round_classes(w, alpha, idxs_kh, shards):
        binary = {f: v for f, v in shards.items() if f != "classes"}

        def one_class(w_t, alpha_t, t):
            labels = class_labels(shards["classes"], shards["mask"], t)
            dw, alpha_new = jax.vmap(per_shard, in_axes=(None, 0, 0, 0))(
                w_t, alpha_t, idxs_kh, {**binary, "labels": labels})
            return dw.sum(axis=0), alpha_new

        return jax.vmap(one_class)(w, alpha, jnp.arange(classes))

    def per_round(w, carry, idxs_kh, shards):
        return (per_round_lanes if "sp_indices" in shards
                else per_round_block if w.ndim == 3
                else per_round_tiles if pallas
                else per_round_classes)(w, carry, idxs_kh, shards)

    def carry_form(shards):
        if not pallas or "sp_indices" in shards:
            return None
        from cocoa_tpu.ops.pallas_sdca import (class_state_alpha,
                                               class_state_pack)

        # the pack and its inverse are the solve's: they wear its scope
        solve = jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
        n_shard = shards["sq_norms"].shape[-1]
        return (solve(lambda alpha: class_state_pack(
                    alpha, shards["sq_norms"], shards["classes"])),
                solve(lambda state: class_state_alpha(state, classes,
                                                      n_shard)))

    return per_round, carry_form


def make_round_step(mesh, params: Params, k: int, alg, **parts_kw):
    """Build the jitted (w, alpha, idxs, shard_arrays) -> (w', alpha') step.
    ``alg`` = (mode, scaling, sigma), see :func:`_alg_config`."""
    per_shard, _, apply_fn, _ = _sdca_round_parts(params, k, *alg,
                                                  **parts_kw)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_step(w, alpha, idxs, shard_arrays):
        dw_sum, alpha_new = base.fanout(
            per_shard, mesh, w, alpha, idxs, shard_arrays,
            reduce_scope=_tracing.SCOPE_DW_REDUCE,
        )
        with jax.named_scope(_tracing.SCOPE_DW_REDUCE):
            return apply_fn(w, dw_sum), alpha_new

    return round_step


def _make_chunk_kernel(mesh, params: Params, k: int, alg, sampler=None,
                       **parts_kw):
    """The un-jitted traceable chunk body every branch of
    :func:`build_sdca_loop`'s table is (so the host-stepped step and the
    device-resident driver cannot diverge):
    (w, alpha, idxs_ckh, shard_arrays) -> (w', alpha'), C rounds as one
    ``lax.scan`` (parallel/fanout.py chunk_fanout).  On Pallas configs the
    caller (run_sdca_family) pre-folds ``shard_arrays["X_folded"]`` once per
    dataset — the kernel itself never folds.  Whether a dispatch still
    relays the folded rows depends on how they are STORED
    (``SolverPath.rows`` / ``row_align``): lane-padded, row-major is the
    device's own layout for them and the loop reads them as they are;
    otherwise a TPU keeps the row index on the lanes and every dispatch
    opens with one pass of the relayout kernel over the whole array
    (ops/pallas_sdca.lane_aligned).

    ``idxs_ckh`` is a concrete (C, K, H) table, or — device-sampling mode —
    the ``{"t": (C,)}`` spec expanded in-jit through ``sampler`` (index
    draws stay on device; see base.IndexSampler)."""
    from cocoa_tpu.parallel.fanout import chunk_fanout

    per_shard, per_round_batched, apply_fn, carry_form = _sdca_round_parts(
        params, k, *alg, **parts_kw
    )

    def chunk_kernel(w, alpha, idxs_ckh, shard_arrays):
        if isinstance(idxs_ckh, dict):
            idxs_ckh = sampler.tables_from_ts(idxs_ckh["t"])
        # the fold cache lane-aligned once a chunk, outside its scan (the
        # device loop has done it once a dispatch, and this emits nothing)
        from cocoa_tpu.ops.pallas_sdca import with_aligned_rows

        shard_arrays = with_aligned_rows(shard_arrays, mesh)
        # likewise the class kernel's state: alpha packed into its tiles
        # once a chunk, the scan's carry the tiles, alpha taken back out
        # for the eval behind the chunk (:func:`_class_round`); on every
        # other path the carry is alpha as it is
        form = carry_form(shard_arrays) if carry_form else None
        w, carry = chunk_fanout(
            mesh, per_shard, apply_fn, w, form[0](alpha) if form else alpha,
            idxs_ckh, shard_arrays, per_round_batched=per_round_batched,
            # pallas_call's internal slices confuse shard_map's VMA type
            # checker; the manual pcast/psum handling makes it safe to skip
            check_vma=not (parts_kw.get("pallas", False)
                           or parts_kw.get("block_chain", "xla") != "xla"),
        )
        return w, form[1](carry) if form else carry

    return chunk_kernel


_CHUNK_STEPS: dict = base.ExecutableCache()


@jax.named_scope(_tracing.SCOPE_ACCEL_JUMP)
def secant_jump(w, alpha, hist, shard_arrays, mesh, inv_lam_n):
    """The secant (Anderson-1) jump from the banked window displacements
    (solvers/base.py layout note): α ← α + c·(α − h2), c from the windows'
    autocorrelation (base.secant_coef), clipped to the hinge-family dual
    box and padding-masked, and w advanced by the EXACT correspondence
    update Σ y·Δα·x/(λn) — (w, α) stays a feasible certified pair."""
    # multiply-then-sum, not vdot: on a dp mesh (explicit axis types) a
    # contraction over the sharded shard axis has no unambiguous output
    # sharding and is rejected; a sum over it reduces to a replicated scalar
    d1 = hist[1] - hist[0]
    den = jnp.sum(d1 * d1)
    rho = jnp.where(
        den > 0,
        jnp.sum(d1 * (alpha - hist[1]))
        / jnp.where(den > 0, den, jnp.float32(1)),
        jnp.float32(0))
    cj = base.secant_coef(jnp, rho)
    a_ext = jnp.clip(alpha + cj * (alpha - hist[1]),
                     0.0, 1.0) * shard_arrays["mask"]
    coefs = (shard_arrays["labels"] * (a_ext - alpha)
             * jnp.float32(inv_lam_n))
    if mesh is None:
        return _rows.shards_axpy(coefs, shard_arrays, w), a_ext

    # on a mesh the scatter is shard-local and the combine is the same one
    # Δw psum a round pays (base.fanout)
    def shard_axpy(w_, coefs_k, shard_k):
        return (_rows.shards_axpy(
            coefs_k[None], jax.tree.map(lambda a: a[None], shard_k),
            jnp.zeros_like(w_)),)

    (dw_jump,) = base.fanout(shard_axpy, mesh, w, coefs, shard_arrays)
    return w + dw_jump, a_ext


def build_sdca_loop(mesh, params: Params, k: int, alg, sampler, parts_kw, *,
                    levels: tuple, branch_params: list, theta_hs: tuple,
                    warm_end: int = 0, bank: bool = False):
    """The loop program of an SDCA-family job, from its static description:
    the σ′ ladder ``levels`` (base.anneal_levels), ``branch_params`` (the
    job's Params, or under ``--warmStart`` the smooth-hinge phase that runs
    for rounds ≤ ``warm_end`` and then the job's), the Θ ladder
    ``theta_hs`` (base.theta_ladder) and ``bank`` (the state carries the
    ``--accel`` window bank ``hist``).  Returns ``(chunk_kernel,
    chunk_step, sched_token)``: the traceable ``chunk_kernel(state,
    idxs_ckh, shard_arrays) -> state`` the device loop's body calls, the
    jitted host-stepped ``chunk_step(*state, idxs_ckh, shard_arrays)``
    (cached per configuration in ``_CHUNK_STEPS``) and the token that
    names the description in a cache key.

    ONE branch table, ``[branch(bp, lv, hs) for lv in levels for bp in
    branch_params for hs in theta_hs]``, every branch the SAME
    statically-specialized chunk (:func:`_make_chunk_kernel`: every
    Pallas/block configuration keeps its baked-in scalars).  A one-entry
    table with no bank is the plain job: state ``(w, α)``, the kernel IS
    its branch.  Anything else carries the float32 schedule leaf last
    (base.SCHED_LEN layout; donated, checkpointed and resumed with (w,
    α)): the traced stage, round and Θ stage in it pick WHICH branch a
    chunk runs — a ``lax.switch``, so σ′, the loss phase and H change IN
    the device loop with no re-dispatch and no retrace, and a run that
    never leaves its first branch is bit-identical to the fixed job.  A Θ
    stage slices the sampled tables to its H_s prefix: every mode's draw
    stream is prefix-stable, so a stage runs FEWER of the reference draws,
    never different ones.  With ``bank`` the state is ``(w, α, hist,
    sched)`` and a chunk opens by consuming an armed secant jump
    (base.A_JUMP, set by the drivers' eval-boundary update;
    :func:`secant_jump`): the rounds themselves are UNMODIFIED — the
    acceleration lives between windows, so the certificate arithmetic
    never changes."""
    n_levels, n_phases, n_theta = (len(levels), len(branch_params),
                                   len(theta_hs))
    full_h = params.local_iters
    scheduled = bank or n_levels * n_phases * n_theta > 1

    def branch(bp, lv, hs):
        kern = _make_chunk_kernel(
            mesh, dataclasses.replace(bp, local_iters=int(hs)), k,
            (alg[0], alg[1], lv), sampler=sampler, **parts_kw)
        if hs >= full_h:
            return kern
        return lambda w, alpha, idxs_ckh, shard_arrays: kern(
            w, alpha, idxs_ckh[:, :, :hs], shard_arrays)

    table = [branch(bp, lv, hs) for lv in levels for bp in branch_params
             for hs in theta_hs]
    inv_lam_n = 1.0 / (params.lam * params.n)

    def chunk_kernel(*args):
        *state, idxs_ckh, shard_arrays = args
        if not scheduled:
            return table[0](*state, idxs_ckh, shard_arrays)
        (w, alpha), sched = state[:2], state[-1]
        if isinstance(idxs_ckh, dict):
            idxs_ckh = sampler.tables_from_ts(idxs_ckh["t"])
        c_len = idxs_ckh.shape[0]
        th = ph = 0
        if bank:
            w, alpha = jax.lax.cond(
                sched[base.A_JUMP] > 0,
                lambda w, a: secant_jump(w, a, state[2], shard_arrays, mesh,
                                          inv_lam_n),
                lambda w, a: (w, a), w, alpha)
            sched = sched.at[base.A_JUMP].set(jnp.float32(0))
        stage = jnp.clip(sched[0].astype(jnp.int32), 0, n_levels - 1)
        if bank:
            th = jnp.clip(sched[base.A_TH_STAGE].astype(jnp.int32), 0,
                          n_theta - 1)
        if n_phases == 2:
            # the chunk is warm iff it ends at or before warm_end; chunks
            # never straddle an eval-cadence boundary (the drivers cut them
            # there), so one test a chunk is exact for every driver
            ph = jnp.where(
                sched[4] + (c_len - 1) <= jnp.float32(warm_end), 0, 1)
        w, alpha = jax.lax.switch((stage * n_phases + ph) * n_theta + th,
                                  table, w, alpha, idxs_ckh, shard_arrays)
        return (w, alpha, *state[2:-1],
                sched.at[4].add(jnp.float32(c_len)))

    sched_token = (None if not scheduled else
                   (levels, warm_end, branch_params[0].loss,
                    branch_params[0].smoothing, theta_hs, bank))
    step_key = (
        mesh, k, alg, sched_token, params.lam, params.n, params.local_iters,
        params.beta, params.gamma, params.loss, params.smoothing,
        sampler.cache_token(), tuple(sorted(parts_kw.items())),
    )
    chunk_step = _CHUNK_STEPS.get(step_key)
    if chunk_step is None:
        # every leaf is donated but hist: it is read-only in the kernel
        # (the drivers rebind it at eval boundaries)
        chunk_step = _CHUNK_STEPS[step_key] = jax.jit(
            chunk_kernel, donate_argnums=tuple(
                i for i in range(2 + bank + scheduled)
                if not (bank and i == 2)))
    return (lambda state, idxs_ckh, shard_arrays: chunk_kernel(
        *state, idxs_ckh, shard_arrays)), chunk_step, sched_token


_START_PROGRAMS: dict = base.ExecutableCache()


def _start_state(ds: ShardedDataset, dtype, mesh, arm: str, residual: bool,
                 start_round: int, w_init, alpha_init, hist_init,
                 sched_init, lanes: bool = False) -> tuple:
    """The start state of an SDCA-family job, ``(w, α)`` and per ``arm``
    the leaves its loop carries: ``"accel"`` the (2, K, n_shard) window
    bank and the schedule leaf, ``"sched"`` the schedule leaf, ``"plain"``
    neither.  ``residual`` (the prox family): the shared vector is
    r = A·x − b and a job with no ``w_init`` starts it at −``ds.target``
    (x = 0), not at 0.

    Nothing handed in (every benchmark job, every first run): ONE jitted
    program per (shapes, dtype, arm, mesh) makes every leaf, placed by its
    ``out_shardings``, and is dispatched without waiting — the loop
    program goes out right behind it, and the device fills the zeros while
    the host walks the rest of its path.  The schedule leaf's start values
    are host constants (base.sched_init_values), passed in.  An init
    handed in (resume, warm start): a leaf at a time, as before — each a
    tiny program the device waits for, then its ``device_put`` on a mesh."""
    # plain ints: the cached start program must not keep ``ds`` alive
    d, k, n_shard = int(ds.num_features), ds.k, int(ds.n_shard)
    # a one-vs-rest job's leaves carry the class axis: first where a class
    # is a sublane of the dense kernel, w (T, d), alpha (T, K, n_shard);
    # last and as tiles in the lanes form (``lanes``:
    # :func:`class_state_on_lanes`), W (d, R, 128), alpha (K, n_shard, R,
    # 128) (data/sharding.class_tile_shape); at T = 1 there is none
    lead = (ds.num_classes,) if ds.num_classes > 1 and not lanes else ()
    trail = class_tile_shape(ds.num_classes) if lanes else ()
    if lanes and any(v is not None for v in (w_init, alpha_init)):
        raise ValueError(
            f"a job over {ds.num_classes} classes whose models ride the "
            f"lanes starts from alpha = 0, W = 0: no state is handed in "
            f"yet")
    accel = arm == "accel"
    sched = (None if arm == "plain"
             else base.sched_init_values(start_round, sched_init, accel))
    shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from cocoa_tpu.parallel.mesh import (DP_AXIS, primal_sharding,
                                             sharded_rows)

        shardings = (primal_sharding(mesh),
                     sharded_rows(mesh, extra_dims=1),
                     *((NamedSharding(mesh, P(None, DP_AXIS)),)
                       if accel else ()),
                     *(() if sched is None else (NamedSharding(mesh, P()),)))
    if all(v is None for v in (w_init, alpha_init, hist_init, sched_init)):
        key = (d, k, n_shard, str(dtype), arm, residual, mesh, lead, trail)
        start = _START_PROGRAMS.get(key)
        target = ds.target if residual else None
        _sanitize.count_launch()
        if start is not None:
            return start(sched, target)
        # the process's first job of this shape: the one place that waits
        # for the leaves, so the span's closing reading holds them
        with _tracing.cold_span("build_start") as cold:
            def start(sched, target):
                w = (jnp.zeros(lead + (d,) + trail, dtype=dtype)
                     if target is None else (-target).astype(dtype))
                alpha = jnp.zeros(lead + (k, n_shard) + trail, dtype=dtype)
                hist = ((jnp.zeros((2, k, n_shard), dtype=dtype),)
                        if accel else ())
                return (w, alpha, *hist,
                        *(() if sched is None else (sched,)))

            start = _START_PROGRAMS[key] = jax.jit(
                start, out_shardings=shardings)
            leaves = start(sched, target)
            cold.built(start, sched, target)
            cold.made(leaves)
        return leaves

    if w_init is not None:
        w = jnp.array(w_init, dtype=dtype, copy=True)
    elif residual:
        w = (-ds.target).astype(dtype)
    else:
        w = jnp.zeros(lead + (d,) + trail, dtype=dtype)
    if alpha_init is None:
        alpha = jnp.zeros(lead + (k, n_shard) + trail, dtype=dtype)
    elif lead:
        alpha = jnp.array(alpha_init, dtype=dtype, copy=True)
    else:
        alpha = base.align_alpha(alpha_init, ds, dtype)
    if lead and (w.shape, alpha.shape) != (lead + (d,),
                                            lead + (k, n_shard)):
        raise ValueError(
            f"a job over {lead[0]} classes starts from w {lead + (d,)} and "
            f"alpha {lead + (k, n_shard)}; it was handed {w.shape} and "
            f"{alpha.shape}")
    leaves = [w, alpha]
    if accel:
        leaves.append(jnp.zeros((2,) + alpha.shape, dtype=dtype)
                      if hist_init is None
                      else jnp.array(hist_init, dtype=dtype, copy=True))
    if sched is not None:
        leaves.append(jnp.asarray(sched))
    _sanitize.count_launch(len(leaves))
    if shardings is not None:
        leaves = [jax.device_put(a, sh) for a, sh in zip(leaves, shardings)]
    return tuple(leaves)


def _check_class_job(ds: ShardedDataset, alg, arm: str, debug: DebugParams,
                     test_ds, own_eval: bool) -> None:
    """Refuse, by name and before anything is built, what a one-vs-rest job
    (``ds.num_classes`` > 1) does not carry yet.  The layout, the mesh and
    the block kernels are refused where the path is resolved
    (:func:`resolve_solver_path`)."""
    t = ds.num_classes
    if ds.classes is None:
        raise ValueError(f"the dataset states {t} classes and carries no "
                         f"class ids (ShardedDataset.classes)")
    if alg[0] == "prox" or own_eval:
        raise ValueError(
            f"a set of {t} classes trains {t} SVMs one-vs-rest "
            f"(run_cocoa, run_minibatch_cd); the prox family "
            f"(--objective=lasso) regresses on one target and has no "
            f"class axis")
    if arm != "plain":
        raise ValueError(
            f"a one-vs-rest job over {t} classes runs the plain outer "
            f"loop: the --accel window bank and the sigma' / warm-start "
            f"schedule leaf hold ONE model's state (pass --accel=off, no "
            f"--sigma=auto, --sigmaSchedule or --warmStart)")
    if debug.chkpt_dir and debug.chkpt_iter > 0:
        raise ValueError(
            f"checkpoints hold one model's (w, alpha): a one-vs-rest job "
            f"over {t} classes writes none yet (drop --chkptIter)")
    if test_ds is not None and test_ds.num_classes != t:
        raise ValueError(
            f"the training set states {t} classes and the test set "
            f"{test_ds.num_classes}: load both with the same --classes")


def _kernel_arrays(ds: ShardedDataset, path: SolverPath,
                   block_size: int) -> dict:
    """``ds.shard_arrays()`` with what the resolved kernels read beside
    them, each made ONCE per DATASET and kept on the ds object: inside the
    round loop it would be redone every round, and per RUN it is a fixed
    cost a process that reuses the dataset — back-to-back jobs, sweep
    loops, the sigma=auto trial+safe pair — would pay on every call.  Safe
    to share: both are jit INPUTS (never donated), so no dispatch can
    overwrite them."""
    def once(attr, phase, make):
        made = getattr(ds, attr, None)
        if made is None:
            with _tracing.cold_span(phase) as cold:
                made = make()
                cold.made(made)
            setattr(ds, attr, made)
        return made

    shard_arrays = ds.shard_arrays()
    if path.pallas and ds.layout == "dense":
        # X folded for the dense kernel.  Where the lane padding is small
        # it is stored lane-padded (path.rows), which makes the layout the
        # device loop reads it in the device's own for that shape: the
        # loop opens with no copy of it.
        from cocoa_tpu.ops.pallas_sdca import fold_rows

        shard_arrays["X_folded"] = once(
            "_x_folded_cache", "fold_rows", lambda: fold_rows(
                shard_arrays["X"], row_major=path.rows == "row_major"))
    if ((path.pallas or block_size > 0) and ds.layout == "sparse"
            and "sp_row_len" not in shard_arrays):
        # per-row nnz counts for the kernels' group early exit (sequential
        # sparse kernel AND the sparse block-chain path); a dataset in
        # length order brings them itself
        from cocoa_tpu.ops.pallas_sparse import row_lengths

        # (jitted: one reduce, where the eager form holds a (K, n_shard, W)
        # mask and its int32 twin, 1.6 x the values, while it runs)
        shard_arrays["sp_row_len"] = once(
            "_row_len_cache", "row_lengths",
            lambda: jax.jit(row_lengths)(shard_arrays["sp_values"]))
        _slot_stats(ds)     # counted on the host here, once, in the job
                            # that made the lengths: not in the next one
    return shard_arrays


def _loop_description(params: Params, debug: DebugParams, alg, path,
                      block_size, gap_target, sigma_levels, warm_start,
                      accel: bool, theta: str) -> dict:
    """Validate the schedule flags of an SDCA-family job and return
    :func:`build_sdca_loop`'s static description of it, as keywords."""
    theta_hs = (params.local_iters,)
    if accel:
        if debug.debug_iter <= 0:
            raise ValueError(
                "--accel requires --debugIter > 0 (the momentum restart "
                "rule rides the eval cadence)")
        if theta == "adaptive" and gap_target is None:
            raise ValueError(
                "--theta=adaptive requires --gapTarget (the Θ ladder's "
                "final full-accuracy stage is keyed to the target)")
        theta_hs = base.theta_ladder(params.local_iters, theta == "adaptive")
        if len(theta_hs) > 1 and (path.pallas or block_size > 0):
            raise ValueError(
                "--theta=adaptive slices the sequential (C, K, H) "
                "index tables and is not available on the Pallas/"
                "--blockSize paths (their kernels and the "
                "block-distinct sampling license are keyed to the "
                "full H); drop --theta=adaptive or the block flags")
    warm_end = 0
    branch_params = [params]
    if warm_start is not None:
        warm_s, warm_end = warm_start
        if debug.debug_iter <= 0 or warm_end % debug.debug_iter != 0:
            raise ValueError(
                f"warm_start rounds ({warm_end}) must be a multiple of "
                f"debugIter ({debug.debug_iter}, > 0): the loss handoff "
                f"lands on the eval-cadence chunk boundary — the CLI "
                f"rounds up for you")
        branch_params = [
            dataclasses.replace(params, loss="smooth_hinge",
                                smoothing=float(warm_s)),
            params,
        ]
    return dict(
        levels=(tuple(float(v) for v in sigma_levels)
                if sigma_levels is not None else (float(alg[2]),)),
        branch_params=branch_params, theta_hs=theta_hs, warm_end=warm_end,
        bank=accel)


@_tracing.cold_entry
def run_sdca_family(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    alg_name: str,
    alg,   # (mode, scaling, sigma) — _alg_config
    mesh=None,
    test_ds: Optional[ShardedDataset] = None,
    rng: str = "reference",
    w_init: Optional[jax.Array] = None,
    alpha_init: Optional[jax.Array] = None,
    start_round: int = 1,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    scan_chunk: int = 0,
    math: str = "exact",
    pallas=None,
    block_size: int = 0,
    block_chain=None,
    block_sparse_gram=None,
    device_loop: bool = False,
    eval_fn=None,
    eval_kernel=None,
    eval_arrays=None,
    sampling: str = "auto",
    divergence_guard: str = "auto",
    sigma_levels=None,
    warm_start=None,
    sched_init=None,
    accel: bool = False,
    theta: str = "fixed",
    hist_init=None,
    overlap_io: bool = False,
):
    """Shared driver for the SDCA-family algorithms (CoCoA, CoCoA+,
    mini-batch CD — they differ only in their ``alg`` scaling triple, see
    :func:`_alg_config`) and, with eval overrides, the primal prox family
    (solvers/prox_cocoa.py).  Train; returns (w, alpha, Trajectory).
    Validate, resolve the path (:func:`resolve_solver_path`), make the
    per-dataset caches, build the loop (:func:`build_sdca_loop`), drive it
    (base.drive_device_paths).

    The schedule (normally reached via :func:`run_cocoa`; what each means
    for the loop program is :func:`build_sdca_loop`'s to say):
    ``sigma_levels`` — the static σ′ ladder of ``--sigmaSchedule=anneal``
    (base.anneal_levels; the stall watch fires → stage += 1);
    ``warm_start=(s, warm_end)`` — smooth_hinge(s) for rounds ≤ warm_end
    (a ``debugIter`` multiple) before the final loss; ``accel=True`` — the
    ACCELERATED outer loop (docs/DESIGN.md "Accelerated outer loop"; the
    outer-acceleration structure of Smith et al., arXiv:1711.05305 with a
    measured secant extrapolation in place of fixed momentum): at each
    eval boundary the drivers bank the current α, and once two consecutive
    improving windows are banked the next chunk opens with a secant jump;
    a gap rise restarts the bank (base.eval_boundary_update);
    ``theta="adaptive"`` — with it, the Θ local-accuracy ladder
    (base.theta_ladder): early rounds run H/2 inner steps, tightening to
    the full H near the target.  ``sched_init`` / ``hist_init`` restore
    the schedule leaf and the window bank from a checkpoint (bit-identical
    mid-schedule resume).

    ``eval_fn(state) -> (primal, gap|None, test_err|None)`` and
    ``eval_kernel(state, shard_arrays, test_arrays) -> (3,) metrics``
    override the classification objectives (needed when the state has
    different semantics — e.g. ProxCoCoA+'s residual/coordinates);
    ``eval_arrays`` is then what the device loop hands ``eval_kernel`` as
    ``test_arrays`` (the prox family's regression target, ``ds.target``).

    Extensions over the reference: ``gap_target`` stops early once the
    duality gap — checked at the ``debugIter`` cadence — falls below the
    target; ``w_init``/``alpha_init``/``start_round`` resume from a
    checkpoint (see cocoa_tpu.checkpoint) — round-indexed RNG makes the
    resumed trajectory identical to an uninterrupted run; ``scan_chunk >
    0`` runs rounds device-side in blocks of that size via ``lax.scan``;
    ``device_loop=True`` runs the ENTIRE loop — rounds, evaluations, early
    stop — as one ``lax.while_loop`` on device (base.drive_on_device;
    needs debug_iter > 0).  Every driver shows the same trajectory.

    ``math="fast"``: the margins-decomposition inner loop (equal in real
    arithmetic; trajectories agree to ~1e-6).  ``pallas`` (None = auto:
    fast math + f32 + TPU backend + fits on-chip) runs it as a Pallas TPU
    kernel, by layout.  ``block_size > 0`` (``--blockSize``) runs it as
    the block-coordinate MXU kernel (ops/local_sdca.local_sdca_block) on
    the same sampled index stream, ``block_sparse_gram`` (None = auto) its
    sparse CSR-Gram form; needs ``math="fast"``, excludes ``pallas``.
    ``overlap_io=True`` (``--overlapComm``, single-process runs):
    checkpoint WRITES on the device-loop path ride a writer thread
    (base.drive_device_full).  ``divergence_guard`` ("auto" | "on" |
    "off"): the gap-target stall watch (base.resolve_divergence_guard).
    """
    base.check_shards(ds)
    # a sparse set whose all-rows passes run in row blocks: they stop at a
    # block's longest row, and rows in length order make that a half of the
    # slots.  Once per dataset, in place; α is in ds's order, here and after
    as_built = ds.row_order is None
    if passes_want_order(ds):
        with _tracing.cold_span("order_rows") as cold:
            order_rows_for_passes(ds)
            cold.made(ds.shard_arrays(), ds.row_order)
    if as_built and ds.row_order is not None:
        # what the caller holds by row is in the order ds had on entry
        alpha_init, hist_init = (
            None if a is None else rows_as_ordered(ds, a)
            for a in (alpha_init, hist_init))
    # checkpoints keep what is held by row (α, the --accel window bank) by
    # the rows' positions as built, whatever order ds keeps them in
    ckpt_rows = (None if ds.row_order is None
                 else functools.partial(rows_as_built, ds))
    guard_on = base.resolve_divergence_guard(
        divergence_guard, alg[0], alg[2], ds.k, params.gamma)
    k = ds.k
    if not quiet:
        # ds.n, not params.n: the prox family clones params with n=1 (its
        # update has no λn factor) while ds.n stays the coordinate count
        print(f"\nRunning {alg_name} on {ds.n} data examples, "
              f"distributed over {k} workers")

    dtype = ds.labels.dtype
    if gap_target is not None and dtype == jnp.bfloat16:
        # the dual objective's Σα/n accumulation and the primal−dual
        # cancellation both sit below bf16's ~2^-8 relative resolution, so
        # the computed gap is noise at 1e-4 scale (measured in
        # tests/test_bf16.py): such a run would burn its whole round budget
        # or "certify" on rounding artifacts
        raise ValueError(
            "gap-targeted runs cannot certify in bfloat16 (the duality "
            "gap is below bf16 resolution — docs/DESIGN.md §6); use "
            "--dtype=float32, or drop --gapTarget for an uncertified "
            "bf16 run"
        )
    # the loop's carry beyond (w, α): --accel the window bank and the
    # schedule leaf, a σ′ schedule or warm start the schedule leaf
    scheduled = ((sigma_levels is not None and len(sigma_levels) > 1)
                 or warm_start is not None)
    arm = "accel" if accel else "sched" if scheduled else "plain"
    classes = ds.num_classes
    if classes > 1:
        _check_class_job(ds, alg, arm, debug, test_ds,
                         eval_fn is not None or eval_kernel is not None)
    counted = (_sanitize.launches_total, _sanitize.intended_fetches_total)

    def resolve(early=False):
        # (in a first job the kernels' modules are imported here, Pallas
        # with them: a second that gets a cold span of its own.  A job that
        # resolves ahead of its start program has opened no ``first_job``
        # yet: where that import is still to pay it opens the span itself)
        with (_tracing.cold_span if early and _KERNELS not in sys.modules
              else _tracing.first_job_span)("resolve_path"):
            return resolve_solver_path(
                ds, params.local_iters, mesh, math=math, pallas=pallas,
                block_size=block_size, block_chain=block_chain,
                block_sparse_gram=block_sparse_gram, loss=params.loss,
            ).for_mode(alg[0], params.smoothing)

    # the leaves of T class models over dense rows take their shape from
    # the resolved path (a class a sublane, or the classes on the lanes),
    # so that job resolves first; every other job's start program goes out
    # ahead of the host's walk to the loop's dispatch, as before
    path = (resolve(early=True) if classes > 1 and ds.layout == "dense"
            else None)
    # init_state: one start program when the job starts from nothing —
    # dispatched here, so the device fills the leaves meanwhile
    # (_start_state)
    with _tracing.span("init_state"):
        state0 = _start_state(
            ds, dtype, mesh, arm, alg[0] == "prox", start_round, w_init,
            alpha_init, hist_init, sched_init,
            lanes=classes > 1 and (ds.layout == "sparse"
                                   or path.class_axis == "lanes"))
    if path is None:
        path = resolve()
    pallas, block_chain = path.pallas, path.block_chain
    if not quiet:
        print(f"local solver: {path.describe()}; the shared vector is "
              f"{ds.num_features} long")
    parts_kw = dict(
        classes=classes, math=math, pallas=pallas,
        pallas_interpret=path.pallas and path.interpret,
        pallas_state=path.state,
        hbm_plan=(_hbm_plan(ds, params.local_iters) if path.local_ids
                  else path.plan),
        block=block_size, block_chain=block_chain,
        block_sparse_gram=block_sparse_gram,
        # permuted sampling with n_local % H == 0 keeps every round inside
        # one epoch's permutation, so the round's H draws are pairwise
        # distinct per shard — the license for the block kernel's
        # one-scatter-per-round α update (local_sdca_block_batched)
        block_distinct=(
            block_size > 0
            and rng == "permuted"
            and bool(np.all(np.asarray(ds.counts) % params.local_iters == 0))
        ),
    )
    sampler = base.IndexSampler(rng, debug.seed, params.local_iters, ds.counts)
    sampler.device = base.resolve_sampling(sampling, sampler,
                                           params.num_rounds)
    shard_arrays = _kernel_arrays(ds, path, block_size)
    if path.class_axis == "lanes":
        # the certificate on the lanes form needs T stated: the state is
        # T_pad wide (objectives.eval_metrics ``classes``)
        def eval_kernel(state, shard_arrays, test_arrays):
            return objectives.eval_metrics(
                state[0], state[1], shard_arrays, params.lam, params.n,
                test_shard_arrays=test_arrays,
                test_n=test_ds.n if test_ds is not None else 0,
                loss=params.loss, smoothing=params.smoothing,
                classes=classes)

    if eval_fn is None:
        def eval_fn(state):
            # (a one-vs-rest job: a fourth element, every class's gap)
            # state[0:2]: the duality-gap certificate reads only (w, α) and
            # is exact under any σ′/loss stage (which is the backoff's
            # soundness argument)
            return objectives.evaluate(
                ds, state[0], state[1], params.lam, test_ds=test_ds,
                loss=params.loss, smoothing=params.smoothing)

    description = _loop_description(
        params, debug, alg, path, block_size, gap_target, sigma_levels,
        warm_start, accel, theta)
    # the per-round program (under the chunked driver at chunk = 1) carries
    # (w, α) across a vmap or a plain fanout shard_map: no schedule leaf, no
    # class axis, no Pallas kernel (those own the shard axis themselves)
    per_round = not (device_loop or scan_chunk > 0 or arm != "plain"
                     or classes > 1 or pallas or block_chain != "xla")
    chunk_kernel = sched_token = None
    if per_round:
        step = make_round_step(mesh, params, k, alg, **parts_kw)

        def chunk_fn(t0, c, state):
            return step(*state, sampler.round_indices(t0), shard_arrays)
    else:
        chunk_kernel, chunk_step, sched_token = build_sdca_loop(
            mesh, params, k, alg, sampler, parts_kw, **description)

        def chunk_fn(t0, c, state):
            return chunk_step(*state, sampler.chunk_indices(t0, c),
                              shard_arrays)

    cache_key = (
        "sdca", alg_name, alg, math, pallas, block_size, block_chain,
        block_sparse_gram, sched_token,
        sampler.cache_token(), k, mesh,
        params.lam, params.n, params.local_iters, params.beta,
        params.gamma, params.loss, params.smoothing,
        params.num_rounds, debug.debug_iter, start_round,
        gap_target, ds.layout, str(dtype), classes,
        # (T class models over dense rows: a class a sublane or the classes
        # on the lanes is another state and another loop; sparse rows as a
        # rectangle or as a stream another chain on another plan)
        path.class_axis, path.storage,
    )
    state, traj = base.drive_device_paths(
        alg_name, params, debug, state0, chunk_kernel, chunk_fn,
        eval_fn, sampler, shard_arrays, alpha_in_state=True, mesh=mesh,
        test_ds=test_ds, quiet=quiet, gap_target=gap_target,
        start_round=start_round, scan_chunk=max(scan_chunk, 1),
        device_loop=device_loop, cache_key=cache_key,
        eval_kernel=eval_kernel, eval_arrays=eval_arrays,
        divergence_guard=guard_on,
        sigma_levels=description["levels"] if arm != "plain" else None,
        accel=description["theta_hs"] if accel else None,
        overlap_io=overlap_io, ckpt_rows=ckpt_rows,
    )
    # beside the path: what the drive ladder issued for this job and how
    # often it read the device, counted on the host (analysis/sanitize.py);
    # of a first job, its cold branches' seconds and bytes (else empty)
    launches = _sanitize.launches_total - counted[0]
    fetches = _sanitize.intended_fetches_total - counted[1]
    cold = _tracing.finish_job()
    traj.meta.update(solver_path=path.as_dict(),
                     vector_len=int(ds.num_features),
                     launches=launches, fetches=fetches, cold=cold)
    if not quiet:
        print(f"drive ladder: {launches} programs launched, "
              f"{fetches} host fetches")
        if cold:
            print(f"cold path: {_tracing.cold_line(cold)}")
        for build in _tracing.stray_builds():
            print(_tracing.stray_line(build))
    return state[0], state[1], traj


def run_cocoa(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    plus: bool,
    sigma_schedule: Optional[str] = None,
    warm_start=None,
    accel: Optional[str] = None,
    theta: Optional[str] = None,
    **kw,
):
    """CoCoA (plus=False, averaging, scaling β/K) / CoCoA+ (plus=True,
    additive, scaling γ with σ′ = K·γ) — CoCoA.scala:22-66.  Train; returns
    (w, alpha, Trajectory).  See :func:`run_sdca_family` for the keyword
    options (mesh, rng, gap_target, scan_chunk, math, pallas, device_loop,
    checkpoint/resume).

    **One-vs-rest.**  A dataset that states T > 1 classes
    (``ds.num_classes``, class ids in ``ds.classes``: a multi-class file
    loaded as one) trains T models in this one job, class t against the
    rest, over the ONE copy of the rows: returns (w (T, d), alpha
    (T, K, n_shard), Trajectory).  Every class takes the job's one sampler's
    rows, so lane t is the run of class t against the rest under that
    sampler (what a solo job on those labels gives, to rounding: the dense
    Pallas kernel reduces x . (w_t + sigma' dw_t) once where the T = 1
    kernel reduces x . w and x . dw apart).  The job stops at the first
    evaluation at which EVERY class's gap is at or under the target; no
    lane is frozen before that; the budget and the divergence watch read
    the worst class.  A record's ``gap`` is the worst class's, ``primal``
    that class's, ``class_gaps`` all of them.  The branch is on what the
    dataset declares; at T = 1 nothing of it runs.  Where the T models
    outgrow the dense class kernel's state tiles (ops/pallas_sdca.classes_fit:
    T = 1,000 at any size) the job keeps them on the lanes, as on sparse
    rows — returns (W (d, R, 128), alpha (K, n_shard, R, 128), Trajectory)
    — and solves a block of rows a step (ops/block_lanes.py): chosen from
    the shapes, never by a flag.  Sparse rows kept as a stream
    (data/sharding.stream_suits: long, uneven rows) carry the class axis on
    the lanes too, their chain walking the stream itself
    (ops/pallas_longrows_lanes.py).  Not carried yet, and refused by name:
    the hybrid layout, a mesh, ``--accel``, the sigma' schedule and warm
    start, a state handed in, checkpoints, ``--blockSize`` (the T = 1 block
    kernels' tile).

    ``params.sigma="auto"`` (flag ``--sigma=auto``) exploits the measured
    σ′ trade-off (the aggressive σ′ = K·γ/2 HALVES
    the certified comm-rounds on randomly partitioned data, while σ′
    pushed below the data's coherence diverges) in one of two ways,
    selected by ``sigma_schedule`` (flag ``--sigmaSchedule``):

    - ``"anneal"`` (the default): a DEVICE-RESIDENT schedule — start at
      K·γ/2 and, when the stall watch fires, back σ′ off multiplicatively
      toward the safe K·γ *inside* the driver loop, continuing from the
      current iterate (sound: the primal-dual correspondence and the α
      box are σ′-independent, so the exact gap certificate survives the
      switch).  A wrong guess costs one stall window, never a restart.
    - ``"trial"`` (the A/B control — the pre-schedule behavior, bit-exact):
      run a guarded trial at K·γ/2 and, if the divergence guard fires,
      RESTART from scratch at the safe K·γ.

    ``sigma_schedule="anneal"`` with an explicit ``--sigma=<float>`` below
    the safe bound anneals from that σ′ instead (the deliberately
    divergence-prone configs in the tests start there).

    ``warm_start=(s, rounds)`` (flag ``--warmStart=<s>,<rounds>``): run a
    smooth_hinge(s) phase for the first ``rounds`` rounds (rounded up to
    the ``debugIter`` cadence), handing off to hinge inside the same
    device loop — the measured-but-manual "warm smooth_hinge"
    procedure as a flag.  Requires ``--loss=hinge``; the handoff is exact
    because the smooth-hinge dual keeps α in the hinge dual's [0,1] box,
    and the reported gap is the hinge certificate throughout.

    ``accel`` ("auto" | "on" | "off", flag ``--accel``): the accelerated
    outer loop — a secant (Anderson-1) extrapolation of the dual at
    eval-window boundaries, with a gap-monitored restart (see
    :func:`run_sdca_family`).  ``auto`` enables it for gap-targeted
    CoCoA+ runs (the regime the round-count win is measured in);
    ``off`` (the library default) is bit-identical to the
    pre-acceleration code.  ``theta`` ("fixed" | "adaptive", flag
    ``--theta``): the adaptive local-accuracy ladder — early rounds run
    far fewer inner SDCA steps, resolved on device from the current gap
    estimate; requires an accelerated gap-targeted run.  Not available
    with ``--sigmaSchedule=trial`` (the trial is the bit-exact
    pre-schedule A/B control and stays untouched)."""
    import dataclasses as _dc

    if sigma_schedule not in (None, "trial", "anneal"):
        raise ValueError(f"sigma schedule must be trial|anneal, got "
                         f"{sigma_schedule!r}")
    accel = "off" if accel is None else str(accel).lower()
    if accel not in ("auto", "on", "off"):
        raise ValueError(f"accel must be auto|on|off, got {accel!r}")
    theta = "fixed" if theta is None else str(theta).lower()
    if theta not in ("fixed", "adaptive"):
        raise ValueError(f"theta must be fixed|adaptive, got {theta!r}")
    if sigma_schedule == "trial":
        # the trial path is preserved bit-exact as the pre-schedule A/B
        # control — acceleration on top would change what it controls for
        if accel == "on":
            raise ValueError(
                "--accel cannot ride --sigmaSchedule=trial (the trial is "
                "the bit-exact A/B control); use --sigmaSchedule=anneal")
        accel = "off"
    # resolve auto HERE (before the sigma=auto recursion, whose inner
    # calls see sigma already replaced): on for gap-targeted CoCoA+ runs
    # — the regime where momentum's round-count win is measured and the
    # restart rule has a gap to monitor
    accel_on = (accel == "on"
                or (accel == "auto" and plus
                    and kw.get("gap_target") is not None))
    if accel_on and ds.num_classes > 1:
        # ``auto`` means at T classes what it means at one: said here, by
        # name, instead of resolving to something else
        raise ValueError(
            f"--accel={accel} resolves ON for this job, and its secant "
            f"bank and jump hold one model's alpha: a one-vs-rest job over "
            f"{ds.num_classes} classes runs the plain outer loop; pass "
            f"--accel=off")
    if theta == "adaptive" and not accel_on:
        if accel == "off":
            raise ValueError(
                "--theta=adaptive requires an accelerated run: pass "
                "--accel=on, or --accel=auto with --gapTarget on CoCoA+")
        # accel=auto resolved OFF for this run (plain-CoCoA leg of the
        # CLI's run_all, or no gap target): Θ is an accelerated-run
        # knob, so it degrades to the full-H schedule instead of
        # rejecting a run the caller never asked to accelerate
        theta = "fixed"
    accel_kw = dict(accel="on" if accel_on else "off", theta=theta)
    if warm_start is not None:
        s_w, r_w = warm_start
        if params.loss != "hinge":
            raise ValueError(
                "--warmStart hands a smooth_hinge phase off to hinge and "
                "requires --loss=hinge")
        if not float(s_w) > 0:
            raise ValueError(
                f"--warmStart smoothing must be > 0, got {s_w}")
        if int(r_w) < 1:
            raise ValueError(
                f"--warmStart rounds must be >= 1, got {r_w}")
        if debug.debug_iter <= 0:
            raise ValueError(
                "--warmStart requires --debugIter > 0 (the in-loop "
                "handoff lands on the eval-cadence chunk boundary)")
        r_al = -(-int(r_w) // debug.debug_iter) * debug.debug_iter
        if r_al != int(r_w) and not kw.get("quiet", False):
            print(f"warmStart: handoff rounded up to round {r_al} "
                  f"(the debugIter={debug.debug_iter} cadence the device "
                  f"loop chunks on)")
        warm_start = (float(s_w), r_al)

    safe = ds.k * params.gamma
    if params.sigma == "auto":
        if not plus:
            # σ′ only enters the plus-mode subproblem (CoCoA.scala:158-160);
            # plain CoCoA ignores it, so auto degenerates to the default —
            # important because the reference driver runs BOTH algorithms
            # from one flag set (hingeDriver.scala:84-89)
            return run_cocoa(ds, _dc.replace(params, sigma=None), debug,
                             plus, warm_start=warm_start, **accel_kw, **kw)
        if (sigma_schedule or "anneal") == "anneal":
            return _run_cocoa_anneal(
                ds, params, debug, plus,
                base.anneal_levels(safe / 2.0, safe), warm_start, accel_kw,
                kw)
        if kw.get("gap_target") is None:
            # the divergence guard rides the gap-target early-stop path; a
            # fixed-round auto run could burn its whole budget diverged
            # and never trigger the fallback
            raise ValueError("--sigma=auto requires --gapTarget (the "
                             "σ′ fallback triggers on the divergence "
                             "guard, which runs on the gap-target path)")
        if kw.get("divergence_guard", "auto") == "off":
            # the trial's only exit from a bad guess IS the guard
            raise ValueError("--sigma=auto requires the divergence guard "
                             "(drop --divergenceGuard=off)")
        quiet = kw.get("quiet", False)
        if kw.get("w_init") is not None or kw.get("start_round", 1) > 1:
            # a RESUMED run must not re-experiment: the restored state may
            # be mid-trial (possibly diverging), and a trial verdict from
            # it is meaningless.  Continue with the safe σ′ — any (w, α)
            # is a valid primal-dual pair, so the safe run converges from
            # the restored state and the certificate stays exact.
            if not quiet:
                print("sigma=auto: resumed run continues with the safe "
                      f"σ′=K·γ={ds.k * params.gamma:g} (no re-trial from "
                      "restored state)")
            return run_cocoa(ds, _dc.replace(params, sigma=None), debug,
                             plus, warm_start=warm_start, **accel_kw, **kw)
        import os as _os

        ckpt_dir = debug.chkpt_dir if debug.chkpt_iter > 0 else ""
        before = (set(_os.listdir(ckpt_dir))
                  if ckpt_dir and _os.path.isdir(ckpt_dir) else set())
        trial = _dc.replace(params, sigma=ds.k * params.gamma / 2.0)
        w, alpha, traj = run_cocoa(ds, trial, debug, plus,
                                   warm_start=warm_start, **kw)
        if traj.stopped != "diverged":
            return w, alpha, traj
        if ckpt_dir and _os.path.isdir(ckpt_dir):
            # the diverged trial's checkpoints must not survive: the safe
            # rerun restarts from round 1, and a later --resume would
            # otherwise pick the trial's (higher-round, diverged) state.
            # Deletion is scoped to THIS run's files only — the exact
            # algorithm prefix the trial's checkpoint writer used and the
            # round range it actually reached — so a concurrent CoCoA /
            # CoCoA+ run sharing the directory (elastic workers, parallel
            # sweeps) can never lose its checkpoints to our cleanup
            # (ADVICE r5: the bare 'CoCoA' prefix matched them all).
            import re as _re

            algo = ("CoCoA+" if plus else "CoCoA").replace(" ", "_")
            last = traj.records[-1].round if traj.records else 0
            stamp = _re.compile(
                _re.escape(algo) + r"-r(\d+)\.(npz|npz\.json|json)$")
            for f in sorted(set(_os.listdir(ckpt_dir)) - before):
                m = stamp.match(f)
                if m and int(m.group(1)) <= last:
                    _os.remove(_os.path.join(ckpt_dir, f))
        from cocoa_tpu.telemetry import events as _tele

        _tele.get_bus().emit(
            "restart", reason="sigma_trial_diverged",
            algorithm="CoCoA+" if plus else "CoCoA",
            sigma_trial=trial.sigma, sigma_safe=ds.k * params.gamma,
            round=traj.records[-1].round if traj.records else 0)
        if not quiet:
            print(f"sigma=auto: σ′=K·γ/2={trial.sigma:g} diverged; "
                  f"restarting with the safe σ′=K·γ={ds.k * params.gamma:g}")
        safe_params = _dc.replace(params, sigma=None)
        # from SCRATCH: strip any resume state so the safe run cannot
        # inherit the diverged trial's iterates (belt to the resumed-run
        # guard's suspenders above)
        safe_kw = {k2: v for k2, v in kw.items()
                   if k2 not in ("w_init", "alpha_init", "start_round",
                                 "sched_init", "hist_init")}
        return run_cocoa(ds, safe_params, debug, plus,
                         warm_start=warm_start, **accel_kw, **safe_kw)

    if sigma_schedule == "trial":
        raise ValueError(
            "sigma schedule 'trial' is the --sigma=auto A/B control; it "
            "needs --sigma=auto")
    if (sigma_schedule == "anneal" and plus and params.sigma is not None
            and float(params.sigma) < safe):
        # anneal from an explicit aggressive σ′ (the divergence-prone
        # configs the schedule exists to rescue start here)
        return _run_cocoa_anneal(
            ds, params, debug, plus,
            base.anneal_levels(float(params.sigma), safe), warm_start,
            accel_kw, kw)

    alg = _alg_config(params, ds.k, plus)
    return run_sdca_family(
        ds, params, debug, "CoCoA+" if plus else "CoCoA", alg,
        warm_start=warm_start, accel=accel_on, theta=theta, **kw
    )


# --- bounded-staleness CoCoA+ aggregation (--staleRounds, round 17) ---------
#
# The bulk-synchronous round pays the slowest worker's wall-clock at
# every barrier.  Bounded staleness relaxes the barrier, not the math:
# a worker may start round t+1 with peer contributions for rounds
# (t-S, t] still outstanding, as long as every round-r contribution is
# APPLIED before round r+S+1's local solve begins (the join window).
#
# Safety (the adding-vs-averaging analysis, Ma et al. arXiv:1502.03508):
# every local subproblem is solved against σ′ = K·γ — the bound that
# makes SIMULTANEOUS additive aggregation of all K contributions safe.
# Applying a SUBSET of m ≤ K contributions with the same γ is strictly
# inside that safety region (the subset's mutual interference is
# bounded by m/K of what σ′ already covers), and a late contribution
# joining alone later is the m = 1 case.  The scale must be the SAME γ
# for every contribution regardless of when it joins: the owner already
# advanced its α by γ·Δα at solve time, so any other Δw scale would
# break the primal-dual correspondence w = (1/λn)·Σ y·α·x that the
# exact duality-gap certificate rests on (:func:`partial_gamma` is
# where that argument lives).  The trajectory changes — a late joiner's
# peers ran a few rounds on a w missing its Δw — but the certificate
# does not: the gap is evaluated on the ACTUAL (w, α) at a drained
# boundary, where every contribution has landed and w = w(α) holds
# exactly again (the general-CoCoA inexactness argument,
# arXiv:1611.02189 — the certificate never assumed a particular
# trajectory).
#
# Determinism: the join window is ROUND-indexed, never arrival-indexed.
# Which contribution joins at which round is a pure function of round
# numbers (round r joins at round r+S), so the trajectory is
# bit-reproducible run to run and the asynchrony moves the WAITING off
# the critical path, not the data.  Whoever arrives early is simply
# already in the collector's buffer when its join round comes due.
#
# Docs: docs/DESIGN.md §15 "Asynchrony model".


def partial_gamma(gamma: float, k: int, m: int) -> float:
    """The safe aggregation scale for applying ``m`` of ``k`` CoCoA+
    contributions whose local subproblems were solved against
    σ′ = K·γ.

    Returns γ unchanged — deliberately.  σ′ ≥ γ·m holds for every
    m ≤ K, so the subset application is safe at γ (the adding analysis
    bounds the interference of ν simultaneous updates by σ′ ≥ γ·ν, and
    a subset has less interference than the full gang σ′ was sized
    for).  An UP-scaled subset (γ·K/m — also admissible by the bound)
    is rejected by design: the owner applied α += γ·Δα at solve time
    without knowing which peers would make the same on-time subset, so
    any size-dependent Δw scale would need a gang-wide agreement
    protocol to keep w = w(α) — and a disagreement breaks the exact
    certificate, the one thing this mode must never do."""
    if not 1 <= m <= k:
        raise ValueError(f"partial aggregation needs 1 <= m <= K, got "
                         f"m={m}, K={k}")
    return float(gamma)


class StaleJoinWindow:
    """Bounded-staleness join-window bookkeeping for a host-exchange
    gang round (the policy half of ``--staleRounds``; the transport is
    parallel/distributed.py's :class:`ExchangeHandle`).

    Per round ``t`` the caller posts its contribution, wraps the
    exchange in a handle, and calls :meth:`admit` followed by
    :meth:`join_due` — which joins exactly the rounds whose window
    expires at ``t`` (round r at t = r + S) and returns their payloads
    for application.  :meth:`drain` force-joins everything pending (the
    eval/checkpoint boundaries — the points where w = w(α) must hold
    exactly for the certificate and for a resumable checkpoint).
    ``stale_rounds=0`` degenerates to today's synchronous barrier:
    round t joins at round t.

    **Gap-rise collapse** (:meth:`on_eval`): a gap rise at an eval
    boundary collapses the window to synchronous (S = 0) until a later
    eval improves again — the ``momentum_restart`` pattern: damage from
    staleness-hurt progress is bounded to one eval cadence, and the
    collapse discards the permission for further stale joins rather
    than any applied contribution (an applied Δw can never be unwound
    without breaking w = w(α)).

    **Elastic interaction** (:meth:`abort`): a gang teardown or resize
    drops pending handles without joining them — the collector daemons
    die with the process, the bounded KV budget caps any straggling
    get, and the next generation resumes from a DRAINED checkpoint, so
    no half-joined round can ever leak across generations.

    Emits one typed ``stale_join`` event per late-joined round
    (``rounds_late >= 1``); synchronous joins are not events.
    """

    def __init__(self, stale_rounds: int, algorithm: str = "CoCoA+"):
        s = int(stale_rounds)
        if s < 0:
            raise ValueError(f"staleRounds must be >= 0, got {stale_rounds}")
        self.stale_rounds = s
        self.algorithm = algorithm
        self.collapsed = False   # gap-rise: window forced to 0
        self._last_gap = None
        self._pending: dict = {}   # round -> ExchangeHandle | payload list

    def effective_window(self) -> int:
        return 0 if self.collapsed else self.stale_rounds

    def pending_rounds(self) -> list:
        return sorted(self._pending)

    def admit(self, t: int, handle) -> None:
        """Register round ``t``'s in-flight exchange (an ExchangeHandle,
        or an already-collected payload list on the synchronous path)."""
        if t in self._pending:
            raise ValueError(f"round {t} already has a pending exchange")
        self._pending[t] = handle

    def join_due(self, t: int) -> list:
        """Join every round whose window expires by round ``t`` (rounds
        r <= t - S).  Returns ``[(round, payloads, rounds_late), ...]``
        in round order; ``rounds_late = t - r`` is bounded by the
        CONFIGURED window (never admits later than S — pinned)."""
        cut = t - self.effective_window()
        return self._join([r for r in sorted(self._pending) if r <= cut], t)

    def drain(self, t: int) -> list:
        """Force-join everything pending (eval/checkpoint boundary): the
        returned contributions must be applied before the gap is
        evaluated, restoring exact w = w(α)."""
        return self._join(sorted(self._pending), t)

    def abort(self) -> None:
        """Drop pending handles without joining (teardown/resize): the
        daemon collectors die with the process; nothing is applied."""
        self._pending.clear()

    def _join(self, rounds: list, t: int) -> list:
        from cocoa_tpu.telemetry import events as _tele

        out = []
        for r in rounds:
            h = self._pending.pop(r)
            payloads = h.join() if hasattr(h, "join") else h
            late = max(0, t - r)
            if late > self.stale_rounds:
                # the user-facing bound (and what keeps the
                # rounds_late metrics label set finite) — a caller that
                # skipped join_due for some round must fail loudly, not
                # silently apply an arbitrarily stale contribution
                raise RuntimeError(
                    f"round {r} would join {late} rounds late — past "
                    f"the --staleRounds={self.stale_rounds} window; a "
                    f"caller skipped join_due for it")
            if late >= 1:
                _tele.get_bus().emit(
                    "stale_join", algorithm=self.algorithm, t=int(t),
                    round=int(r), rounds_late=int(late),
                    workers=len(payloads) if payloads is not None else None)
            out.append((r, payloads, late))
        return out

    def on_eval(self, gap) -> bool:
        """The gap-rise rule at an eval boundary (call AFTER
        :meth:`drain` + gap evaluation): a rise collapses the window to
        synchronous until an improving eval restores it.  Returns True
        when this eval changed the collapse state."""
        if gap is None:
            return False
        g = float(gap)
        prev = self._last_gap
        self._last_gap = g
        if prev is None:
            return False
        if g > prev and not self.collapsed:
            self.collapsed = True
            return True
        if g <= prev and self.collapsed:
            self.collapsed = False
            return True
        return False


def _run_cocoa_anneal(ds, params, debug, plus, levels, warm_start,
                      accel_kw, kw):
    """The scheduled (device-resident) σ′ anneal entry: validate, resolve
    resume, and hand the static ladder to :func:`run_sdca_family`."""
    import dataclasses as _dc

    quiet = kw.get("quiet", False)
    if kw.get("gap_target") is None:
        raise ValueError(
            "the σ′ anneal schedule requires --gapTarget (the backoff "
            "triggers on the stall watch, which runs on the gap-target "
            "path)")
    if kw.get("divergence_guard", "auto") == "off":
        raise ValueError(
            "the σ′ anneal schedule IS the divergence guard's backoff "
            "action; drop --divergenceGuard=off")
    resumed = kw.get("w_init") is not None or kw.get("start_round", 1) > 1
    if resumed and kw.get("sched_init") is None:
        # resumed without schedule state (a pre-schedule checkpoint, or a
        # bare w_init): the restored iterate may sit mid-stage at an
        # unknown σ′ — continue with the safe bound, exactly like the
        # trial path's resumed-run rule (any (w, α) is a valid primal-dual
        # pair under any σ′, so the certificate stays exact)
        if not quiet:
            print("sigma anneal: resumed run has no schedule state; "
                  f"continuing with the safe σ′=K·γ={ds.k * params.gamma:g}")
        return run_cocoa(ds, _dc.replace(params, sigma=None), debug, plus,
                         warm_start=warm_start, **accel_kw, **kw)
    p = _dc.replace(params, sigma=levels[0])
    alg = _alg_config(p, ds.k, plus)
    return run_sdca_family(
        ds, p, debug, "CoCoA+" if plus else "CoCoA", alg,
        sigma_levels=levels, warm_start=warm_start,
        accel=accel_kw["accel"] == "on", theta=accel_kw["theta"], **kw
    )
