"""Sequential sparse SDCA round whose state stays in HBM (padded-CSR).

``ops/pallas_sparse.py`` keeps, per shard, w | Δw lane-blocked over all d
columns and the per-row scalars over all n_shard rows in VMEM.  At d =
3·10⁷ (kddb) that is 6 GB against a 12 MB budget.  This module runs the
same chain — for the sampled row i of shard k: margin = x_i·w +
σ′·x_i·Δw_k, the α step of ``losses.alpha_step``, Δw_k += coef·x_i
(CoCoA.scala:148-188; ``local_sdca_fast``) — with w, α and the rows left
in HBM, and only what a **segment** of one shard's steps touches on the
chip.  Shards run one after another (one shard's working set is an eighth
of eight, and interleaving K chains bought nothing measurable while the
scalar core's address arithmetic bound a step; what a step costs now is
under "The chain" below); a shard's round is cut into T segments of S
steps where its touched columns outgrow VMEM.

What the v5e measured for the pieces decides the design (PERF.md §6,
PR 26): an XLA gather or scatter of single elements costs 11-24 ns an
element whatever the table, a sort 2.6 ns an element, a dense pass 0.005.
So nothing here gathers per nonzero except the row fetch, and that has a
kernel of its own where the device stores the rows with the row index on
the lanes:

- **Row fetch** (scope ``cocoa_sparse_gather``).  A TPU keeps
  (K, n_shard, W) with the rows on the lanes wherever W pads worse under
  the (8, 128) tile than n_shard does (W = 64: 2x), and a gather of whole
  rows then makes layout assignment copy the whole dataset row-major
  first: 2 x 9.2 GB at kddb, which cannot compile.  ``_fetch_rows`` reads
  the arrays as they are stored: per sampled row one DMA of the (W, 128)
  tile column that holds it, the row's lane picked out on the VPU, the
  result a (W, S) table with the steps on the lanes.  Where the rows are
  stored row-major a plain gather of rows is the cheap form.
- **Local ids by sorting, not by table** (same scope).  Every column the
  segment touches gets a local id in [0, M), M = min(d, S·W) lanes: the
  id is the column where M = d, else the column's rank among the
  segment's distinct columns — one sort of the (column, slot) pairs by
  column, a running count of the boundaries, one sort back by slot.  w and
  the shard's carried Δw are then gathered **once per distinct column**
  (in chunks, as many as the segment has columns: a tenth of its
  nonzeros at kddb), not once per nonzero, into the kernel's compact
  [w | Δw] operand; the Δw the kernel returns is scattered back the same
  way.
- **The chain** (``pallas_sparse_hbm_round``, scope
  ``cocoa_local_solve``): one ``pallas_call`` a segment.  [w | Δw] lives
  lane-blocked and lane-concatenated over the M local ids in VMEM scratch
  (one dynamic sublane read serves both picks, as in ``pallas_sparse``);
  the per-step tables — local ids, values, and the step's scalars (nnz,
  y, σ′‖x‖², α) — stream through SMEM in blocks of ``CHUNK`` steps
  (addresses must be scalars), so a segment is not bound by what SMEM
  holds: S follows from the VMEM budget alone.  Δw is written by masked
  single-lane stores, so a row's W updates do not wait on one another.
  **No floating-point value of a step is 0-d** (PR 46, as the dense
  kernel's ``pallas_sdca._advance``): y, σ′‖x‖² and α leave SMEM as splats
  to (1, 1) — scalar to vector, the cheap direction — the margin's total
  and a repeated row's α are reduces that keep their axes,
  ``losses.alpha_step`` runs elementwise on (1, 1) vectors under every
  loss, and coef broadcasts into the scatter's multiply-add; the scalar
  core keeps the integers (nnz, prev, the local ids and their row and
  lane, the trip count) and hands each nonzero's value over as a splat.
  One chain runs at a time, so a value that went to the scalar core and
  came back was paid in full: measured on the v5e, the kernel alone
  (PERF.md §6, PR 46), four trips (the α pick, the margin's total, two
  divides) were ~210 ns of a hinge step, logistic's ten Newton iterations
  1,792 → 429 ns.
  **The walk** (PR 47).  What is left of a step is its two passes over
  the row's slots, the margin's and the scatter's.  The tables are as wide
  as the rectangle's own 8-slot groups (``plan.w_r``, ``ops/rows
  .SLOT_GROUP``: 40 for criteo's rows of 39, 64 while it was whole 32-slot
  groups).  A pass writes its first ``head`` slots out as straight-line
  code of the step, whatever the row's length, and walks what the row has
  past them in loops: ``(cnt - head) // 32`` trips of a 32-slot body, then
  the remainder in trips of ``TAIL_GROUP`` = 8 slots.  ``head`` follows
  from what the plan observed, never from a flag: all ``w_r`` slots where
  the loader saw rows of one length (``HbmPlan.unrolled``, from
  ``data/sharding.rows_of_one_length``: no dynamic trip at all), else the
  first 32.  The scatter's mask ``slot < cnt`` covers the written-out
  slots too, so a short row or a padded step (``cnt`` -1) adds
  ``row × 0.0`` to its margin and stores nothing: (Δw, α) are the bits
  they were under every walk.  Why a head: measured on the v5e (PERF.md
  §6, PR 47; the kernel alone, hinge, ns a step), inside a loop's body or a
  branch a slot of both passes costs 12.5 ns, a dynamic trip 7-10 ns and
  what is around the two passes ~115 ns; the same slots as straight-line
  code of the step cost ~250 ns a step less, because nothing crosses a
  region's edge: the scatter's table reads and row loads cannot start
  under the α step's latency, nor the margin's under the α pick's.
  criteo's shape: 942 walking 64 slots in two 32-slot trips a pass (the
  parent), 643 at 32 + 8 in loops, 444 with 32 written out and a trip of
  8, **366 with all 40 written out** (633 with the 40 behind one branch);
  kddb's lengths (mean 29.4, to 64): 675 in whole 32-slot groups, 575 in
  loops of 32 then 8 (32.8 slots a step), 524 / 451 / 424 / **420** with
  the first 8 / 16 / 24 / 32 written out ahead of those loops (37.7 slots
  a step at 32), 649 with all 64 written out: so ragged rows get a head of
  32 and only rows of one length the whole width.  The written-out
  scatter reads 32 slots' rows before it stores them, as a trip's body
  does (8 at a time: 455 for 366; 16: 420; all 40: 368).

A row sampled twice in one segment (any ``rng`` but ``permuted``; there, a
segment that crosses an epoch) reads its α from the earlier step's output:
the steps are linked by a sort of the segment's S row indices.

Sizing: ``hbm_plan`` (steps per segment from ``HBM_VMEM_BUDGET``, which
the kernel asks Mosaic for through ``vmem_limit_bytes``; a v5e core has
128 MiB of VMEM, 16 MiB is only the default scoped limit).  The step math
is ``losses.alpha_step``, ``mode_factors`` and ``coef_divisor``, as in
every other path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.ops.pallas_sdca import (LANES, check_dtype, lane_pick,
                                       tile_total)
from cocoa_tpu.ops.pallas_sparse import GROUP, row_lengths
from cocoa_tpu.ops.rows import SLOT_GROUP, spread_index, spread_table
from cocoa_tpu.telemetry.tracing import (SCOPE_LOCAL_SOLVE,
                                         SCOPE_SPARSE_GATHER)

CHUNK = 32                       # steps per SMEM block
TAIL_GROUP = 8                   # slots a trip past a row's whole GROUPs
FETCH_ROWS = 8                   # rows fetched per grid step of _fetch_rows
FETCH_BLOCK = 1 << 15            # rows per call of it (their indices: SMEM)
COLUMN_CHUNK = 1 << 17           # distinct columns gathered per loop trip
HBM_VMEM_BUDGET = 88 << 20       # [w | Δw] scratch + the step outputs
HBM_VMEM_LIMIT = 100 << 20       # what the kernels ask Mosaic for
HBM_SMEM_BUDGET = 768 << 10      # the two double-buffered step tables (of
                                 # 1 MiB: 292 KB compiles, 1,026 KB does not)
N_INT, N_FLT = 2, 3              # per-step scalars: (nnz, prev), (y, q, α)
_NONE = 2 ** 31 - 1              # sorts after every column


@dataclasses.dataclass(frozen=True)
class HbmPlan:
    """How one shard's round is cut: ``t`` segments of ``s`` steps (``s`` a
    multiple of ``chunk``), ``m`` local ids (a multiple of 1024 and of the
    column chunk), ``w_r`` the slots a step takes in the tables (whole
    ``SLOT_GROUP``s, the rectangle's own), ``direct``: the local id is the
    column (M = d_pad), ``unrolled``: a step's two passes are all ``w_r``
    slots written out, no dynamic trip (the loader saw rows of one length:
    nothing of that walk is padding but the rectangle's own); otherwise
    the first GROUP are, and loops walk what a row has past them
    (:func:`walk_head`).  ``t_pad`` > 0: a plan of the chain with a class
    axis on the lanes, T_pad models wide, which
    ops/pallas_sparse_lanes.lanes_plan makes (``hbm_plan`` never does)."""
    t: int
    s: int
    m: int
    w_r: int
    chunk: int
    direct: bool
    unrolled: bool = False
    t_pad: int = 0

    @property
    def column_chunk(self) -> int:
        return min(COLUMN_CHUNK, self.m)


def _w_round(max_nnz: int) -> int:
    return -(-max(1, max_nnz) // SLOT_GROUP) * SLOT_GROUP


def walk_head(w_r: int, unrolled: bool) -> int:
    """Slots of a pass the chain writes out ahead of its loops: all of
    them where the plan is ``unrolled``, else the first GROUP."""
    return w_r if unrolled else min(GROUP, w_r)


def walk_slots(lens, max_nnz: int, unrolled: bool):
    """Slots one pass of the chain's walk covers for rows of ``lens``
    nonzeros in a rectangle ``max_nnz`` wide (a NumPy array, on the host):
    the head, then past it the row's whole GROUPs and its TAIL_GROUPs as
    far as its length reaches."""
    head = walk_head(_w_round(max_nnz), unrolled)
    past = np.maximum(lens - head, 0)
    whole = past // GROUP * GROUP
    return head + whole + -(-(past - whole) // TAIL_GROUP) * TAIL_GROUP


def _table_width(w_r: int) -> int:
    """Words a step takes in each SMEM table: its W slots and its scalars,
    rounded to 16 so that a block of >= 8 steps is whole 128-word lines
    (what the TPU lowering asks of a blocked operand's last dimension)."""
    return -(-(w_r + max(N_INT, N_FLT)) // 16) * 16


def hbm_vmem_estimate(s: int, m: int, itemsize: int) -> int:
    """The chain kernel's VMEM: the (M/128, 2·128) [w | Δw] scratch and the
    (S/128, 128) α-output block, the latter twice (Pallas double-buffers an
    output block)."""
    s_pad = -(-s // LANES) * LANES
    return itemsize * (2 * m + 2 * s_pad)


def hbm_smem_estimate(chunk: int, w_r: int) -> int:
    """The chain kernel's SMEM: two tables (int32, float32), each a
    double-buffered block of ``chunk`` steps."""
    return 2 * 2 * 4 * chunk * _table_width(w_r)


def rows_on_lanes(n_shard: int, max_nnz: int) -> bool:
    """Whether a TPU stores (K, n_shard, W) with the row index on the lanes:
    it takes the dimension order that pads least under the (8, 128) tile
    (tests/test_device_layout.py).  Of the two orders that put W on a tile
    axis; there is a third, K on the sublanes, which pads nothing where K
    is a multiple of 8 and wins wherever W is not: the loader keeps W whole
    sublane tiles (data/sharding.rectangle_width), so a rectangle it built
    never meets it; one forced to another width (``max_nnz=39`` at K = 8)
    does, and the fetch below then reads it through a copy of all of
    it."""
    pad = lambda n, to: -(-n // to) * to / n  # noqa: E731
    return pad(n_shard, LANES) * pad(max_nnz, 8) \
        < pad(max_nnz, LANES) * pad(n_shard, 8)


def hbm_plan(d: int, max_nnz: int, h: int, itemsize: int = 4,
             one_length: bool = False):
    """The plan of one shard's round, or None where not even one block of
    steps fits (the caller keeps the ``fori`` path).  ``one_length``: the
    loader saw every row with the same count of nonzeros
    (data/sharding.rows_of_one_length)."""
    w_r = _w_round(max_nnz)
    if (h + 2 * LANES) * w_r >= (1 << 31):
        return None                     # slot positions are int32
    chunk = CHUNK
    while chunk > 8 and hbm_smem_estimate(chunk, w_r) > HBM_SMEM_BUDGET:
        chunk //= 2
    if hbm_smem_estimate(chunk, w_r) > HBM_SMEM_BUDGET:
        return None
    d_pad = -(-d // 1024) * 1024

    def m_of(s):
        n = s * w_r
        if n >= d_pad:
            return d_pad
        c = min(COLUMN_CHUNK, -(-n // 1024) * 1024)
        return -(-n // c) * c

    def fits(s):
        return hbm_vmem_estimate(s, m_of(s), itemsize) <= HBM_VMEM_BUDGET

    s_all = -(-h // chunk) * chunk
    if fits(s_all):
        t = 1
    else:
        s = (HBM_VMEM_BUDGET // (itemsize * 2 * w_r)) // chunk * chunk
        while s >= chunk and not fits(s):
            s -= chunk
        if s < chunk:
            return None
        t = -(-h // s)
    # even segments: the last is not left with a sliver of steps
    s = -(-(-(-h // t)) // chunk) * chunk
    m = m_of(s)
    return HbmPlan(t=t, s=s, m=m, w_r=w_r, chunk=chunk, direct=m >= d_pad,
                   unrolled=bool(one_length))


def hbm_refusal(d: int, max_nnz: int, h: int, itemsize: int = 4) -> str:
    """Why :func:`hbm_plan` has no plan for these sizes, with the numbers
    (empty where it has one): what the resolver reports beside ``fori``."""
    if hbm_plan(d, max_nnz, h, itemsize) is not None:
        return ""
    w_r = _w_round(max_nnz)
    if (h + 2 * LANES) * w_r >= (1 << 31):
        return (f"H = {h} steps x W = {w_r} slots: a slot's position no "
                f"longer fits an int32")
    smem = hbm_smem_estimate(8, w_r)
    if smem > HBM_SMEM_BUDGET:
        return (f"a row outgrows SMEM: the two step tables of the smallest "
                f"block (8 steps) at W = {w_r} take {smem} B of the "
                f"{HBM_SMEM_BUDGET} B budget (1 MiB of SMEM); rows this long "
                f"are kept as a stream where the loader sees their lengths "
                f"(data/sharding.stream_suits)")
    return (f"no segment of {CHUNK} steps at W = {w_r} fits the "
            f"{HBM_VMEM_BUDGET} B of VMEM asked for [w | dw]")


def sparse_hbm_fits(d: int, max_nnz: int, h: int, itemsize: int) -> bool:
    """The resolver's gate: a plan exists (its segments fit the budgets)."""
    return hbm_plan(d, max_nnz, h, itemsize) is not None


# --- row fetch ---------------------------------------------------------------

def _fetch_kernel(shard_ref, idx_ref, *refs, rows: int):
    """``rows`` sampled rows a grid step: each arrives as the (W, 128) tile
    column that holds it; its lane goes to the step's lane of the output."""
    del shard_ref
    ins, (cols_out, vals_out) = refs[:2 * rows], refs[2 * rows:]
    i = pl.program_id(0)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when((i * rows) % LANES == 0)
    def _init():
        cols_out[...] = jnp.zeros_like(cols_out)
        vals_out[...] = jnp.zeros_like(vals_out)

    for r in range(rows):
        j = i * rows + r
        src, dst = lane == (idx_ref[j] & (LANES - 1)), lane == (
            j & (LANES - 1))
        for ref, out in ((ins[r], cols_out), (ins[rows + r], vals_out)):
            blk = ref[0]                                    # (W, 128)
            col = jnp.sum(jnp.where(src, blk, jnp.zeros_like(blk)), axis=1,
                          keepdims=True)                    # (W, 1)
            out[...] = jnp.where(dst, col, out[...])


def _fetch_rows(sp_indices, sp_values, shard, idx, interpret: bool):
    """Rows ``idx`` (S,) of shard ``shard`` as (W, S) tables, steps on the
    lanes: ``(cols, vals)``."""
    k, n_shard, w_nnz = sp_indices.shape
    s = idx.shape[0]
    if not rows_on_lanes(n_shard, w_nnz):
        take = lambda a: lax.dynamic_index_in_dim(  # noqa: E731
            a, shard, 0, keepdims=False)[idx].T
        return take(sp_indices), take(sp_values)
    rows = FETCH_ROWS
    block = min(FETCH_BLOCK, -(-s // LANES) * LANES)
    n_blocks = -(-s // block)
    idx_p = jnp.pad(idx, (0, n_blocks * block - s)).reshape(n_blocks, block)

    def row_spec(r):
        return pl.BlockSpec(
            (1, w_nnz, LANES),
            lambda i, sh, ix: (sh[0], 0, ix[i * rows + r] // LANES))

    out_spec = pl.BlockSpec((w_nnz, LANES),
                            lambda i, sh, ix: (0, i * rows // LANES))
    fetch = pl.pallas_call(
        functools.partial(_fetch_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(block // rows,),
            in_specs=[row_spec(r) for r in range(rows)] * 2,
            out_specs=[out_spec, out_spec]),
        out_shape=[jax.ShapeDtypeStruct((w_nnz, block), sp_indices.dtype),
                   jax.ShapeDtypeStruct((w_nnz, block), sp_values.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pallas_sparse_fetch_rows",
    )
    # the arrays as stored, seen as (K, W, n_shard): no copy on a TPU
    ci, cv = sp_indices.transpose(0, 2, 1), sp_values.transpose(0, 2, 1)
    sh = jnp.reshape(shard, (1,)).astype(jnp.int32)
    cols, vals = lax.map(
        lambda ix: fetch(sh, ix, *([ci] * rows), *([cv] * rows)), idx_p)
    flat = lambda a: a.transpose(1, 0, 2).reshape(  # noqa: E731
        w_nnz, n_blocks * block)[:, :s]
    return flat(cols), flat(vals)


# --- the chain -----------------------------------------------------------------

def _index(i):
    """A dynamic sublane index in the default integer type: a masked store
    through ``ref.at[...]`` fills in the other dimension's start as a
    default-typed 0, and the two must agree (the tests run with x64 on; on
    the chip both are int32)."""
    return i.astype(jnp.asarray(0).dtype)


def _log2(n: int) -> int:
    assert n & (n - 1) == 0, n
    return n.bit_length() - 1


def _chain_kernel(itab_ref,   # SMEM (1, CHUNK·wt) int32: local ids, nnz, prev
                  ftab_ref,   # SMEM (1, CHUNK·wt) f32: values, y, q, α
                  wd_hbm,     # ANY (M/128, 2·128): [w | Δw carried]
                  a_out,      # VMEM (S/128, 128): the steps' new α
                  wd_out,     # ANY (M/128, 2·128): [w | Δw] after the segment
                  wd_sc,      # VMEM scratch (M/128, 2·128)
                  *, lam_n: float, coef_div: float, sig_eff: float,
                  frozen: bool, w_r: int, chunk: int, loss: str,
                  smoothing: float, head: int, tail: int):
    c = pl.program_id(0)
    wt = _table_width(w_r)
    assert (w_r - head) % tail == 0 and GROUP % tail == 0, (w_r, tail)

    @pl.when(c == 0)
    def _init():
        a_out[...] = jnp.zeros_like(a_out)
        pltpu.sync_copy(wd_hbm, wd_sc)

    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane2 = lax.broadcasted_iota(jnp.int32, (1, 2 * LANES), 1)
    dtype = a_out.dtype

    def step(s, carry):
        j = c * chunk + s                     # the step within the segment
        base = s * wt
        cnt = itab_ref[0, base + w_r]
        prev = itab_ref[0, base + w_r + 1]
        # y, σ′‖x‖² and α leave SMEM as splats: scalar to vector, the one
        # direction a float of the step ever takes
        y, qii, a0 = (jnp.full((1, 1), ftab_ref[0, base + w_r + i], dtype)
                      for i in range(N_FLT))
        # a row this segment already stepped on: α is that step's output
        pj = jnp.maximum(prev, 0)
        prow = a_out[pl.ds(pj >> 7, 1)]                       # (1, LANES)
        a = jnp.where(prev >= 0,
                      lane_pick(prow, lane == (pj & (LANES - 1))), a0)

        def walk(slots, init):
            """``init = slots(first, count, init)`` over the step's slots
            in order, ``count`` static: the first ``head`` of them written
            out, whatever the row's length (a padded step, ``cnt`` -1,
            adds zeros and stores nothing), and past them the row's whole
            GROUPs and then its ``tail``-slot groups, as far as its
            length reaches."""
            for first in range(0, head, GROUP):
                init = slots(first, min(GROUP, head - first), init)
            if head == w_r:
                return init
            live = jnp.maximum(cnt - head, 0)
            whole, left = live >> _log2(GROUP), live & (GROUP - 1)
            if w_r - head >= GROUP:
                init = lax.fori_loop(
                    0, whole,
                    lambda g, v: slots(head + g * GROUP, GROUP, v), init)
            return lax.fori_loop(
                0, (left + (tail - 1)) >> _log2(tail),
                lambda g, v: slots(head + whole * GROUP + g * tail, tail, v),
                init)

        # margin = x·w + sig_eff·x·Δw: per nonzero one dynamic sublane read
        # of the [w | Δw] row and a masked multiply-add into a lane vector;
        # ONE cross-lane sum a step, which keeps its axes
        def margin_slots(first, count, acc):
            for u in range(count):
                f = itab_ref[0, base + first + u]
                vj = ftab_ref[0, base + first + u]
                row = wd_sc[pl.ds(f >> 7, 1)]                 # (1, 2·LANES)
                fl = f & (LANES - 1)
                pick = jnp.where(lane2 == fl, 1.0, 0.0)
                if not frozen:
                    pick = pick + jnp.where(lane2 == fl + LANES, sig_eff,
                                            0.0)
                acc = acc + row * (pick * vj)
            return acc

        acc = walk(margin_slots, jnp.zeros((1, 2 * LANES), dtype))
        new_a = losses.alpha_step(loss, a, y * tile_total(acc), qii, lam_n,
                                  smoothing=smoothing)
        coef = y * (new_a - a) / coef_div                     # (1, 1)

        # Δw += coef·x: a masked store of the one lane each nonzero owns.
        # A row has no column twice (LIBSVM rows, ``shard_dataset``), so
        # within a step no store feeds a later slot's read, and the
        # group's reads all go first.
        def scatter_slots(first, count, carry_):
            fs = [itab_ref[0, base + first + u] for u in range(count)]
            vs = [ftab_ref[0, base + first + u] for u in range(count)]
            rows = [wd_sc[pl.ds(f >> 7, 1)] for f in fs]
            for u, (f, vj, row) in enumerate(zip(fs, vs, rows)):
                # a slot past the row's length holds id 0 and value 0: its
                # store would put back the Δw[0] read before this group's
                # stores, so it stores nothing
                pltpu.store(wd_sc.at[pl.ds(_index(f >> 7), 1)],
                            row + coef * vj,
                            mask=(lane2 == (f & (LANES - 1)) + LANES)
                            & (first + u < cnt))
            return carry_

        walk(scatter_slots, jnp.int32(0))
        pltpu.store(a_out.at[pl.ds(_index(j >> 7), 1)],
                    jnp.broadcast_to(new_a, (1, LANES)).astype(dtype),
                    mask=lane == (j & (LANES - 1)))
        return carry

    lax.fori_loop(0, chunk, step, jnp.int32(0))

    @pl.when(c == pl.num_programs(0) - 1)
    def _flush():
        pltpu.sync_copy(wd_sc, wd_out)


def _chain_call(plan: HbmPlan, dtype, interpret: bool, **consts):
    """The ``pallas_call`` of one segment: (itab, ftab, wd) -> (α of the
    steps (S/128, 128), wd after)."""
    s_blk = -(-plan.s // LANES)
    wt = _table_width(plan.w_r)
    rows = plan.m // LANES
    return pl.pallas_call(
        functools.partial(_chain_kernel, w_r=plan.w_r, chunk=plan.chunk,
                          head=walk_head(plan.w_r, plan.unrolled),
                          tail=TAIL_GROUP, **consts),
        grid=(plan.s // plan.chunk,),
        in_specs=[
            pl.BlockSpec((1, plan.chunk * wt), lambda c: (0, c),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, plan.chunk * wt), lambda c: (0, c),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((s_blk, LANES), lambda c: (0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[jax.ShapeDtypeStruct((s_blk, LANES), dtype),
                   jax.ShapeDtypeStruct((rows, 2 * LANES), dtype)],
        scratch_shapes=[pltpu.VMEM((rows, 2 * LANES), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=HBM_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="pallas_sparse_hbm_round",
    )


# --- around the chain, in XLA --------------------------------------------------

def _link_repeats(idx, live):
    """For the S row indices of a segment: ``prev`` (the latest earlier live
    step on the same row, or -1) and ``last`` (no later live step is on
    this row).  A stable sort of S indices, not of nonzeros."""
    key = jnp.where(live, idx, _NONE)
    order = jnp.argsort(key, stable=True)
    srt = key[order]
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), srt[1:] == srt[:-1]])
    same_next = jnp.concatenate([srt[1:] == srt[:-1], jnp.zeros((1,), bool)])
    prev_sorted = jnp.where(
        same_prev, jnp.concatenate([order[:1], order[:-1]]), -1)
    prev = jnp.zeros_like(idx).at[order].set(prev_sorted.astype(idx.dtype))
    last = jnp.zeros(idx.shape, bool).at[order].set(~same_next)
    return jnp.where(live, prev, -1), last & live


def _local_ids(cols, used):
    """Rank every used slot's column among the segment's distinct columns:
    ``(lid (W, S), ucols (W·S,), n_cols)`` — ``ucols[:n_cols]`` the distinct
    columns ascending, the rest ``_NONE``.  Two sorts of (key, payload)
    pairs and one of keys; no gather, no scatter."""
    n = cols.size
    key = jnp.where(used, cols, _NONE).reshape(n)
    sc, sp = lax.sort((key, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    real = sc != _NONE
    new = real & jnp.concatenate([jnp.ones((1,), bool), sc[1:] != sc[:-1]])
    rank = jnp.cumsum(new.astype(jnp.int32)) - 1
    _, lid = lax.sort((sp, jnp.where(real, rank, 0)), num_keys=1)
    ucols = jnp.sort(jnp.where(new, sc, _NONE))
    return lid.reshape(cols.shape), ucols, rank[-1] + 1


def _per_column(plan: HbmPlan, ucols, n_cols, body, init):
    """``init = body(start, columns, valid, init)`` over the segment's
    distinct columns, ``plan.column_chunk`` at a time and only as many
    trips as the segment has columns."""
    c = plan.column_chunk

    def trip(i, acc):
        start = i * c
        cc = lax.dynamic_slice_in_dim(ucols, start, c)
        return body(start, cc, start + jnp.arange(c) < n_cols, acc)

    return lax.fori_loop(0, (n_cols + c - 1) // c, trip, init)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing", "plan"),
)
def pallas_sparse_hbm_round(
    w: jax.Array,            # (d,) the round's primal vector
    alpha: jax.Array,        # (K, n_shard)
    sp_indices: jax.Array,   # (K, n_shard, W) int32 padded-CSR columns
    sp_values: jax.Array,    # (K, n_shard, W)
    labels: jax.Array,       # (K, n_shard)
    sq_norms: jax.Array,     # (K, n_shard)
    idxs: jax.Array,         # (K, H) int32 sampled rows
    lam: float,
    n: int,
    mode: str = "plus",
    sigma: float = 1.0,
    interpret: bool = False,
    loss: str = "hinge",
    smoothing: float = 1.0,
    row_len: jax.Array = None,   # (K, n_shard) int32, pallas_sparse.row_lengths
    plan: HbmPlan = None,        # None: hbm_plan on the shapes
):
    """One sparse SDCA round for K shards on this chip, one shard after
    another.  Returns ``(dw_sum, alpha_inner)``: dw_sum (d,) the K shards'
    Δw already summed — one target, never K dense d-vectors — and
    alpha_inner (K, n_shard) the locally advanced α."""
    k, n_shard, w_nnz = sp_indices.shape
    h, d, dtype = idxs.shape[1], w.shape[0], w.dtype
    check_dtype(dtype)
    if plan is None:
        plan = hbm_plan(d, w_nnz, h, jnp.dtype(dtype).itemsize)
    if plan is None:
        raise ValueError(f"no segment of the sparse HBM-state kernel fits "
                         f"(W={w_nnz}, H={h})")
    sig_eff, qii_factor = mode_factors(mode, sigma)
    if row_len is None:
        row_len = row_lengths(sp_values)
    s, t, w_r, m, wt = plan.s, plan.t, plan.w_r, plan.m, _table_width(
        plan.w_r)
    chain = _chain_call(
        plan, dtype, interpret, lam_n=float(lam * n),
        coef_div=float(coef_divisor(mode, lam * n)), sig_eff=float(sig_eff),
        frozen=mode == "frozen", loss=losses.validate(loss, smoothing),
        smoothing=float(smoothing))
    idxs_p = jnp.pad(idxs.astype(jnp.int32), ((0, 0), (0, t * s - h)))
    live_p = (jnp.arange(t * s) < h).reshape(t, s)
    # gathers read copies made in here, hot columns spread out: at one
    # speed wherever the caller's w sits (ops/rows.py, SPREAD_ROWS)
    w_spread = spread_table(w)
    slot = jnp.arange(w_nnz)[:, None]

    def segment(shard, alpha_k, dwk, idx, live):
        """S steps of shard ``shard``: ``(alpha_k, dwk)`` after them."""
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            at = lambda a: lax.dynamic_index_in_dim(  # noqa: E731
                a, shard, 0, keepdims=False)[idx]
            cols, vals = _fetch_rows(sp_indices, sp_values, shard, idx,
                                     interpret)
            cnt = jnp.where(live, at(row_len), -1)
            used = slot < cnt[None, :]
            vals = jnp.where(used, vals, 0).astype(dtype)
            if plan.direct:
                lid = jnp.where(used, cols, 0)
                pad = (0, m - d)
                w_c, dw_c = jnp.pad(w, pad), jnp.pad(dwk, pad)
            else:
                lid, ucols, n_cols = _local_ids(cols, used)
                ucols = jnp.pad(ucols, (0, m - ucols.shape[0]),
                                constant_values=_NONE)

                def gather(start, cc, valid, acc):
                    at_cc = spread_index(jnp.where(valid, cc, 0), d)
                    return tuple(
                        lax.dynamic_update_slice_in_dim(
                            a, jnp.where(valid, v[at_cc], 0), start, 0)
                        for a, v in zip(acc, (w_spread, spread_table(dwk))))

                zeros = jnp.zeros((m,), dtype)
                w_c, dw_c = _per_column(plan, ucols, n_cols, gather,
                                        (zeros, zeros))
            wd = jnp.concatenate([w_c.reshape(-1, LANES),
                                  dw_c.reshape(-1, LANES)], axis=-1)
            prev, last = _link_repeats(idx, live)
            fill = lambda n_, dt: jnp.zeros(  # noqa: E731
                (s, wt - w_r - n_), dt)
            widen = lambda a: jnp.pad(  # noqa: E731
                a.T, ((0, 0), (0, w_r - w_nnz)))
            itab = jnp.concatenate(
                [widen(lid), cnt[:, None], prev[:, None],
                 fill(N_INT, jnp.int32)], axis=1
            ).astype(jnp.int32).reshape(1, s * wt)
            ftab = jnp.concatenate(
                [widen(vals), at(labels)[:, None],
                 (at(sq_norms) * qii_factor)[:, None], alpha_k[idx][:, None],
                 fill(N_FLT, dtype)], axis=1
            ).astype(dtype).reshape(1, s * wt)
        with jax.named_scope(SCOPE_LOCAL_SOLVE):
            a_new, wd = chain(itab, ftab, wd)
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            alpha_k = alpha_k.at[jnp.where(last, idx, n_shard)].set(
                a_new.reshape(-1)[:s], mode="drop")
            dw_c = wd[:, LANES:].reshape(-1)
            if plan.direct:
                dwk = dw_c[:d]
            else:
                def put(start, cc, valid, dwk):
                    return dwk.at[jnp.where(valid, cc, d)].set(
                        lax.dynamic_slice_in_dim(dw_c, start,
                                                 plan.column_chunk),
                        mode="drop")

                dwk = _per_column(plan, ucols, n_cols, put, dwk)
        return alpha_k, dwk

    def one_shard(carry, xs):
        alpha, dw_sum = carry
        shard, idx_k = xs
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            alpha_k = lax.dynamic_index_in_dim(alpha, shard, 0,
                                               keepdims=False)
            dwk = jnp.zeros((d,), dtype)
        if t == 1:
            alpha_k, dwk = segment(shard, alpha_k, dwk, idx_k[0], live_p[0])
        else:
            (alpha_k, dwk), _ = lax.scan(
                lambda c, x: (segment(shard, *c, *x), None), (alpha_k, dwk),
                (idx_k, live_p))
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            alpha = lax.dynamic_update_index_in_dim(alpha, alpha_k, shard, 0)
            return (alpha, dw_sum + dwk), None

    xs = (jnp.arange(k, dtype=jnp.int32), idxs_p.reshape(k, t, s))
    if k == 1:
        (alpha, dw_sum), _ = one_shard((alpha, jnp.zeros((d,), dtype)),
                                       jax.tree.map(lambda a: a[0], xs))
    else:
        (alpha, dw_sum), _ = lax.scan(one_shard,
                                      (alpha, jnp.zeros((d,), dtype)), xs)
    return dw_sum, alpha
