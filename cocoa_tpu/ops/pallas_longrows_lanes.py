"""One-vs-rest SDCA on rows kept as a STREAM: the class axis on the LANES.

The two modules this one joins: ``ops/pallas_longrows.py`` runs ONE model's
chain over rows kept end to end (``data/sharding.stream_suits``: a hundred
nonzeros a row or thousands, the longest many times the mean), walking a
row's nonzeros out of the stream by a DMA ring of ``CHUNK_PIECES``-piece
chunks, HBM to SMEM; ``ops/pallas_sparse_lanes.py`` runs T models side by
side over a padded-CSR rectangle, W (d, R, 128) and alpha (K, n_shard, R,
128) in HBM (``data/sharding.class_tile_shape``), a 4 KB row of W and of
the shard's dW fetched a nonzero.  A multi-label text set with real
documents for rows (tf-idf rows of a few hundred terms, a tail tens of
times the mean) is both at once: its rectangle would be tens of times its
nonzeros, and it trains a thousand labels.  So here a shard's chain takes a
sampled row's nonzeros from the stream, a chunk at a time, and for each of
them moves a row of W and of dW_k:

    margin_t = x_i.w_t + sigma' x_i.dw_kt,   alpha_ti <- losses.alpha_step,
    dw_kt += coef_t x_i,                      y_ti = +1 iff t in L_i

for all T labels at once on (R, 128) tiles (``SolverPath.margin``
``split``: W and dW_k are two arrays, both in HBM, and a step's margin
reads a row of each a nonzero; dW_k starts a shard's round at zero, so an
update rounds at |dw| and a column no step touched stays exactly 0).

**A step** (``_chain_kernel``).  The sampled rows' scalars — a row's first
slot group in the whole stream, its nonzeros, its position in the shard,
its label ids, sigma' |x|^2 — stream through SMEM ``ROW_BLOCK`` steps at a
time.  The row's first chunk of (column, value) slots was started a step
ahead (the ring across rows: it sits in a slot of its own, by the step's
parity, and serves both passes of the step); the chunks of a longer row
alternate through two more slots, each fetched while the one before is
walked.  Pass 1, the margin: 8 slots a group, a group's 16 rows of W and
dW_k in flight into a VMEM ring of ``plan.ring`` slots while the group
before is added up, one multiply-add a nonzero on the (R, 128) tile.  Then
alpha's row (one (R, 128) row in at the step's start, one out, in place),
``alpha_step`` for T labels side by side, ``coef``.  Pass 2, the update:
dw_k's rows + coef x value, each group written back while the next is
updated.  A row of at most ``plan.ring`` nonzeros still has its dW_k rows
in the ring from pass 1; a longer one (to 8,192 and past: nothing holds a
row in VMEM) reads them again, a group ahead.  A step ends when its writes
have landed, so the next step's reads see them: a row drawn twice in a
round, or a column in every row, needs no link.  No floating-point value of
a step is 0-d: the scalar core holds the integers and hands a nonzero's
value and sigma' |x|^2 over as SMEM loads splatted into vector operations.

**A round**: one shard after another; dW_k zeroed and, after the chain,
added to the round's dW (``cocoa_dw_reduce``), the tables built under
``cocoa_sparse_gather``, the chain under ``cocoa_local_solve``.

**The certificate** (``stream_class_loss_sums``): T primal / dual sums from
one blocked pass over the streamed rows in XLA: a block of ``EVAL_ROWS``
rows reads its own run of the stream ``EVAL_GROUPS`` slot groups at a time
(a row starts on a group boundary, so a group belongs to one row), gathers
a W row a slot, sums a group's eight and adds the groups to their rows by
one 0/1 matrix product at ``highest`` precision.

Off the TPU the round is ``pallas_sparse_lanes.sparse_lanes_round_fori``,
which reads a row through ``ops/rows.get_row`` whatever the storage (and
is the oracle the interpreted kernel is held to).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.data.sharding import class_signs, label_sets
from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.ops.pallas_longrows import (ALIGN, CHUNK, CHUNK_PIECES, PIECE,
                                           _as_pieces, _global_start)
from cocoa_tpu.ops.pallas_sdca import LANES, check_dtype
from cocoa_tpu.ops.pallas_sparse_hbm import (HBM_SMEM_BUDGET,
                                             HBM_VMEM_BUDGET, HBM_VMEM_LIMIT)
from cocoa_tpu.ops.pallas_sparse_lanes import _blend_rows, _step_scalars
from cocoa_tpu.telemetry.tracing import (SCOPE_DW_REDUCE, SCOPE_LOCAL_SOLVE,
                                         SCOPE_SPARSE_GATHER)

GROUP = ALIGN                    # slots a group: a row starts on one, so a
                                 # group never holds slots of two rows
PER_PIECE = PIECE // GROUP       # groups a piece
PER_CHUNK = CHUNK // GROUP       # groups a chunk
ROW_BLOCK = 256                  # steps whose scalars are in SMEM at once
RING_SLOTS = 512                 # rows of W and of dW_k the ring holds at most
RING_MIN = 4 * GROUP             # and at least: a group in flight, one being
                                 # added up, one being written back
LABELSTREAM_VMEM_BUDGET = HBM_VMEM_BUDGET   # the two rings and alpha's rows
LABELSTREAM_SMEM_BUDGET = HBM_SMEM_BUDGET   # the step tables and the chunks
N_INT = 3                        # per-step integer tables ahead of the label
                                 # ids: (start, nnz, the row's position)
EVAL_ROWS = 256                  # rows a block of the certificate's pass
EVAL_GROUPS = 256                # slot groups it reads at a time


@dataclasses.dataclass(frozen=True)
class StreamLanesPlan:
    """What one shard's round runs: ``steps`` table rows (H padded to whole
    blocks of ``row_block``), a ring of ``ring`` W / dW_k rows of
    ``t_pad`` lanes each, ``label_slots`` class ids a step."""
    t_pad: int
    ring: int
    row_block: int
    steps: int
    label_slots: int


def vmem_estimate(ring: int, t_pad: int, itemsize: int) -> int:
    """Bytes of VMEM scratch: the W ring, the dW_k ring, alpha in and out."""
    return (2 * ring + 2) * t_pad * itemsize


def smem_estimate(row_block: int, label_slots: int) -> int:
    """Bytes of SMEM: the step tables, double-buffered by the pipeline, and
    the four chunk slots of (column, value)."""
    return (2 * 4 * row_block * (N_INT + label_slots + 1)
            + 2 * 4 * 4 * CHUNK)


def stream_lanes_plan(h: int, itemsize: int, t_pad: int,
                      label_slots: int) -> Optional[StreamLanesPlan]:
    """The plan of a round of ``h`` steps, or None where even the smallest
    ring outgrows the VMEM budget (a class axis of ~2,700 tiles)."""
    ring = min(RING_SLOTS, (LABELSTREAM_VMEM_BUDGET // (t_pad * itemsize) - 2)
               // 2) // GROUP * GROUP
    if (itemsize != 4 or ring < RING_MIN
            or smem_estimate(ROW_BLOCK, label_slots)
            > LABELSTREAM_SMEM_BUDGET):
        return None
    return StreamLanesPlan(t_pad=t_pad, ring=ring, row_block=ROW_BLOCK,
                           steps=-(-h // ROW_BLOCK) * ROW_BLOCK,
                           label_slots=label_slots)


def stream_lanes_fits(h: int, itemsize: int, t_pad: int,
                      label_slots: int) -> bool:
    """The resolver's gate: a plan exists (a step fits the budgets)."""
    return stream_lanes_plan(h, itemsize, t_pad, label_slots) is not None


def _chain_kernel(shard_ref,   # SMEM (1,) int32: the shard
                  start_ref,   # SMEM (1, ROW_BLOCK): a row's first group
                  cnt_ref,     # its nonzeros (-1: a step that pads the block)
                  row_ref,     # its position in the shard
                  ids_ref,     # SMEM (1, ROW_BLOCK x L): its label ids
                  q_ref,       # SMEM (1, ROW_BLOCK) float: sigma' |x|^2
                  cols_hbm,    # ANY (K x n_pieces, 1, PIECE) int32
                  vals_hbm,    # ANY (K x n_pieces, 1, PIECE)
                  w_hbm,       # ANY (d, R, 128): W, read only
                  dw_in,       # ANY (d, R, 128): dW_k (aliased)
                  a_in,        # ANY (K, n_shard, R, 128): alpha (aliased)
                  dw_hbm,      # the same two arrays as outputs: the chain
                  a_hbm,       # reads and writes these
                  wbuf,        # VMEM (ring, R, 128): W rows
                  dbuf,        # VMEM (ring, R, 128): dW_k rows
                  abuf,        # VMEM (2, R, 128): alpha in, alpha out
                  cbuf,        # SMEM (4, CHUNK_PIECES, 1, PIECE) int32
                  vbuf,        # SMEM (4, CHUNK_PIECES, 1, PIECE)
                  csem,        # DMA (2, 4): [columns | values] by chunk slot
                  rsem,        # DMA (2, 2): [pass 1 | pass 2] reads by parity
                  wsem,        # DMA (2,): writes by parity
                  asem,        # DMA (2,): alpha in, alpha out
                  *, lam_n: float, coef_div: float, sig_eff: float,
                  frozen: bool, ring: int, row_block: int, loss: str,
                  smoothing: float, classes: int, label_slots: int):
    del dw_in, a_in
    shard = shard_ref[0]
    shape = abuf.shape[1:]
    dtype = abuf.dtype
    n_ring = ring // GROUP
    cls = (lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + lax.broadcasted_iota(jnp.int32, shape, 1))
    start_dma = lambda cp: cp.start()  # noqa: E731
    wait_dma = lambda cp: cp.wait()    # noqa: E731

    def chunk_copies(piece, c, slot):
        src = pl.ds(piece + c * CHUNK_PIECES, CHUNK_PIECES)
        return (pltpu.make_async_copy(cols_hbm.at[src], cbuf.at[slot],
                                      csem.at[0, slot]),
                pltpu.make_async_copy(vals_hbm.at[src], vbuf.at[slot],
                                      csem.at[1, slot]))

    def chunk(piece, c, slot, do):
        for cp in chunk_copies(piece, c, slot):
            do(cp)

    def home(i):
        """The chunk slot row ``i``'s first chunk keeps for its whole
        step: by the step's parity, so that the next row's can be on its
        way while this one is walked."""
        return 2 + (i & 1)

    @pl.when(cnt_ref[0, 0] >= 0)
    def _first():
        # the block's first row is the one fetch nothing hides: the tables
        # of the block before are no longer in SMEM when its last row runs
        chunk(start_ref[0, 0] >> 4, 0, home(0), start_dma)

    def step(i, carry):
        cnt = cnt_ref[0, i]

        @pl.when(cnt >= 0)
        def _live():
            start, row = start_ref[0, i], row_ref[0, i]
            piece = start >> 4
            lead = (start & (PER_PIECE - 1)) * GROUP    # slots into the piece
            span = lead + cnt
            n_chunks = jnp.maximum((span + (CHUNK - 1)) // CHUNK, 1)
            g_lead = lead // GROUP
            groups = (cnt + (GROUP - 1)) // GROUP
            held = groups <= n_ring     # pass 1 leaves the row's dW_k rows
            a_read = pltpu.make_async_copy(a_hbm.at[shard, row], abuf.at[0],
                                           asem.at[0])
            a_read.start()

            nxt = jnp.minimum(i + 1, row_block - 1)

            @pl.when((i + 1 < row_block) & (cnt_ref[0, nxt] >= 0))
            def _ahead():
                chunk(start_ref[0, nxt] >> 4, 0, home(nxt), start_dma)

            chunk(piece, 0, home(i), wait_dma)

            def over_chunks(body, init):
                """``init = body(slot, base, lo, hi, init)`` for each chunk
                of the row: its groups [lo, hi) of the chunk in SMEM slot
                ``slot`` are the row's, and group g of them is group
                ``base + g`` of the row.  Chunk 0 is in the row's home
                slot; the others alternate through slots 0 and 1, each
                fetched while the one before is walked."""
                def one(c, acc):
                    slot = jnp.where(c == 0, home(i), c & 1)

                    @pl.when(c + 1 < n_chunks)
                    def _next():
                        chunk(piece, c + 1, (c + 1) & 1, start_dma)

                    @pl.when(c > 0)
                    def _wait():
                        chunk(piece, c, slot, wait_dma)

                    here = jnp.minimum(span - c * CHUNK, CHUNK)
                    return body(slot, c * PER_CHUNK - g_lead,
                                jnp.where(c == 0, g_lead, 0),
                                (here + (GROUP - 1)) // GROUP, acc)

                return lax.fori_loop(0, n_chunks, one, init)

            def at(g):
                """Group ``g`` of a chunk: (its piece, its first lane)."""
                return g // PER_PIECE, (g % PER_PIECE) * GROUP

            def slot_of(gg):
                """Group ``gg`` of the row: its first slot of the ring."""
                return (gg % n_ring) * GROUP

            both = ((w_hbm, wbuf), (dw_hbm, dbuf))     # pass 1's rows
            dw_only = both[1:]                          # pass 2's

            def rows(cslot, base, g, do, arrays, sem):
                """``do`` (start or wait) the copies that bring group g's
                rows of ``arrays`` (W and dW_k, or dW_k) into the ring.  A
                slot past the row's length holds column 0, value 0: its
                rows are read, add nothing and are never written back."""
                pc, lane0 = at(g)
                r0 = slot_of(base + g)
                for u in range(GROUP):
                    col = cbuf[cslot, pc, 0, lane0 + u]
                    for hbm, buf in arrays:
                        do(pltpu.make_async_copy(hbm.at[col],
                                                 buf.at[r0 + u], sem))

            # pass 1: margin_t = x.w_t + sig_eff x.dw_kt, one multiply-add
            # a nonzero on the (R, 128) tile; group g + 1's rows are in
            # flight while group g's are added up
            def margin_chunk(cslot, base, lo, hi, acc):
                @pl.when(hi > lo)
                def _prime():
                    rows(cslot, base, lo, start_dma, both,
                         rsem.at[0, lo & 1])

                def group(g, acc):
                    @pl.when(g + 1 < hi)
                    def _ahead():
                        rows(cslot, base, g + 1, start_dma, both,
                             rsem.at[0, (g + 1) & 1])

                    rows(cslot, base, g, wait_dma, both, rsem.at[0, g & 1])
                    pc, lane0 = at(g)
                    r0 = slot_of(base + g)
                    for u in range(GROUP):
                        vj = vbuf[cslot, pc, 0, lane0 + u]
                        tile = wbuf[r0 + u]
                        if not frozen:
                            tile = tile + sig_eff * dbuf[r0 + u]
                        acc = acc + tile * vj
                    return acc

                return lax.fori_loop(lo, hi, group, acc)

            acc = over_chunks(margin_chunk, jnp.zeros(shape, dtype))
            a_read.wait()
            hit = cls < 0
            for l in range(label_slots):
                hit = hit | (cls == ids_ref[0, i * label_slots + l])
            y = jnp.where(hit, 1.0, -1.0).astype(dtype)
            qii = jnp.full(shape, q_ref[0, i], dtype)
            a = abuf[0]
            new_a = losses.alpha_step(loss, a, y * acc, qii, lam_n,
                                      smoothing=smoothing)
            new_a = jnp.where(cls < classes, new_a, a).astype(dtype)
            coef = y * (new_a - a) / coef_div
            abuf[1] = new_a
            a_write = pltpu.make_async_copy(abuf.at[1], a_hbm.at[shard, row],
                                            asem.at[1])
            a_write.start()

            # pass 2: dw_k += coef x.  A held row's dW_k rows are in the
            # ring already; a longer one reads them again, a group ahead.
            # Each group goes back as it is updated, its writes in flight
            # while the next is updated.  A row has no column twice; the
            # slots past its length (in its last group) are not written:
            # one of them would put back the dW_k[0] it read before this
            # step's stores.
            rest = cnt % GROUP

            def update_chunk(cslot, base, lo, hi, carry_):
                reread = ~held
                # the row's last group, where it is not whole, is this
                # chunk's last: it is written slot by slot
                whole = jnp.where(
                    (rest > 0) & (base + hi == groups), hi - 1, hi)

                @pl.when(reread & (hi > lo))
                def _prime():
                    rows(cslot, base, lo, start_dma, dw_only,
                         rsem.at[1, lo & 1])

                def update(g):
                    @pl.when(reread & (g + 1 < hi))
                    def _ahead():
                        rows(cslot, base, g + 1, start_dma, dw_only,
                             rsem.at[1, (g + 1) & 1])

                    @pl.when(reread)
                    def _wait():
                        rows(cslot, base, g, wait_dma, dw_only,
                             rsem.at[1, g & 1])

                    pc, lane0 = at(g)
                    r0 = slot_of(base + g)
                    for u in range(GROUP):
                        dbuf[r0 + u] = (dbuf[r0 + u]
                                        + coef * vbuf[cslot, pc, 0, lane0 + u])

                def writes(g, do, own=lambda u, f: f()):
                    """``do`` group g's copies back to dW_k, each under
                    ``own(u, .)``: every slot of a whole group."""
                    pc, lane0 = at(g)
                    r0 = slot_of(base + g)
                    for u in range(GROUP):
                        cp = pltpu.make_async_copy(
                            dbuf.at[r0 + u],
                            dw_hbm.at[cbuf[cslot, pc, 0, lane0 + u]],
                            wsem.at[g & 1])
                        own(u, functools.partial(do, cp))

                def rows_own(u, f):     # the last group: the row's own slots
                    pl.when(u < rest)(f)

                def group(g, carry__):
                    update(g)
                    writes(g, start_dma)
                    pl.when(g > lo)(lambda: writes(g - 1, wait_dma))
                    return carry__

                lax.fori_loop(lo, whole, group, jnp.int32(0))

                @pl.when(whole < hi)
                def _partial():
                    update(whole)
                    writes(whole, start_dma, rows_own)

                pl.when(whole > lo)(lambda: writes(whole - 1, wait_dma))
                pl.when(whole < hi)(
                    lambda: writes(whole, wait_dma, rows_own))
                return carry_

            over_chunks(update_chunk, jnp.int32(0))
            a_write.wait()

        return carry

    lax.fori_loop(0, row_block, step, jnp.int32(0))


def _chain_call(plan: StreamLanesPlan, k: int, n_shard: int, d: int, dtype,
                interpret: bool, **consts):
    """The ``pallas_call`` of one shard's round: (shard, start, cnt, row,
    ids, q, columns, values, W, dW_k, alpha) -> (dW_k, alpha), the last
    two updated in place."""
    rb, slots = plan.row_block, plan.label_slots
    r = plan.t_pad // LANES
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    tab = pl.BlockSpec((1, rb), lambda b: (0, b), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_chain_kernel, ring=plan.ring, row_block=rb,
                          label_slots=slots, **consts),
        grid=(plan.steps // rb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM), tab, tab, tab,
            pl.BlockSpec((1, rb * slots), lambda b: (0, b),
                         memory_space=pltpu.SMEM),
            tab, any_, any_, any_, any_, any_,
        ],
        out_specs=[any_, any_],
        out_shape=[jax.ShapeDtypeStruct((d, r, LANES), dtype),
                   jax.ShapeDtypeStruct((k, n_shard, r, LANES), dtype)],
        input_output_aliases={9: 0, 10: 1},
        scratch_shapes=[pltpu.VMEM((plan.ring, r, LANES), dtype),
                        pltpu.VMEM((plan.ring, r, LANES), dtype),
                        pltpu.VMEM((2, r, LANES), dtype),
                        pltpu.SMEM((4, CHUNK_PIECES, 1, PIECE), jnp.int32),
                        pltpu.SMEM((4, CHUNK_PIECES, 1, PIECE), dtype),
                        pltpu.SemaphoreType.DMA((2, 4)),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=HBM_VMEM_LIMIT,
            has_side_effects=True,
        ),
        interpret=interpret,
        name="pallas_longrows_lanes_round",
    )


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing", "plan", "classes", "scaling"),
)
def pallas_stream_lanes_round(
    w: jax.Array,            # (d, R, 128) the round's primal vectors
    alpha: jax.Array,        # (K, n_shard, R, 128)
    shards: dict,            # the stream's arrays, sq_norms, classes, ...
    idxs: jax.Array,         # (K, H) int32 sampled rows
    lam: float,
    n: int,
    classes: int,            # T
    plan: StreamLanesPlan,
    mode: str = "plus",
    sigma: float = 1.0,
    scaling: float = 1.0,
    interpret: bool = False,
    loss: str = "hinge",
    smoothing: float = 1.0,
):
    """One one-vs-rest SDCA round for K shards of streamed rows on this
    chip, one shard after another.  Returns ``(dw_sum (d, R, 128),
    alpha')`` as ``pallas_sparse_lanes_round`` does."""
    sp_indices = shards["sp_indices"]
    k, n_pieces, _ = sp_indices.shape
    n_shard = alpha.shape[1]
    h, (d, r, _), dtype = idxs.shape[1], w.shape, w.dtype
    check_dtype(dtype)
    slots = label_sets(shards["classes"], 2).shape[-1]
    assert (plan.t_pad, plan.label_slots) == (r * LANES, slots), (plan,
                                                                 w.shape)
    sig_eff, qii_factor = mode_factors(mode, sigma)
    s = plan.steps
    chain = _chain_call(
        plan, k, n_shard, d, dtype, interpret,
        lam_n=float(lam * n), coef_div=float(coef_divisor(mode, lam * n)),
        sig_eff=float(sig_eff), frozen=mode == "frozen",
        loss=losses.validate(loss, smoothing), smoothing=float(smoothing),
        classes=int(classes))
    idxs = idxs.astype(jnp.int32)
    before = (None if scaling == 1.0
              else alpha[jnp.arange(k)[:, None], idxs])
    cols, vals = _as_pieces(sp_indices), _as_pieces(shards["sp_values"])
    starts = _global_start(shards["sp_row_ptr"], n_pieces)

    def one_shard(carry, xs):
        alpha, dw_sum = carry
        shard, idx = xs
        with jax.named_scope(SCOPE_SPARSE_GATHER):
            cnt, ids, q = _step_scalars(shards, shard, idx, qii_factor,
                                        dtype)
            start = lax.dynamic_index_in_dim(starts, shard, 0,
                                             keepdims=False)[idx]
            line = lambda a, fill=0: jnp.pad(  # noqa: E731
                a, ((0, s - h),) + ((0, 0),) * (a.ndim - 1),
                constant_values=fill).reshape(1, -1)
            # (the steps that pad the last block: nnz -1, nothing runs)
            tables = (line(start), line(cnt.astype(jnp.int32), -1),
                      line(idx), line(ids.astype(jnp.int32), -1), line(q))
        with jax.named_scope(SCOPE_DW_REDUCE):
            dwk = jnp.zeros_like(w)
        with jax.named_scope(SCOPE_LOCAL_SOLVE):
            dwk, alpha = chain(jnp.reshape(shard, (1,)).astype(jnp.int32),
                               *tables, cols, vals, w, dwk, alpha)
        with jax.named_scope(SCOPE_DW_REDUCE):
            return (alpha, dw_sum + dwk), None

    # two W-sized temporaries a round and no third: dw_k (zeroed, then the
    # chain's in place) and the carried sum, which the add updates in place
    # (tests/test_device_layout.py holds the compiled loop's peak to that)
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


# --- the certificate's pass over the streamed rows ---------------------------


def _eval_blocks(n_shard: int) -> tuple:
    """``(rows a block, blocks a shard)`` of the certificate's pass."""
    block = min(EVAL_ROWS, n_shard)
    return block, -(-n_shard // block)


def stream_class_loss_sums(w: jax.Array, alpha, arrays: dict, classes: int,
                           loss: str, smoothing: float) -> jax.Array:
    """``ops/rows.class_loss_sums`` over rows kept as a stream: (3, R, 128)
    per-class sums over the real rows of the primal loss at the margins
    x_i.W, of the dual term of ``alpha`` (None: zeros) and of the wrong
    signs, from ONE pass, a shard after another, a block of
    ``EVAL_ROWS`` rows at a time.  A block's rows lie one after another in
    the stream, so its slots are one run of it, [the piece its first row
    starts in, its last row's end): the run is read ``EVAL_GROUPS`` groups
    at a time, whole pieces as the stream stores them, a W row a slot, a group's eight slots summed (a row starts on a
    group boundary: a group is one row's), and the groups added to their
    rows by one 0/1 matrix product at ``highest`` precision — a group that
    is another block's, or past the run, matches no row.  y_ti comes from
    the rows' label sets and never exists T times over."""
    idx, val, mask = (arrays[f] for f in ("sp_indices", "sp_values", "mask"))
    ptr, length = arrays["sp_row_ptr"], arrays["sp_row_len"]
    ids_t = jnp.swapaxes(label_sets(arrays["classes"], 2), -1, -2)
    k, n = ptr.shape
    n_pieces = idx.shape[1]
    tile = w.shape[1:]
    t_pad = tile[0] * tile[1]
    block, nb = _eval_blocks(n)
    # a read is whole pieces of the stream as it is stored, (pieces, 128):
    # a (.., 8) view of it would be padded sixteen-fold on the device
    per_pieces = min(EVAL_GROUPS // PER_PIECE, n_pieces)
    per = per_pieces * PER_PIECE
    n_groups = n_pieces * PER_PIECE

    def one(t, sums):
        shard, b = t // nb, t % nb
        start = jnp.minimum(b * block, n - block)

        def rows(a, axis=1):
            """Rows [start, start + block) of shard ``shard`` of ``a``."""
            a = lax.dynamic_slice_in_dim(a, shard, 1, 0)
            return lax.dynamic_slice_in_dim(a, start, block, axis)[0]

        first = rows(ptr)
        count = (rows(length) + (GROUP - 1)) // GROUP
        some = count > 0
        # the block's run of the stream, from the piece its first row
        # starts in: [lo, hi) in groups
        lo = jnp.min(jnp.where(some, first, n_groups)) // PER_PIECE * PER_PIECE
        hi = jnp.max(jnp.where(some, first + count, 0))

        def add(s, m):
            want = lo + s * per
            # (a read that would pass the stream's end starts earlier; the
            # groups it then sees again are masked out below)
            at = jnp.minimum(want // PER_PIECE, n_pieces - per_pieces)
            zero = jnp.zeros_like(at)
            c, v = (lax.dynamic_slice(
                a, (shard.astype(at.dtype), at, zero),
                (1, per_pieces, PIECE)).reshape(per, GROUP)
                for a in (idx, val))
            part = (w[c] * v[..., None, None]).sum(1)      # (per, R, 128)
            g = at * PER_PIECE + jnp.arange(per, dtype=first.dtype)
            owns = ((g[None, :] >= first[:, None])
                    & (g[None, :] < (first + count)[:, None])
                    & (g >= want)[None, :])
            return m + jnp.dot(owns.astype(w.dtype),
                               part.reshape(per, t_pad),
                               precision=lax.Precision.HIGHEST)

        trips = jnp.maximum(hi - lo + (per - 1), 0) // per
        m = lax.fori_loop(0, trips, add,
                          jnp.zeros((block, t_pad), w.dtype))
        m = m.reshape((block,) + tile)
        # the last block starts early enough to end on the last row: the
        # rows it shares with its neighbour are the neighbour's
        own = (rows(mask) * (start + jnp.arange(block) >= b * block)
               )[:, None, None]
        ym = class_signs(rows(ids_t, 2).T, classes, w.dtype) * m
        primal = losses.primal(loss, ym, smoothing=smoothing)
        dual = (jnp.zeros_like(m) if alpha is None else
                losses.dual_term(loss, rows(alpha), smoothing=smoothing))
        return sums + jnp.stack([(primal * own).sum(0), (dual * own).sum(0),
                                 (jnp.where(ym <= 0, 1.0, 0.0) * own).sum(0)])

    return lax.fori_loop(0, k * nb, one, jnp.zeros((3,) + tile, w.dtype))


def pass_slot_share(row_ptr, row_len, n_pieces: int) -> float:
    """Of the slots the stream stores, the share one pass of
    :func:`stream_class_loss_sums` reads, counted on the host from the
    rows' starts and lengths: each block of rows reads its run of the
    stream in whole reads of ``EVAL_GROUPS`` groups."""
    ptr = np.asarray(row_ptr, np.int64)
    count = -(-np.asarray(row_len, np.int64) // GROUP)
    k, n = ptr.shape
    block, nb = _eval_blocks(n)
    per = min(EVAL_GROUPS // PER_PIECE, n_pieces) * PER_PIECE
    read = 0
    for b in range(nb):
        at = min(b * block, n - block)
        first, cnt = ptr[:, at:at + block], count[:, at:at + block]
        lo = (np.where(cnt > 0, first, n_pieces * PER_PIECE).min(1)
              // PER_PIECE * PER_PIECE)
        hi = np.where(cnt > 0, first + cnt, 0).max(1)
        read += int((np.maximum(hi - lo + (per - 1), 0) // per).sum()) * per
    return float(read / (k * n_pieces * PER_PIECE))
