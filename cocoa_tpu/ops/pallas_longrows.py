"""Sparse rows in pieces: the kernels of sets whose rows hold a hundred
nonzeros or thousands and are too uneven for a rectangle (url: 115.6 a
row, the longest 4 times that; webspam: 3,727, the longest 8.8 times).

``data/sharding.py`` keeps such a set as a **stream**: a shard's nonzeros
as one run of (column, value) slots, row after row, each row starting on an
``ALIGN``-slot boundary, the run cut into ``PIECE``-slot pieces (one full
lane row each) — ``sp_indices`` / ``sp_values`` of shape (K, n_pieces,
PIECE), ``sp_row_ptr`` (K, n_shard) a row's first slot in units of ALIGN,
``sp_row_len`` its nonzeros.  Stored slots follow the nonzeros (under ALIGN
slots of padding a row), not n x the longest row, and a row is one
contiguous run of memory.

What the v5e measured decides the rest (PERF.md §6, PR 30 step 0).  A
gather or scatter of single elements costs 11-24 ns in XLA whatever the
table, so nothing here gathers per nonzero in XLA.  One d-vector of 16.6M
float32 (66 MB) does fit a core's 128 MiB of VMEM, two do not.  So one
kernel body serves the three passes over rows, each with **one** d-vector
resident in VMEM, lane-blocked (d/128, 128), and the rows streamed from HBM
as they are stored:

- ``dots``: x_i . v for a list of rows (the certificate's margins over all
  rows), v = w resident;
- ``chain``: the sequential SDCA steps of one shard, in the order of the
  ``fori`` reference (CoCoA.scala:148-188).  A step reads w and dw_k only
  as its margin x_i . (w + sigma' dw_k), so the resident vector is
  **v = w + sigma' dw_k**: loaded as w, the margin one dot against it, the
  alpha step of ``losses.alpha_step``, the update v += sigma' coef x_i; the
  round gives back dw_k = (v - w) / sigma' by one dense pass in XLA.  A
  sampled nonzero is walked twice (dot, update), not three times, and an
  update rounds at |v|, not at |dw| (within 4 eps |w|_inf sqrt(steps on the
  column) of float64; exactly 0 on a column no step touched).  Mini-batch
  CD (``frozen``, sigma' = 0: its margin never reads dw_k, so no v gives
  dw_k back) keeps the ``split`` form: x_i . w of the round's rows from a
  ``dots`` pass, w being fixed for the round, then the chain against dw_k
  alone (:func:`margin_form`, reported as ``SolverPath.margin``);
- ``axpy``: v += c_i x_i over a list of rows (the ``--accel`` jump).

A row's slots reach the scalar core by DMA, HBM to SMEM, a **chunk** of
pieces at a time through a two-slot ring (addresses must be scalars), so a
step's tables follow the row's own length and a row of any length takes as
many chunks as it needs: SMEM holds two chunks, not a row.  **The ring
runs across rows** (PR 41): a pass knows the next row's address before it
works on this one (the row block's ``start`` table is in SMEM; the chain's
sampled rows are drawn before the round), so while the scalar core walks a
chunk the ring's other slot is already taking what comes next — the row's
next chunk or, at its last, the next row's first.  Only the first row of a
``ROW_BLOCK`` waits for its own fetch.  In the chain a row of one chunk is
fetched once: the update reads the chunk the dot left in the ring; a
longer row's chunks come round a second time, its first behind the dot's
last.  A chunk is ``CHUNK_PIECES`` pieces whatever the rows: with the ring
across rows its bytes cost nothing that shows (url's rows fill a ninth of
the slots a chunk brings and run alike at 4 pieces and at 8; what costs is
a row cut in two: PERF.md §6, PR 41), so nothing chooses it;
:func:`chunk_fill` says what share of what it moves is nonzeros.  Per
nonzero one dynamic sublane read of the d-vector and a masked multiply-add
(dots) or a masked single-lane store (axpy), as in ``pallas_sparse_hbm``;
with the fetch hidden the cost is paced by nonzeros plus a step's own
fixed work, which at rows of a hundred nonzeros is no longer small beside
them (PERF.md §6, PR 41, has both).

**No floating-point value of a step, a row or a coefficient is 0-d**
(PR 49, as ``pallas_sparse_hbm._chain_kernel`` since PR 46 and
``pallas_sdca._advance`` since PR 39).  The scalar core keeps the integers
— a row's start, count and ``prev``, a column's sublane and lane, the trip
counts, the ring's slots — and hands floats over as splats of SMEM loads,
scalar to vector, the cheap direction: a nonzero's value into the margin's
masked multiply-add and into the update's product; the chain's y, sigma'
|x|^2, alpha and (``split``) the table's x . w to (1, 1) vectors; axpy's
coefficient once a row.  What is computed stays on the vector side: a
row's total is ONE cross-lane reduce that keeps its axes (``tile_total``),
a repeated row's alpha a masked lane reduce (``lane_pick``), selected on
the integer ``prev``; ``losses.alpha_step`` runs elementwise on (1, 1)
vectors under every loss, logistic's Newton iterations too; ``coef``
(1, 1) broadcasts into ``row + coef * value`` and the new alpha, or a
``dots`` row's total, into the masked store of its lane of the output
block.  One chain runs at a time, so a value that went to the scalar core
and came back (a reduce to 0-d, the hinge rule's divide, ``coef``'s, the
splat) was paid in full: the same IEEE operations in the same order,
measured on the v5e in PERF.md §6, PR 49.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.data.sharding import STREAM_ALIGN as ALIGN
from cocoa_tpu.data.sharding import STREAM_PIECE as PIECE
from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.ops.pallas_sdca import (LANES, check_dtype, lane_pick,
                                       tile_total)

assert PIECE == LANES             # a piece is one full lane row: the unit a
                                 # DMA may start at
CHUNK_PIECES = 8                 # pieces a DMA brings to SMEM (4 KB a table)
CHUNK = CHUNK_PIECES * PIECE     # slots a chunk
GROUP = ALIGN                    # slots per unrolled body of the slot loop:
                                 # a group never holds slots of two rows
ROW_BLOCK = 1024                 # rows whose scalars stream through SMEM at once
VEC_VMEM_BUDGET = 88 << 20       # the resident d-vector
VMEM_LIMIT = 100 << 20           # what the kernels ask Mosaic for


def vec_rows(d: int) -> int:
    """Sublane rows of a d-vector lane-blocked into whole (8, 128) tiles."""
    return -(-d // (8 * LANES)) * 8


def longrows_fits(d: int, itemsize: int = 4) -> bool:
    """The resolver's gate: one lane-blocked d-vector fits the VMEM budget."""
    return itemsize == 4 and vec_rows(d) * LANES * itemsize <= VEC_VMEM_BUDGET


def margin_form(mode: str) -> str:
    """How the chain of a round takes a step's margin x . (w + sig_eff dw_k):
    ``combined``: as one dot against the resident v = w + sig_eff dw_k;
    ``split``: x . w from a ``dots`` pass before the chain, beside a resident
    dw_k — ``frozen``'s form, whose margin never reads dw_k (sig_eff 0), so
    no v gives dw_k back."""
    return "split" if mode == "frozen" else "combined"


def _index(i):
    """A dynamic sublane index in the default integer type (the tests run
    with x64 on; ``pallas_sparse_hbm._index``)."""
    return i.astype(jnp.asarray(0).dtype)


def _kernel(*refs, mode: str, n_f: int, split: bool, step_consts: dict):
    """Rows ``ROW_BLOCK`` at a time (grid axis 1) of group ``g`` (grid axis
    0).  Operands: ``start`` (a row's first slot, in ALIGN-slot groups of
    the whole stream) / ``cnt`` (and, chain, ``prev``) int32 and
    ``n_f`` float32 per-row tables as (1, ROW_BLOCK) SMEM blocks; the piece
    arrays and (but for the ``split`` chain, which starts from zeros) the
    d-vector in HBM; outputs the per-row result block (1, rows/128, 128) in
    VMEM and (chain, axpy) the d-vector after."""
    n_i = 3 if mode == "chain" else 2
    itabs, ftabs = refs[:n_i], refs[n_i:n_i + n_f]
    rest = refs[n_i + n_f:]
    cols_hbm, vals_hbm = rest[0], rest[1]
    rest = rest[2:]
    vec_in = None
    if not split:
        vec_in, rest = rest[0], rest[1:]
    if mode == "axpy":
        (vec_out, vec_sc, cbuf, vbuf, sem), out = rest, None
    elif mode == "dots":
        (out, vec_sc, cbuf, vbuf, sem), vec_out = rest, None
    else:
        out, vec_out, vec_sc, cbuf, vbuf, sem = rest
    g, b = pl.program_id(0), pl.program_id(1)
    first = (g == 0) & (b == 0)
    last = (g == pl.num_programs(0) - 1) & (b == pl.num_programs(1) - 1)
    dtype = vec_sc.dtype

    @pl.when(first)
    def _load():
        if vec_in is None:
            vec_sc[...] = jnp.zeros_like(vec_sc)
        else:
            pltpu.sync_copy(vec_in, vec_sc)

    if out is not None:
        @pl.when(b == 0)
        def _init():
            out[...] = jnp.zeros_like(out)

    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def splat(table, i):
        """Row ``i``'s float of an SMEM table as a (1, 1) vector: scalar to
        vector, the one direction a float of a row ever takes."""
        return jnp.full((1, 1), table[0, i], dtype)

    def copies(piece, c, slot):
        src = pl.ds(piece + c * CHUNK_PIECES, CHUNK_PIECES)
        return (pltpu.make_async_copy(cols_hbm.at[src], cbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(vals_hbm.at[src], vbuf.at[slot],
                                      sem.at[1, slot]))

    def fetch(piece, c, slot):
        for cp in copies(piece, c, slot):
            cp.start()

    def row_at(i):
        """Row ``i`` of the block as ``(piece, lead, span, chunks)``: its
        first chunk starts at ``piece``, its slots are [lead, span) of the
        chunks' run (``lead`` > 0: the row starts inside a piece), and it
        takes ``chunks`` of them — one even where it holds nothing, so
        that every row is one link of the ring."""
        start, cnt = itabs[0][0, i], itabs[1][0, i]
        lead = (start & 15) << 3
        span = lead + cnt
        return (start >> 4, lead, span,
                jnp.maximum((span + (CHUNK - 1)) // CHUNK, 1))

    def over_row(row, s0, body, init, after, moves=True):
        """``init = body(slot, j0, lo, hi, init)`` for each chunk of
        ``row`` (:func:`row_at`): chunk c sits in ring slot ``slot`` =
        (s0 + c) & 1, its groups [lo, hi) hold the row's slots, and its
        slot 0 is slot ``j0`` of the row (negative in the first chunk of a
        row that starts inside a piece).  **The ring runs across rows:**
        the row's chunk 0 is in flight in slot ``s0`` when this is called,
        and before chunk c is waited for the other slot is given what
        comes next: the row's chunk c + 1 or, at its last chunk,
        whatever ``after(slot)`` starts (the next row's chunk 0; the
        chain: this row's chunk 0 again, for its update).  ``moves``
        false (a traced condition or the constant): the row's one chunk is
        still in slot ``s0`` from the pass before, so nothing is started
        and nothing waited for."""
        piece, lead, span, n_chunks = row

        def chunk(c, acc):
            slot = (s0 + c) & 1

            @pl.when(moves & (c + 1 < n_chunks))
            def _next():
                fetch(piece, c + 1, 1 - slot)

            @pl.when(moves & (c + 1 == n_chunks))
            def _after():
                after(1 - slot)

            @pl.when(moves)
            def _wait():
                for cp in copies(piece, c, slot):
                    cp.wait()

            here = jnp.minimum(span - c * CHUNK, CHUNK)
            return body(slot, c * CHUNK - lead,
                        jnp.where(c == 0, lead >> 3, 0),
                        (here + (GROUP - 1)) // GROUP, acc)

        return lax.fori_loop(0, n_chunks, chunk, init)

    def over_groups(slot, lo, hi, group, init):
        """``init = group(piece, g, init)`` over the chunk's slot groups
        [lo, hi), piece by piece: group ``g`` of ``piece`` holds the chunk's
        slots piece x PIECE + g x GROUP + (0..GROUP-1).  Two loops, so that a
        slot's SMEM address is a piece's base, g x GROUP and a constant."""
        per = PIECE // GROUP

        def piece(pc, acc):
            return lax.fori_loop(
                jnp.maximum(lo - pc * per, 0), jnp.minimum(hi - pc * per, per),
                functools.partial(group, pc), acc)

        return lax.fori_loop(lo // per, (hi + (per - 1)) // per, piece, init)

    def dot_row(row, s0, after):
        """x . vec_sc as a (1, 1) vector: a masked multiply-add into a lane
        vector per nonzero, one cross-lane sum a row, which keeps its axes.
        The slots between a row's end and the next ALIGN boundary hold
        column 0, value 0."""
        def body(slot, j0, lo, hi, acc):
            del j0

            def group(pc, g, acc):
                for u in range(GROUP):
                    f = cbuf[slot, pc, 0, g * GROUP + u]
                    vj = vbuf[slot, pc, 0, g * GROUP + u]
                    row = vec_sc[pl.ds(f >> 7, 1)]              # (1, LANES)
                    acc = acc + row * jnp.where(
                        lane == (f & (LANES - 1)), vj, 0.0).astype(dtype)
                return acc

            return over_groups(slot, lo, hi, group, acc)

        return tile_total(over_row(row, s0, body,
                                   jnp.zeros((1, LANES), dtype), after))

    def axpy_row(row, s0, coef, after, moves=True):
        """vec_sc += coef x, ``coef`` a (1, 1) vector: a nonzero's value is
        splatted into the product, and a masked store writes the one lane
        the nonzero owns.  A row has no column twice, so within a group no
        store feeds a later slot's read and the group's reads all go first;
        a slot past the row's length (column 0, value 0) stores nothing: it
        would put back a lane read before this group's stores."""
        cnt = row[2] - row[1]

        def body(slot, j0, lo, hi, carry):
            def group(pc, g, carry):
                at = pc * PIECE + g * GROUP
                pairs = [(cbuf[slot, pc, 0, g * GROUP + u],
                          vbuf[slot, pc, 0, g * GROUP + u])
                         for u in range(GROUP)]
                rows = [vec_sc[pl.ds(f >> 7, 1)] for f, _ in pairs]
                for u, ((f, vj), row) in enumerate(zip(pairs, rows)):
                    pltpu.store(
                        vec_sc.at[pl.ds(_index(f >> 7), 1)],
                        row + (coef * vj).astype(dtype),
                        mask=(lane == (f & (LANES - 1)))
                        & (j0 + at + u < cnt))
                return carry

            return over_groups(slot, lo, hi, group, carry)

        over_row(row, s0, body, jnp.int32(0), after, moves)

    def put(ref, r, value):
        """Row ``r``'s (1, 1) result into its lane of the per-row block."""
        pltpu.store(ref.at[0, pl.ds(_index(r >> 7), 1)],
                    jnp.broadcast_to(value, (1, LANES)).astype(dtype),
                    mask=lane == (r & (LANES - 1)))

    # the block's first row is the one fetch nothing hides: the tables of
    # the block before are no longer in SMEM when its last row runs
    fetch(row_at(0)[0], 0, 0)

    def one_row(i, s0):
        """Row ``i`` of the block, its chunk 0 in flight in ring slot
        ``s0``; returns the slot the next row's is in flight in."""
        r = b * ROW_BLOCK + i                  # the row within the group
        row = row_at(i)
        n_chunks = row[3]

        def next_row(slot):
            @pl.when(i + 1 < ROW_BLOCK)
            def _start():
                after = jnp.minimum(i + 1, ROW_BLOCK - 1)
                fetch(itabs[0][0, after] >> 4, 0, slot)

        if mode == "dots":
            put(out, r, dot_row(row, s0, next_row))
            return (s0 + n_chunks) & 1
        if mode == "axpy":
            axpy_row(row, s0, splat(ftabs[0], i), next_row)
            return (s0 + n_chunks) & 1
        prev = itabs[2][0, i]
        y, qii, a0 = (splat(t, i) for t in ftabs[-3:])
        # a row this round already stepped on: alpha is that step's
        pj = jnp.maximum(prev, 0)
        prow = out[0, pl.ds(pj >> 7, 1)]
        a = jnp.where(prev >= 0,
                      lane_pick(prow, lane == (pj & (LANES - 1))), a0)
        # vec_sc holds v = w + sig_eff dw_k, or (split) dw_k beside
        # the table of x . w
        sig_eff = step_consts["sig_eff"]
        # a row of one chunk is walked once: the update reads the chunk
        # the dot left in the ring, and the next row's is already on its
        # way; a longer row's chunk 0 comes round again behind the dot
        again = n_chunks > 1

        def after_dot(slot):
            @pl.when(again)
            def _again():
                fetch(row[0], 0, slot)

            @pl.when(~again)
            def _on():
                next_row(slot)

        margin = dot_row(row, s0, after_dot)
        if split:
            margin = splat(ftabs[0], i) + sig_eff * margin
        new_a = losses.alpha_step(
            step_consts["loss"], a, y * margin, qii,
            step_consts["lam_n"], smoothing=step_consts["smoothing"])
        coef = y * (new_a - a) / step_consts["coef_div"]      # (1, 1)
        axpy_row(row, jnp.where(again, (s0 + n_chunks) & 1, s0),
                 coef if split else sig_eff * coef, next_row, again)
        put(out, r, new_a)
        return jnp.where(again, s0, 1 - s0)

    lax.fori_loop(0, ROW_BLOCK, one_row, jnp.int32(0))

    if vec_out is not None:
        @pl.when(last)
        def _flush():
            pltpu.sync_copy(vec_sc, vec_out)


def _call(mode: str, groups: int, rows: int, n_f: int, d_rows: int, dtype,
          interpret: bool, split: bool = False, **step_consts):
    """The ``pallas_call`` of one pass: ``groups`` x ``rows`` rows
    (``rows`` a multiple of ROW_BLOCK).  ``split``: the chain of
    :func:`margin_form`'s ``split`` form."""
    n_i = 3 if mode == "chain" else 2
    n_blocks = rows // ROW_BLOCK
    tab = pl.BlockSpec((1, ROW_BLOCK), lambda g, b: (0, g * n_blocks + b),
                       memory_space=pltpu.SMEM)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    per_row = pl.BlockSpec((1, rows // LANES, LANES), lambda g, b: (g, 0, 0))
    vec = jax.ShapeDtypeStruct((d_rows, LANES), dtype)
    out_specs, out_shape = [], []
    if mode != "axpy":
        out_specs.append(per_row)
        out_shape.append(jax.ShapeDtypeStruct(
            (groups, rows // LANES, LANES), dtype))
    if mode != "dots":
        out_specs.append(any_)
        out_shape.append(vec)
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode, n_f=n_f, split=split,
                          step_consts=step_consts),
        grid=(groups, rows // ROW_BLOCK),
        in_specs=[tab] * (n_i + n_f) + [any_, any_]
        + ([] if split else [any_]),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((d_rows, LANES), dtype),
            pltpu.SMEM((2, CHUNK_PIECES, 1, PIECE), jnp.int32),
            pltpu.SMEM((2, CHUNK_PIECES, 1, PIECE), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=f"pallas_longrows_{mode}",
    )


def _as_pieces(a):
    """(K, n_pieces, PIECE) as the kernels' (K x n_pieces, 1, PIECE): the
    same bytes (a piece is one full lane row), every piece its own tile, so
    a DMA may start at any piece."""
    return a.reshape(-1, 1, PIECE)


def _lane_blocked(vec, d_rows: int):
    return jnp.pad(vec, (0, d_rows * LANES - vec.shape[0])).reshape(
        d_rows, LANES)


def _row_tables(tables, rows: int):
    """(G, R) per-row tables padded to ``rows`` columns, as the one line
    (1, G x rows) whose ROW_BLOCK-wide blocks stream through SMEM."""
    return [jnp.pad(t, ((0, 0), (0, rows - t.shape[1]))).reshape(1, -1)
            for t in tables]


def _global_start(row_ptr, n_pieces: int):
    """(K, R) row starts within a shard's stream, in ALIGN-slot groups ->
    within the (K x n_pieces) stream the kernels see."""
    k = row_ptr.shape[0]
    if k * n_pieces * (PIECE // ALIGN) >= 1 << 31:
        raise ValueError(f"{k} x {n_pieces} pieces: a row's start no longer "
                         f"fits an int32")
    return row_ptr + (jnp.arange(k, dtype=jnp.int32)
                      * (n_pieces * (PIECE // ALIGN)))[:, None]


def _pad_rows(r: int) -> int:
    return -(-r // ROW_BLOCK) * ROW_BLOCK


def rows_dot(vec, sp_indices, sp_values, start, cnt, interpret: bool):
    """x_r . vec for the (G, R) rows that begin at pieces ``start`` (within
    the whole (K x n_pieces) stream) and hold ``cnt`` nonzeros: (G, R)."""
    g, r = start.shape
    rows, d_rows = _pad_rows(r), vec_rows(vec.shape[0])
    call = _call("dots", g, rows, 0, d_rows, vec.dtype, interpret)
    (out,) = call(*_row_tables([start, cnt], rows), _as_pieces(sp_indices),
                  _as_pieces(sp_values), _lane_blocked(vec, d_rows))
    return out.reshape(g, rows)[:, :r]


def rows_axpy(vec, sp_indices, sp_values, start, cnt, coefs,
              interpret: bool):
    """vec + sum_r coefs_r x_r over the (G, R) rows: (d,)."""
    g, r = start.shape
    rows, d_rows = _pad_rows(r), vec_rows(vec.shape[0])
    call = _call("axpy", g, rows, 1, d_rows, vec.dtype, interpret)
    (out,) = call(*_row_tables([start, cnt, coefs.astype(vec.dtype)], rows),
                  _as_pieces(sp_indices), _as_pieces(sp_values),
                  _lane_blocked(vec, d_rows))
    return out.reshape(-1)[:vec.shape[0]]


def chunk_fill(row_ptr, row_len) -> float:
    """Of the slots the ring moves in one pass over every row, the share
    that hold a nonzero, counted on the host: a row takes the chunks from
    the piece it starts in to its last slot, one even where it holds
    nothing (``row_ptr``: a row's first slot / ALIGN)."""
    row_len = np.asarray(row_len, np.int64)
    lead = np.asarray(row_ptr, np.int64) % (PIECE // ALIGN) * ALIGN
    moved = np.maximum(-(-(lead + row_len) // CHUNK), 1).sum() * CHUNK
    return float(row_len.sum() / moved) if moved else 1.0


def shard_margins(w, shard: dict, interpret: bool):
    """x_i . w for every row of the stacked (K, ..) piece shards."""
    idx = shard["sp_indices"]
    return rows_dot(w, idx, shard["sp_values"],
                    _global_start(shard["sp_row_ptr"], idx.shape[1]),
                    shard["sp_row_len"], interpret)


def shards_axpy(coefs, shards: dict, vec, interpret: bool):
    idx = shards["sp_indices"]
    return rows_axpy(vec, idx, shards["sp_values"],
                     _global_start(shards["sp_row_ptr"], idx.shape[1]),
                     shards["sp_row_len"], coefs, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing"),
)
def pallas_longrows_round(
    w: jax.Array,            # (d,) the round's primal vector
    alpha: jax.Array,        # (K, n_shard)
    sp_indices: jax.Array,   # (K, n_pieces, PIECE) int32
    sp_values: jax.Array,    # (K, n_pieces, PIECE)
    row_ptr: jax.Array,      # (K, n_shard) int32: a row's first piece
    row_len: jax.Array,      # (K, n_shard) int32: its nonzeros
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
):
    """One SDCA round for K shards of piece rows on this chip, one shard
    after another.  Returns ``(dw_sum (d,), alpha_inner (K, n_shard))`` as
    ``pallas_sparse_hbm_round`` does."""
    from cocoa_tpu.ops.pallas_sparse_hbm import _link_repeats
    from cocoa_tpu.telemetry.tracing import (SCOPE_LOCAL_SOLVE,
                                             SCOPE_SPARSE_GATHER)

    k, n_pieces, _ = sp_indices.shape
    h, d, dtype = idxs.shape[1], w.shape[0], w.dtype
    check_dtype(dtype)
    sig_eff, qii_factor = mode_factors(mode, sigma)
    rows, d_rows = _pad_rows(h), vec_rows(d)
    idxs = idxs.astype(jnp.int32)
    at = lambda a: jnp.take_along_axis(a, idxs, 1)  # noqa: E731
    with jax.named_scope(SCOPE_SPARSE_GATHER):
        start = _global_start(at(row_ptr), n_pieces)
        cnt = at(row_len)
        live = jnp.ones((h,), bool)
        prev, last = jax.vmap(lambda i: _link_repeats(i, live))(idxs)
        ftabs = [at(labels), at(sq_norms) * qii_factor, at(alpha)]
    with jax.named_scope(SCOPE_LOCAL_SOLVE):
        split = margin_form(mode) == "split"
        chain = _call(
            "chain", 1, rows, len(ftabs) + split, d_rows, dtype, interpret,
            split=split,
            lam_n=float(lam * n), coef_div=float(coef_divisor(mode, lam * n)),
            sig_eff=float(sig_eff),
            loss=losses.validate(loss, smoothing),
            smoothing=float(smoothing))
        cols, vals = _as_pieces(sp_indices), _as_pieces(sp_values)
        if split:
            # x_i . w of the round's rows, a pass of its own: the chain
            # starts from zeros and its vector is dw_k alone
            ftabs.insert(0, rows_dot(w, sp_indices, sp_values, start, cnt,
                                     interpret))
            vec_in = ()
        else:
            vec_in = (_lane_blocked(w, d_rows),)

        def one_shard(dw_sum, xs):
            a_new, vec = chain(*_row_tables([x[None] for x in xs], rows),
                               cols, vals, *vec_in)
            if not split:
                # the chain ran from w and returns v = w + sig_eff dw_k: a
                # column no step touched still holds w's own bits, so dw_k
                # is exactly 0 there
                vec = (vec - vec_in[0]) / sig_eff
            return dw_sum + vec, a_new.reshape(-1)[:h]

        dw_sum, a_new = lax.scan(
            one_shard, jnp.zeros((d_rows, LANES), dtype),
            (start, cnt, prev.astype(jnp.int32), *ftabs))
    with jax.named_scope(SCOPE_SPARSE_GATHER):
        n_shard = alpha.shape[1]
        alpha = jax.vmap(lambda a, i, keep, new: a.at[
            jnp.where(keep, i, n_shard)].set(new, mode="drop"))(
                alpha, idxs, last, a_new)
    return dw_sum.reshape(-1)[:d], alpha
