"""Pallas TPU kernel for the block-coordinate scalar recurrence.

The block-coordinate inner solver (ops/local_sdca.local_sdca_block) reduces
each coordinate step's O(d) sequential work to O(B) — margins read cached
block Gram entries instead of re-dotting Δw (the hot-loop contract is
CoCoA.scala:148-188; the restructuring is exact, see that docstring).  But
under plain XLA each of the B chained steps still costs ~µs of loop
overhead, which is the same price the O(d) sequential kernels pay — the
blocking buys nothing (measured: 31 ms/round vs the sequential Pallas
kernel's ~9 ms at epsilon scale).

This kernel runs the whole B-step recurrence inside one ``pallas_call``
with every operand VMEM-resident and ZERO dynamic HBM traffic in the chain,
and — the part that actually wins — advances ALL K logical shards' chains
in lockstep inside one kernel instance:

- the per-step scalars (margins0, y, ‖x‖²·qf, α₀, X_B·Δw, live-mask) of
  every shard arrive lane-blocked as one (6K, B) tile; a single masked
  reduce yields the step-j column for all shards at once;
- the Gram row for step j arrives for all shards from ONE dynamic sublane
  slice of a precomputed (B, 2K, B) operand (gram is symmetric, so row j ==
  column j), concatenated with the equality rows (below);
- within-block duplicate draws are exact through the equality tiles
  ``eq_k[i, j] = (idx_i == idx_j)``: the live α for step j is
  ``α₀[j] + Σ_i δ_i·eq[i, j]`` — δ_i is zero for i ≥ j, so the sum ranges
  over earlier same-index steps only, exactly the sequential recurrence
  (cross-block duplicates are the caller's additive α scatter);
- the running (2K, B) coef/δ rows live in loop-carried vector registers;
- the coordinate update itself is elementwise on (K, 1) columns — one
  evaluation serves every shard.

Per step that is ~a dozen small VPU ops and one sublane slice FOR ALL K
CHAINS — hundreds of ns where the sequential kernels pay ~1.7 µs per
lockstep — while the O(B·d) tile work (row gathers, Gram matrices, Δw
apply) stays outside in XLA where it lands on the MXU
(local_sdca_block_batched).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.ops import losses

LANES = 128
SCAL_ROWS = 6  # [margins0 | labels | qii | alpha0 | mb | live-mask]
CHAIN_VMEM_BUDGET = 12 << 20  # leave ~4 MB of the ~16 MB VMEM for Mosaic

# ``--blockSize=auto`` candidates in preference order.  At the epsilon
# shape (400,000 x 2,000, K=8) 128 is the only tile that rides the fused
# kernel: 256 already fails fused_fits there and falls to the split
# path; 512 additionally fails chain_fits at K=8 and falls all the way
# to the XLA chain.  The resolver (solvers/cocoa.py auto_block_size)
# walks this ranking and takes the FIRST candidate that passes the same
# fit accounting the dispatch layer uses — a ranked choice, not
# largest-that-fits.  No cell runs THIS block family (the T = 1 kernels
# behind --blockSize), so the ranking has no number on record (ROADMAP D4);
# the block path a cell does run, ilsvrc1k's T class models on the lanes
# (ops/block_lanes.py, PR 53), derives its B from its own fit and shares
# only the restructuring (ops/local_sdca.local_sdca_block) with these.
BLOCK_SIZE_PREFERENCE = (128, 256, 512)


def chain_vmem_estimate(k: int, b: int, itemsize: int) -> int:
    """Rough VMEM working set of one chain_block_batched instance: the
    (B, 2K, B) gq operand, the (6K, B) scal input + prologue copy, the
    (2K, B) carry + outputs."""
    return itemsize * (2 * k * b * b + 12 * k * b + 6 * k * b)


def chain_fits(k: int, b: int, itemsize: int) -> bool:
    return chain_vmem_estimate(k, b, itemsize) <= CHAIN_VMEM_BUDGET


def _chain_loop(b, unroll, step, init):
    """B dependent steps as a partially-unrolled fori_loop: ``unroll``
    consecutive steps per loop iteration as straight-line code, so Mosaic
    can hoist/pipeline each step's state-independent slices (the gq row,
    the prologue column) around its neighbours' dependent scalar ops.
    Swept on hardware — see DEFAULT_UNROLL."""
    if unroll <= 1:
        return jax.lax.fori_loop(0, b, step, init)
    assert b % unroll == 0, (b, unroll)

    def group(g, cd):
        for u in range(unroll):
            cd = step(g * unroll + u, cd)
        return cd

    return jax.lax.fori_loop(0, b // unroll, group, init)


def _chain_kernel_batched(scal_ref, gq_ref, delta_ref, coef_ref, *,
                          k, b, lam_n, coef_div, sig_eff, frozen, loss,
                          smoothing, unroll=1):
    """All K shards' B-step chains advance in lockstep: one masked reduce
    yields every shard's step scalars as a (·K, 1) column, one dynamic
    sublane slice of the (B, 2K, B) gq operand yields every shard's
    Gram AND duplicate-equality rows at once, one fused (2K, B)
    multiply-reduce forms both chain dots, and the coordinate update
    itself is elementwise on (K, 1) columns — the per-step latency is that
    of ONE chain."""
    gw = k if frozen else 2 * k   # frozen gq carries only the eq rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    dtype = scal_ref.dtype
    scal = scal_ref[...]            # (6K, b)
    zero = jnp.zeros((2 * k, b), dtype)
    one = jnp.asarray(1.0, dtype)

    if loss == "hinge":
        # Hinge collapses algebraically: for qii > 0 the reference's
        # projected-gradient + vanishing-gradient branches are reproduced
        # exactly by the plain clip (at a boundary the clip re-pins α
        # wherever the projection would have zeroed the step), and for
        # qii == 0 the rule is the constant 1 (z = 0 ⇒ grad = −λn ≠ 0).
        # That lets every per-step constant hoist into a vectorized
        # prologue — the chained work per step is the two dots, one clip,
        # and one masked write:
        #     u_j  = a_j − (base_j + S_j·(c·G row j)),  a_j = a0_j + δ·eq row j
        #     α'_j = qii>0 ? clip(u_j, 0, 1) : 1
        m0, y, qii, a0, mb, live = (scal[i * k:(i + 1) * k]
                                    for i in range(6))
        q_safe = jnp.where(qii != 0.0, qii, one)
        base = (y * (m0 + sig_eff * mb) - 1.0) * lam_n / q_safe
        s_row = y * (sig_eff * lam_n) / q_safe
        fac = jnp.concatenate([y * (live / coef_div), live], axis=0)
        pre = jnp.concatenate(
            [base, s_row, a0, jnp.where(qii != 0.0, one, 0.0), fac], axis=0
        )  # (6K, b): [base | S | a0 | qflag | Yl | Ll]

        def step(j, cd):            # cd rows: [coefs_0..K-1 | delta_0..K-1]
            mask = lane == j
            sv = jnp.sum(jnp.where(mask, pre, 0.0), axis=1, keepdims=True)
            gq = gq_ref[pl.ds(j, 1)].reshape(gw, b)
            dots = jnp.sum(cd[2 * k - gw:] * gq, axis=1, keepdims=True)
            a = sv[2 * k:3 * k] + dots[gw - k:]
            u = a - sv[:k]
            if not frozen:
                u = u - sv[k:2 * k] * dots[:k]
            new_a = jnp.where(sv[3 * k:4 * k] > 0.0,
                              jnp.clip(u, 0.0, 1.0), one)
            dm = new_a - a
            upd = sv[4 * k:] * jnp.concatenate([dm, dm], axis=0)
            return jnp.where(mask, upd, cd)

        cd = _chain_loop(b, unroll, step, zero)
        coef_ref[...] = cd[:k]
        delta_ref[...] = cd[k:]
        return

    def step(j, cd):                # cd rows: [coefs_0..K-1 | delta_0..K-1]
        mask = lane == j
        sv = jnp.sum(jnp.where(mask, scal, 0.0), axis=1, keepdims=True)
        m0, y, qii, a0, mb, live = (sv[i * k:(i + 1) * k] for i in range(6))
        gq = gq_ref[pl.ds(j, 1)].reshape(gw, b)
        dots = jnp.sum(cd[2 * k - gw:] * gq, axis=1, keepdims=True)
        if frozen:
            margin = m0
        else:
            margin = m0 + sig_eff * (mb + dots[:k])
        a = a0 + dots[gw - k:]
        new_a = losses.alpha_step(loss, a, y * margin, qii, lam_n,
                                  smoothing=smoothing)
        d_j = (new_a - a) * live
        c_j = y * d_j / coef_div
        return jnp.where(mask, jnp.concatenate([c_j, d_j], axis=0), cd)

    cd = _chain_loop(b, unroll, step, zero)
    coef_ref[...] = cd[:k]
    delta_ref[...] = cd[k:]


DEFAULT_UNROLL = 8    # swept on v5e through the real chunked driver
                      # (epsilon fused config, B=128): 8 → 3.4-3.8
                      # ms/round, 32 → 4.3; a synthetic harness preferred
                      # 32, the production index stream prefers 8.
                      # Re-swept round 5 on the distinct path: 4 → 3.47,
                      # 8 → 3.21, 16 → 3.18, 32 → 3.52 — 8 and 16 tie
                      # within run-to-run noise; 8 stays


@functools.partial(
    jax.jit,
    static_argnames=("lam_n", "coef_div", "sig_eff", "frozen", "loss",
                     "smoothing", "interpret", "unroll"),
)
def chain_block_batched(
    scal: jax.Array,   # (K, 6, B): [m0 | y | qii | alpha0 | mb | mask]
    gq: jax.Array,     # (B, 2K, B) fused Gram+equality operand:
                       # gq[j, k, i] = x_i·x_j of shard k (transposed Gram,
                       # einsum("kjd,kid->jki")), gq[j, K+k, i] =
                       # (idx_i == idx_j); frozen mode passes (B, K, B)
                       # with only the equality rows
    lam_n: float,
    coef_div: float,
    sig_eff: float,
    frozen: bool,
    loss: str,
    smoothing: float,
    interpret: bool = False,
    unroll: int = DEFAULT_UNROLL,
):
    """Run one block's B-step recurrence for K shards in lockstep.
    Returns ``(delta, coefs)``, both (K, B): per-step α deltas (for the
    caller's additive scatter — duplicate-safe by construction) and Δw
    coefficients (for the caller's ``coefs·X_B`` apply).  B must be a
    multiple of 128 (whole lane tiles; also of ``unroll``)."""
    k, nrows, b = scal.shape
    if nrows != SCAL_ROWS:
        raise ValueError(f"scal must carry {SCAL_ROWS} metric rows, "
                         f"got {nrows}")
    if b % LANES:
        raise ValueError(f"chain_block_batched needs B % {LANES} == 0, "
                         f"got {b}")
    if gq.shape != (b, (k if frozen else 2 * k), b):
        raise ValueError(f"gq shape {gq.shape} does not match frozen={frozen}")
    # (K, 6, B) -> (6K, B) grouped by metric so the kernel's static column
    # slices are [m0_0..m0_K-1 | y_0.. | ...]
    scal_rows = scal.transpose(1, 0, 2).reshape(SCAL_ROWS * k, b)
    kernel = functools.partial(
        _chain_kernel_batched, k=k, b=b, lam_n=lam_n, coef_div=coef_div,
        sig_eff=sig_eff, frozen=frozen,
        loss=losses.validate(loss, smoothing), smoothing=smoothing,
        unroll=(unroll if b % max(unroll, 1) == 0 else 1),
    )
    delta, coefs = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((k, b), scal.dtype),
            jax.ShapeDtypeStruct((k, b), scal.dtype),
        ],
        interpret=interpret,
        name="pallas_chain_block",
    )(scal_rows, gq)
    return delta, coefs


# ---------------------------------------------------------------------------
# Fused per-block kernel: Gram + margins + equality + chain + Δw update in
# ONE pallas_call.
# ---------------------------------------------------------------------------
#
# Profiling the split design (XLA einsums around a chain-only kernel) on a
# v5e showed the chain itself is CHEAP (~0.46 ms/round at epsilon scale,
# ~90 ns per lockstep step) and the round time is dominated by XLA-side
# materialization the kernel boundary forces: the (B, 2K, B) Gram+equality
# concat (3 big HBM copies), the equality-tile broadcast-compare (168 MB
# written per round), the transposing Gram einsum epilogue, and streaming
# the fused operand back in.  Hoisting that work out of the scan made it
# WORSE (7.8 vs 4.7 ms): the tiles cost more to materialize than their
# serialization ever cost.  The fix is to stop materializing: this kernel
# consumes the (K, B, d) gathered row tile directly and keeps every
# intermediate — Gram, margins, equality, the chain carry — in VMEM.
#
# VMEM is 16 MiB (measured; a 15.9 MB scratch compiles, 16 MB does not),
# and a (K, B, d) f32 tile at epsilon scale (8, 128, 2000) is 8.2 MB —
# too big to double-buffer.  So the grid is (2,) over B-HALVES: each grid
# step streams a (K, B/2, d) half-tile (4.1 MB, auto-double-buffered by
# Mosaic's pipeline), the first half parks in scratch, and the Gram
# assembles from the four half products on the MXU.  The equality tile is
# ONE broadcast compare of the f32-cast indices (no scalar reads), margins
# are one batched matvec against the caller-combined v = w + σ·Δw, and the
# Δw update leaves as a (K, d) MXU product of the coefficients against the
# two halves.  The only per-round work left outside is the row gather, the
# α gather/scatter (XLA's scatter beats in-kernel dynamic picks at ~11 ns
# per scalar-addressed op), and the (K, d) Δw add.


FUSED_VMEM_BUDGET = 14 << 20   # hard cap 16 MiB; leave ~2 MiB for Mosaic


def fused_vmem_estimate(k: int, b: int, d: int, itemsize: int) -> int:
    """Working set of one fused_block instance: the double-buffered
    (K, B/2, d) half-tile operand + the parked first half, the (K, B, B)
    Gram and equality scratch, the (K, d) v operand and Δw-update output
    (double-buffered), and the small per-draw vectors."""
    half = k * (b // 2) * d
    return itemsize * (
        3 * half            # operand double-buffer + s0 scratch
        + 2 * k * b * b     # gram + eq scratch
        + 4 * k * d         # v in + dwu out, double-buffered
        + 16 * k * b        # idxf/yb/qb/a0/live + pre + carry + delta
    )


def fused_fits(k: int, b: int, d: int, itemsize: int,
               n_shard: int = 0) -> bool:
    return (
        b % LANES == 0
        and (b // 2) % 8 == 0
        and itemsize == 4
        # the in-kernel equality compare runs on f32-cast indices — only
        # exact below 2^24 (the legacy path compares integers)
        and n_shard < (1 << 24)
        and fused_vmem_estimate(k, b, d, itemsize) <= FUSED_VMEM_BUDGET
    )


def _fused_kernel(xb_ref, idxf_ref, idxft_ref, yb_ref, qb_ref, a0_ref,
                  live_ref, v_ref, delta_ref, dwu_ref, s0_ref, gram_ref,
                  eq_ref, mb_ref, *, k, b, d, lam_n, coef_div, sig_eff,
                  frozen, loss, smoothing, unroll):
    """Grid (2,) over B-halves.  Step 0 parks its half-tile and computes
    the half-products that need no second half; step 1 completes the Gram,
    runs the chain, and emits (delta, Δw update).

    Layout rules (Mosaic): the Gram/equality scratches are j-LEADING
    (B, K, B) so the chain's per-step row read is a leading-dim dynamic
    sublane slice (``ref[pl.ds(j, 1)]``) — dynamic slicing a middle dim
    lowers to an unsupported gather.  Gram pieces are therefore computed
    per shard (static k) as plain 2D MXU matmuls and stored with a static
    middle index; the margins use a VPU lane-reduce (the matvec is 128K
    MACs — not worth an MXU lowering's layout constraints); the equality
    tile is one broadcast compare of the two index layouts the caller
    provides (f32 row-major and its transpose), so nothing transposes
    in-kernel."""
    h = pl.program_id(0)
    b2 = b // 2
    dtype = xb_ref.dtype
    # Precision: the Gram/margin products at DEFAULT measure EXACT against
    # the sequential path (da error 0.0 at epsilon scale), but the
    # vector-matrix Δw-update products lowered with ~bf16 error (2.9e-3
    # relative — enough to stall the duality gap at ~3e-4, since the
    # certificate rests on w = (1/λn)·Σyαx staying tight).  HIGHEST on
    # everything OOMs the 16 MiB VMEM by ~1 MB of matmul temps at the
    # k=8/B=128/d=2000 flagship shape, so it is applied ONLY where the
    # error was measured: the dwu dots.
    prec = jax.lax.Precision.HIGHEST
    dot2 = lambda a_, b_: jax.lax.dot_general(  # noqa: E731
        a_, b_, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dtype)

    def margins_half(lo):
        # mb[kk, lo:lo+b2] = x_kk · v_kk  (VPU lane reduce per shard)
        for kk in range(k):
            x_kk = xb_ref[kk]                         # (B/2, d)
            v_kk = v_ref[kk:kk + 1, :]                # (1, d)
            mb_ref[kk:kk + 1, lo:lo + b2] = jnp.sum(
                x_kk * v_kk, axis=1)[None, :]

    @pl.when(h == 0)
    def _first_half():
        s0_ref[...] = xb_ref[...]
        # equality tile in one vectorized compare — no scalar reads, no
        # in-kernel transpose: eq[j, kk, i] = (idx_i == idx_j) in shard kk
        eq_ref[...] = (idxft_ref[...][:, :, None]
                       == idxf_ref[...][None, :, :]).astype(dtype)
        margins_half(0)
        if not frozen:
            for kk in range(k):
                g = dot2(xb_ref[kk], xb_ref[kk])      # (B/2, B/2)
                gram_ref[0:b2, kk, 0:b2] = g

    @pl.when(h == 1)
    def _second_half():
        margins_half(b2)
        if not frozen:
            for kk in range(k):
                s0_kk = s0_ref[kk]
                x1_kk = xb_ref[kk]
                gram_ref[0:b2, kk, b2:b] = dot2(s0_kk, x1_kk)
                gram_ref[b2:b, kk, 0:b2] = dot2(x1_kk, s0_kk)
                gram_ref[b2:b, kk, b2:b] = dot2(x1_kk, x1_kk)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
        one = jnp.asarray(1.0, dtype)
        zero = jnp.zeros((2 * k, b), dtype)
        m0 = mb_ref[...]
        y = yb_ref[...]
        qii = qb_ref[...]
        a0 = a0_ref[...]
        live = live_ref[...]

        if loss == "hinge":
            # same algebraic collapse as _chain_kernel_batched: constants
            # hoist into a prologue, the chain is two dots + clip + write
            q_safe = jnp.where(qii != 0.0, qii, one)
            base = (y * m0 - 1.0) * lam_n / q_safe
            s_row = y * (sig_eff * lam_n) / q_safe
            fac = jnp.concatenate([y * (live / coef_div), live], axis=0)
            pre = jnp.concatenate(
                [base, s_row, a0, jnp.where(qii != 0.0, one, 0.0), fac],
                axis=0,
            )  # (6K, B)

            def step(j, cd):
                mask = lane == j
                sv = jnp.sum(jnp.where(mask, pre, 0.0), axis=1,
                             keepdims=True)
                eqr = eq_ref[pl.ds(j, 1)].reshape(k, b)
                ddot = jnp.sum(cd[k:] * eqr, axis=1, keepdims=True)
                a = sv[2 * k:3 * k] + ddot
                u = a - sv[:k]
                if not frozen:
                    gr = gram_ref[pl.ds(j, 1)].reshape(k, b)
                    u = u - sv[k:2 * k] * jnp.sum(cd[:k] * gr, axis=1,
                                                  keepdims=True)
                new_a = jnp.where(sv[3 * k:4 * k] > 0.0,
                                  jnp.clip(u, 0.0, 1.0), one)
                dm = new_a - a
                upd = sv[4 * k:] * jnp.concatenate([dm, dm], axis=0)
                return jnp.where(mask, upd, cd)

        else:
            scal = jnp.concatenate([m0, y, qii, a0, live], axis=0)

            def step(j, cd):
                mask = lane == j
                sv = jnp.sum(jnp.where(mask, scal, 0.0), axis=1,
                             keepdims=True)
                m0j, yj, qj, a0j, livej = (sv[i * k:(i + 1) * k]
                                           for i in range(5))
                eqr = eq_ref[pl.ds(j, 1)].reshape(k, b)
                a = a0j + jnp.sum(cd[k:] * eqr, axis=1, keepdims=True)
                margin = m0j
                if not frozen:
                    gr = gram_ref[pl.ds(j, 1)].reshape(k, b)
                    margin = margin + sig_eff * jnp.sum(
                        cd[:k] * gr, axis=1, keepdims=True)
                new_a = losses.alpha_step(loss, a, yj * margin, qj, lam_n,
                                          smoothing=smoothing)
                d_j = (new_a - a) * livej
                c_j = yj * d_j / coef_div
                return jnp.where(mask, jnp.concatenate([c_j, d_j], axis=0),
                                 cd)

        cd = _chain_loop(b, unroll, step, zero)
        delta_ref[...] = cd[k:]
        coefs = cd[:k]                                # (K, B)
        for kk in range(k):
            dwu_ref[kk:kk + 1, :] = (
                jax.lax.dot_general(
                    coefs[kk:kk + 1, :b2], s0_ref[kk],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec,
                )
                + jax.lax.dot_general(
                    coefs[kk:kk + 1, b2:], xb_ref[kk],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec,
                )
            ).astype(dtype)


@functools.partial(
    jax.jit,
    static_argnames=("lam_n", "coef_div", "sig_eff", "frozen", "loss",
                     "smoothing", "interpret", "unroll"),
)
def fused_block(
    xb: jax.Array,     # (K, B, d) gathered row tile
    idxf: jax.Array,   # (K, B) f32-cast sampled indices (exact < 2^24)
    yb: jax.Array,     # (K, B) labels
    qb: jax.Array,     # (K, B) qii = ||x||^2 * qii_factor
    a0: jax.Array,     # (K, B) alpha at block start
    live: jax.Array,   # (K, B) 1.0 for real steps, 0.0 for padding
    v: jax.Array,      # (K, d) margin vector: w + sig_eff * dw_blockstart
                       # (just w broadcast for frozen mode)
    lam_n: float,
    coef_div: float,
    sig_eff: float,
    frozen: bool,
    loss: str,
    smoothing: float,
    interpret: bool = False,
    unroll: int = DEFAULT_UNROLL,
):
    """One fused block step: margins, Gram, equality, the B-step chain, and
    the Δw update in a single kernel.  Returns (delta (K, B), dwu (K, d)):
    per-step α deltas (additive-scatter-safe) and the block's Δw increment
    Σ_j c_j·x_j."""
    k, b, d = xb.shape
    if b % LANES or (b // 2) % 8:
        raise ValueError(f"fused_block needs B % {LANES} == 0, got {b}")
    kernel = functools.partial(
        _fused_kernel, k=k, b=b, d=d, lam_n=lam_n, coef_div=coef_div,
        sig_eff=sig_eff, frozen=frozen,
        loss=losses.validate(loss, smoothing), smoothing=smoothing,
        unroll=(unroll if b % max(unroll, 1) == 0 else 1),
    )
    b2 = b // 2
    full = lambda s: pl.BlockSpec(s, lambda h: (0,) * len(s))  # noqa: E731
    delta, dwu = pl.pallas_call(
        kernel,
        grid=(2,),
        in_specs=[
            pl.BlockSpec((k, b2, d), lambda h: (0, h, 0)),
            full((k, b)), full((b, k)), full((k, b)), full((k, b)),
            full((k, b)), full((k, b)), full((k, d)),
        ],
        out_specs=[full((k, b)), full((k, d))],
        out_shape=[
            jax.ShapeDtypeStruct((k, b), xb.dtype),
            jax.ShapeDtypeStruct((k, d), xb.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((k, b2, d), xb.dtype),   # parked first half
            pltpu.VMEM((b, k, b), xb.dtype),    # gram, j-leading
            pltpu.VMEM((b, k, b), xb.dtype),    # eq, j-leading
            pltpu.VMEM((k, b), xb.dtype),       # margins
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="pallas_chain_fused_block",
    )(xb, idxf, idxf.T, yb, qb, a0, live, v)
    return delta, dwu
