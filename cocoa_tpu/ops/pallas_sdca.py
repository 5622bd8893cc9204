"""Pallas TPU kernel for the sequential SDCA inner loop (dense layout).

The H coordinate steps of local SDCA are inherently sequential
(CoCoA.scala:148-188); under plain XLA each step pays HBM round-trips for
the row gather and the Δw update.  This kernel keeps the hot state — the Δw
accumulator and the shard's α vector — resident in VMEM scratch across all
H steps and lets Pallas's grid pipeline prefetch each sampled row HBM→VMEM
(double-buffered) while the previous step computes.

Uses the margins decomposition (ops/local_sdca.py ``mode_factors``): the
per-step margin is ``x·w₀ + sig_eff·(x·Δw)``, with **both dots computed
in-kernel** against the VMEM-resident w₀ and Δw.  Round 3 precomputed
margins0 = X·w₀ as one MXU matvec per round instead; round 4 retired it:
the sampled row is already in VMEM for the axpy, so the w₀ dot is one more
VPU reduce on data the step touches anyway (measured: free — scalar
address generation bounds the step), while the matvec reads ALL of X every
round — at localIterFrac = 0.1 that is 10× the rows the round touches
(~90% of the demo round's HBM traffic, ~4 ms/round at epsilon scale).
The sparse kernel (ops/pallas_sparse.py) has computed margins in-kernel
since round 2 for the same reason.  Per step the kernel does the two row
dots, scalar box-projection logic, one row axpy, and an α write.

**Folded rows.**  A (1, d) row uses one sublane — 1/8 of the VPU.  The
caller reinterprets each dense row as an (8, d/8) tile instead (a free
reshape: the row is contiguous in HBM), so the per-step O(d) work — the
Δw dot and the axpy — runs at full VPU width, and the sampled row is its
own tile-aligned DMA unit (no sublane-alignment tricks).  Requires
d % 8 == 0; ``shard_dataset`` pads dense feature columns to a multiple of 8
(zero columns touch nothing), and the wrapper pads on the fly otherwise.

**Step groups.**  Grid is (K, ceil(H/S)): shard-major, step groups inner
(TPU grids execute sequentially with the last dimension fastest, which is
exactly the dependency order).  Each grid iteration runs S sequential
coordinate steps (unrolled in the kernel body) against S independently-
prefetched row blocks, amortizing per-grid-step fixed costs — grid
bookkeeping, DMA issue, pipeline bubbles — over S steps.  Groups past H
(when S ∤ H) clamp their row index and zero their update — inert, any H
works.

**Lane-blocked scalar access.**  TPU vectors have no cheap dynamic lane
indexing; reading a per-step scalar (y, ‖x‖², margins0[idx], α[idx]) with a
full-width iota-mask reduce costs O(n_shard) VPU work per step, which at
epsilon scale (n_shard = 100K) would dwarf the O(d) coordinate update.
Instead, the per-shard vectors are laid out as (n_shard/128, 128) — lane
blocks — so a scalar read is a *dynamic sublane slice* (legal and cheap) of
one (1, 128) row followed by a 128-wide mask pick, and the α write masks
one (1, 128) row.  Per-step cost is O(d + 128) regardless of shard size.

Block/alignment rules used:

- the sampled row arrives as a (1, 1, 8, d/8) block of the folded
  (K, n_shard, 8, d/8) X, selected by ``idxs`` via scalar prefetch;
- the per-shard vectors arrive as ``(1, n_blocks, 128)`` blocks selected by
  the grid's k index (their second-to-last dim is the full axis, which is
  always legal); they stay VMEM-resident across that shard's H steps and
  re-DMA only when k advances;
- outputs (Δw, α) are per-shard blocks too: the kernel writes them at the
  shard's last step and Pallas flushes each block to HBM when the grid
  moves to the next shard — no cross-shard masking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors

LANES = 128
SUBLANES = 8  # f32 sublane count: rows fold to (8, d/8)
VMEM_BUDGET = 12 << 20  # leave ~4 MB of the ~16 MB VMEM for the compiler
UNROLL_CANDIDATES = (16, 8, 4, 2, 1)


def check_dtype(dtype) -> None:
    """2-byte dtypes are rejected: bf16 SDCA can't certify a 1e-4 duality
    gap anyway, and the folded-row layout assumes 8-sublane (4-byte) tiling
    (use the fori_loop path, which handles bf16).  f32 is the TPU path; f64
    works in interpret mode (the x64 validation tests)."""
    if jnp.dtype(dtype).itemsize < 4:
        raise ValueError(
            f"the Pallas SDCA kernel does not support 2-byte dtypes, got "
            f"{jnp.dtype(dtype).name}; use math='fast' without pallas"
        )


def vmem_estimate(n_shard: int, d: int, itemsize: int, unroll: int) -> int:
    """Rough VMEM working set of the kernel: the lane-concatenated stacked
    state (3·n_pad input, double-buffered across the k advance, + 3·n_pad
    scratch) + the α output (double-buffered) — 11 n_pad-vectors total —
    the w₀ operand, the Δw scratch/output plus temporaries (~5 d-vectors),
    and ``unroll`` double-buffered folded row blocks."""
    n_pad = -(-n_shard // LANES) * LANES
    return itemsize * (11 * n_pad + (2 * unroll + 5) * d)


def pick_unroll(n_shard: int, d: int, itemsize: int, h: int) -> int:
    """Largest step-group size whose row blocks still fit the VMEM budget
    (0 if even S=1 does not fit — caller should stay on the fori_loop
    path)."""
    for s in UNROLL_CANDIDATES:
        if s <= max(1, h) and vmem_estimate(n_shard, d, itemsize, s) <= VMEM_BUDGET:
            return s
    return 0


INTERLEAVE_BUDGET = 14 << 20  # measured headroom: flush-only outputs and the
                              # constant-block stacked input are not all
                              # double-buffered, so this can run closer to
                              # the 16 MB physical VMEM than VMEM_BUDGET


def interleave_vmem_estimate(k: int, n_shard: int, d: int, itemsize: int,
                             unroll: int) -> int:
    """Working set of the shard-interleaved kernel: ALL K shards' stacked
    state resident at once (3·n_pad input + 3·n_pad scratch each), the w₀
    operand, the Δw accumulators/outputs, and K·unroll double-buffered row
    blocks."""
    n_pad = -(-n_shard // LANES) * LANES
    return itemsize * (6 * k * n_pad + 3 * k * d + d + 2 * k * unroll * d)


def pick_interleave(k: int, n_shard: int, d: int, itemsize: int, h: int) -> int:
    """Step-group size for the interleaved kernel (0 = does not fit or
    nothing to interleave; use the shard-major kernel)."""
    if k <= 1:
        return 0
    for s in (2, 1):
        if s <= max(1, h) and interleave_vmem_estimate(
                k, n_shard, d, itemsize, s) <= INTERLEAVE_BUDGET:
            return s
    return 0


def fold_rows(X: jax.Array) -> jax.Array:
    """(K, n_shard, d) -> (K, n_shard, 8, d/8): the kernel's folded-row
    operand.  The fold is a physical relayout on TPU (the 3-D and 4-D tiled
    layouts differ), so hot paths call this ONCE per dispatch — outside
    ``lax.scan``/``lax.while_loop`` — and pass the folded array through the
    loop; folding inside the round body would relayout the whole X every
    round (measured: 2×0.3 ms/round at demo scale, the entire kernel's cost
    many times over)."""
    k, n_shard, d = X.shape
    if d % SUBLANES:
        X = jnp.pad(X, ((0, 0), (0, 0), (0, SUBLANES - d % SUBLANES)))
        d = X.shape[-1]
    return X.reshape(k, n_shard, SUBLANES, d // SUBLANES)


STACK = 3  # lane-concatenated per-shard rows: [labels, sqn, alpha]


def _step_body(srow, sub_lane, live, x, dw_k, w_k, *, frozen, sig_eff,
               qii_factor, lam_n, coef_div, loss, smoothing):
    """One coordinate step given the (1, 3·LANES) lane-concatenated state
    row (labels in lanes [0,128), ‖x‖² [128,256), α [256,384)).  Returns
    (new row, Δw contribution).

    The concatenated layout is the kernel's key scalar-unit optimization:
    all three per-step values arrive from ONE dynamic slice, and the α
    write goes back through the same row — 2 dynamically-addressed VMEM
    accesses per step instead of 5.  Address generation on the scalar core
    is the per-step bottleneck, not the O(d) vector work (measured: the
    frozen mode, which skips the Δw dot entirely, costs the same) — which
    is also why the base margin is one more VPU reduce against the
    VMEM-resident w₀ rather than a precomputed margins0 read (see the
    module docstring: the whole-shard matvec it replaces was most of the
    round's HBM traffic)."""
    lane4 = jax.lax.broadcasted_iota(jnp.int32, (1, STACK * LANES), 1)
    y = jnp.sum(jnp.where(lane4 == sub_lane, srow, 0.0))
    sq = jnp.sum(jnp.where(lane4 == sub_lane + LANES, srow, 0.0))
    a = jnp.sum(jnp.where(lane4 == sub_lane + 2 * LANES, srow, 0.0))

    margin = jnp.sum(x * w_k)
    if not frozen:
        margin = margin + sig_eff * jnp.sum(x * dw_k)
    # the dual coordinate update is pure scalar jnp — shared with the
    # fori_loop kernels via ops/losses.py (hinge = CoCoA.scala:166-178)
    new_a = losses.alpha_step(loss, a, y * margin, sq * qii_factor, lam_n,
                              smoothing=smoothing)
    coef = y * (new_a - a) / coef_div
    wmask = lane4 == sub_lane + 2 * LANES
    if live is not None:   # tail group past H (only when unroll ∤ H): inert
        coef = jnp.where(live, coef, 0.0)
        wmask = wmask & live
    return jnp.where(wmask, new_a, srow), coef * x


def _kernel(
    idxs_ref,        # scalar-prefetch: (K, H) int32 sampled rows
    *refs,           # S row blocks, w, stacked vecs, 2 outs, 2 scratch
    lam_n: float,
    coef_div: float,
    sig_eff: float,
    qii_factor: float,
    frozen: bool,
    h: int,
    loss: str,
    smoothing: float,
    unroll: int,
    n_groups: int,
):
    # refs layout:
    #   x_refs[j]      (1, 1, 8, d8) VMEM: folded row of sample j
    #   w_ref          (8, d8) VMEM: the replicated w₀ (margin base)
    #   stacked_in     (1, n_blocks, 3·LANES) VMEM: shard k's lane-blocked
    #                  [labels | sq_norms | alpha] concatenation
    #   dw_ref         out (1, 8, d8) VMEM: shard k's Δw (flushed on k advance)
    #   alpha_ref      out (1, n_blocks, LANES) VMEM (flushed on k advance)
    #   dw_acc         scratch (8, d8) VMEM: this shard's Δw accumulator
    #   stacked_sc     scratch (n_blocks, 3·LANES): the advancing state
    x_refs = refs[:unroll]
    w_ref, stacked_in, dw_ref, alpha_ref, dw_acc, stacked_sc = refs[unroll:]
    k_ = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init_shard():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        stacked_sc[...] = stacked_in[0]

    # S sequential coordinate steps per grid iteration, each against its own
    # prefetched row block; step j reads the dw_acc/stacked_sc written by j-1
    exact = h % unroll == 0
    for j in range(unroll):
        step = i * unroll + j
        # groups past H clamp their index (the row spec's index map does the
        # same clamp, so the DMA'd block matches) and zero their update;
        # when unroll | H there is no tail and the masking drops out
        idx = idxs_ref[k_, step if exact else jnp.minimum(step, h - 1)]
        live = None if exact else step < h
        blk = idx // LANES
        srow = stacked_sc[pl.ds(blk, 1)]      # (1, 3·LANES): one dyn read
        x = x_refs[j][0, 0]                   # (8, d8): the folded row
        new_row, dws = _step_body(
            srow, idx - blk * LANES, live, x, dw_acc[...], w_ref[...],
            frozen=frozen,
            sig_eff=sig_eff, qii_factor=qii_factor, lam_n=lam_n,
            coef_div=coef_div, loss=loss, smoothing=smoothing,
        )
        dw_acc[...] = dw_acc[...] + dws
        stacked_sc[pl.ds(blk, 1)] = new_row   # one dyn write

    @pl.when(i == n_groups - 1)
    def _flush_shard():
        dw_ref[0] = dw_acc[...]
        alpha_ref[0] = stacked_sc[:, 2 * LANES:]


def _kernel_interleaved(
    idxs_ref,        # scalar-prefetch: (K, H) int32 sampled rows
    *refs,           # K*S row blocks, stacked_in, 2 outs, 2K scratch
    lam_n: float,
    coef_div: float,
    sig_eff: float,
    qii_factor: float,
    frozen: bool,
    h: int,
    loss: str,
    smoothing: float,
    unroll: int,
    n_groups: int,
    k: int,
):
    """Shard-interleaved variant: 1-D grid over step groups; each iteration
    advances EVERY shard's chain by S steps.  The K chains are independent
    and — crucially — keep their state in SEPARATE scratch refs, so Mosaic
    does not serialize them on ref aliasing and their per-step dependency
    chains overlap (measured ~1.6x over the shard-major kernel at epsilon
    scale, where the chain latency, not bandwidth, is the bound).  Needs
    all K shards' stacked state VMEM-resident (interleave_vmem_estimate)."""
    x_refs = refs[:k * unroll]           # x_refs[j*k + kk]
    w_ref = refs[k * unroll]
    stacked_in = refs[k * unroll + 1]
    dw_ref, alpha_ref = refs[k * unroll + 2:k * unroll + 4]
    dw_accs = refs[k * unroll + 4:k * unroll + 4 + k]
    st_scs = refs[k * unroll + 4 + k:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for kk in range(k):
            dw_accs[kk][...] = jnp.zeros_like(dw_accs[kk])
            st_scs[kk][...] = stacked_in[kk]

    exact = h % unroll == 0
    for j in range(unroll):
        step = i * unroll + j
        live = None if exact else step < h
        step_c = step if exact else jnp.minimum(step, h - 1)
        for kk in range(k):
            idx = idxs_ref[kk, step_c]
            blk = idx // LANES
            srow = st_scs[kk][pl.ds(blk, 1)]
            x = x_refs[j * k + kk][0, 0]
            new_row, dws = _step_body(
                srow, idx - blk * LANES, live, x, dw_accs[kk][...],
                w_ref[...],
                frozen=frozen, sig_eff=sig_eff, qii_factor=qii_factor,
                lam_n=lam_n, coef_div=coef_div, loss=loss,
                smoothing=smoothing,
            )
            dw_accs[kk][...] = dw_accs[kk][...] + dws
            st_scs[kk][pl.ds(blk, 1)] = new_row

    @pl.when(i == n_groups - 1)
    def _flush():
        for kk in range(k):
            dw_ref[kk] = dw_accs[kk][...]
            alpha_ref[kk] = st_scs[kk][:, 2 * LANES:]


@functools.partial(
    jax.jit,
    static_argnames=("lam", "n", "mode", "sigma", "interpret", "loss",
                     "smoothing", "unroll", "interleave"),
)
def pallas_sdca_round(
    w: jax.Array,            # (d,) the replicated primal vector w₀
    alpha: jax.Array,        # (K, n_shard)
    X: jax.Array,            # (K, n_shard, d) dense rows
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
    unroll: int = 0,
    interleave=None,
):
    """One SDCA round for K shards on this chip.  Returns (dw, alpha_inner):
    dw (K, d) unreduced per-shard updates; alpha_inner (K, n_shard) the
    locally-advanced alpha (callers apply the outer scaling law).

    ``unroll`` = coordinate steps per grid iteration (0 = auto: the largest
    of 16/8/4/2/1 whose row blocks fit the VMEM budget).  Any value yields
    the same math — it only changes DMA batching.

    ``interleave`` (None = auto: K > 1 and all shards' state fits VMEM)
    advances the K independent chains in lockstep with separate scratch
    refs, overlapping their per-step dependency chains — same math, ~1.6x
    at epsilon scale.

    Inside ``shard_map`` this must run under ``check_vma=False`` (the
    chunked driver does; pallas_call's internal slices confuse the VMA
    checker)."""
    if X.ndim == 4:
        # pre-folded (K, n_shard, 8, d/8) — the hot paths fold once per run
        # OUTSIDE the round loop: folding in here would relayout the whole X
        # every round (the 3-D and 4-D tiled layouts differ physically)
        k, n_shard, _, d8 = X.shape
        d = d_orig = SUBLANES * d8
        X_folded = X
    else:
        k, n_shard, d = X.shape
        d_orig = d
        if d % SUBLANES:
            # hot configs avoid this copy: shard_dataset pads dense d to 8
            pad = SUBLANES - d % SUBLANES
            X = jnp.pad(X, ((0, 0), (0, 0), (0, pad)))
            d += pad
        d8 = d // SUBLANES
        X_folded = X.reshape(k, n_shard, SUBLANES, d8)
    h = idxs.shape[1]
    dtype = X.dtype
    check_dtype(dtype)
    itemsize = jnp.dtype(dtype).itemsize
    if interleave is None:
        # auto: the fit check must use the unroll that will actually run
        # (an explicit large unroll can blow the all-shards VMEM budget)
        fit = pick_interleave(k, n_shard, d, itemsize, h)
        interleave = fit > 0 and (
            not unroll
            or interleave_vmem_estimate(k, n_shard, d, itemsize, unroll)
            <= INTERLEAVE_BUDGET
        )
    if interleave and not unroll:
        # the interleaved budget governs the group size (pick_unroll's
        # single-shard budget would overshoot the all-shards working set)
        unroll = pick_interleave(k, n_shard, d, itemsize, h) or 1
    if not unroll:
        unroll = pick_unroll(n_shard, d, itemsize, h) or 1
    n_groups = -(-h // unroll)
    sig_eff, qii_factor = mode_factors(mode, sigma)

    # lane-block the per-shard vectors and lane-concatenate them into the
    # (K, n_blocks, 3·128) stacked state the kernel reads with ONE dynamic
    # slice per step (see _step_body).  Sampled indices never exceed the
    # shard's true row count, so zero padding is inert.
    n_pad = -(-n_shard // LANES) * LANES
    pad = [(0, 0), (0, n_pad - n_shard)]
    blocked = lambda v: jnp.pad(v, pad).reshape(k, n_pad // LANES, LANES)  # noqa: E731
    n_blocks = n_pad // LANES
    stacked = jnp.concatenate(
        [blocked(labels), blocked(sq_norms), blocked(alpha)], axis=-1,
    )  # (K, n_blocks, STACK*LANES)
    # the replicated w₀, folded like the rows (free reshape: contiguous)
    w_pad = jnp.pad(w.astype(dtype), (0, d - w.shape[0]))
    w_folded = w_pad.reshape(SUBLANES, d8)

    def row_spec(j, kk=None):
        # sample j of group i: the folded row at [shard, idx, :, :].  Groups
        # past H (only when unroll does not divide H) clamp to the last
        # sample — the kernels compute the same clamped index, so the DMA'd
        # block always matches.  ``kk`` fixes the shard (interleaved 1-D
        # grid); kk=None reads it from the grid (shard-major 2-D grid).
        exact = h % unroll == 0

        def step_of(i_):
            step = i_ * unroll + j if unroll > 1 else i_
            return step if exact else jnp.minimum(step, h - 1)

        if kk is None:
            index_map = lambda k_, i_, idxs_: (k_, idxs_[k_, step_of(i_)], 0, 0)
        else:
            index_map = lambda i_, idxs_: (kk, idxs_[kk, step_of(i_)], 0, 0)
        return pl.BlockSpec((1, 1, SUBLANES, d8), index_map)

    common = dict(
        lam_n=float(lam * n),
        coef_div=float(coef_divisor(mode, lam * n)),
        sig_eff=float(sig_eff),
        qii_factor=float(qii_factor),
        frozen=(mode == "frozen"),
        h=h,
        loss=losses.validate(loss, smoothing),
        smoothing=float(smoothing),
        unroll=unroll,
        n_groups=n_groups,
    )

    if interleave:
        kernel = functools.partial(_kernel_interleaved, k=k, **common)


        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_groups,),
            in_specs=[
                *[row_spec(j, kk)
                  for j in range(unroll) for kk in range(k)],
                pl.BlockSpec((SUBLANES, d8), lambda i_, idxs_: (0, 0)),
                pl.BlockSpec((k, n_blocks, STACK * LANES),
                             lambda i_, idxs_: (0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((k, SUBLANES, d8), lambda i_, idxs_: (0, 0, 0)),
                pl.BlockSpec((k, n_blocks, LANES),
                             lambda i_, idxs_: (0, 0, 0)),
            ],
            scratch_shapes=(
                [pltpu.VMEM((SUBLANES, d8), dtype)] * k
                + [pltpu.VMEM((n_blocks, STACK * LANES), dtype)] * k
            ),
        )
        n_row_ops = k * unroll
        semantics = ("arbitrary",)
    else:
        kernel = functools.partial(_kernel, **common)


        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k, n_groups),
            in_specs=[
                *[row_spec(j) for j in range(unroll)],
                pl.BlockSpec((SUBLANES, d8), lambda k_, i_, idxs_: (0, 0)),
                pl.BlockSpec((1, n_blocks, STACK * LANES),
                             lambda k_, i_, idxs_: (k_, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, SUBLANES, d8),
                             lambda k_, i_, idxs_: (k_, 0, 0)),
                pl.BlockSpec((1, n_blocks, LANES),
                             lambda k_, i_, idxs_: (k_, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((SUBLANES, d8), dtype),
                pltpu.VMEM((n_blocks, STACK * LANES), dtype),
            ],
        )
        n_row_ops = unroll
        semantics = ("arbitrary", "arbitrary")

    dw, alpha_blocked = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((k, SUBLANES, d8), dtype),
            jax.ShapeDtypeStruct((k, n_blocks, LANES), dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        interpret=interpret,
    )(idxs, *([X_folded] * n_row_ops), w_folded, stacked)
    alpha_inner = alpha_blocked.reshape(k, n_pad)[:, :n_shard]
    return dw.reshape(k, d)[:, :d_orig], alpha_inner
