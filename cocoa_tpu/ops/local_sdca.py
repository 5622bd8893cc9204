"""Local SDCA — the per-worker inner solver of CoCoA / CoCoA+ / mini-batch CD.

TPU-native re-implementation of the reference's sequential coordinate-ascent
loops (CoCoA.scala:130-192 ``localSDCA`` and MinibatchCD.scala:76-132).  The
H coordinate steps are inherently sequential (step i+1 reads the w/Δw written
by step i — CoCoA.scala:159,183-185), so the loop runs as one fused
``lax.fori_loop`` inside jit with the whole shard resident in HBM; per step:
one row gather, one or two d-dots, a box projection, and a row axpy.

Three statically-selected gradient modes cover the three algorithms:

- ``"cocoa"``  — CoCoA (plus=false): grad reads the locally-advancing w
  (CoCoA.scala:161), w += update each step (:182-184), qii = ‖x‖²       (:174)
- ``"plus"``   — CoCoA+: w frozen; grad reads x·(w + σ′·Δw) (:158-160),
  qii = ‖x‖²·σ′ (:174)
- ``"frozen"`` — mini-batch CD: w frozen, plain grad (MinibatchCD.scala:104),
  qii = ‖x‖² (:114); α still advances within the batch (:123)
- ``"prox"``   — ProxCoCoA+ primal coordinate descent (no reference
  analogue; arXiv:1512.04011 structure): the roles of examples and
  features swap — the shard's "rows" are columns a_j of the design
  matrix, ``w`` is the replicated residual r₀ = Ax − b, ``alpha`` the
  shard's coordinate block of x, and the margin a_jᵀ(r₀ + σ′Δv) feeds a
  prox rule (losses.PROX_RULES) instead of a dual-ascent rule.  Same
  σ′-scaled read structure as "plus"; the Δw axpy coefficient is the raw
  coordinate delta (``coef_divisor`` == 1) rather than y·Δα/(λn)

Sampled indices arrive precomputed as ``idxs`` (H,) — index draws are
data-independent, so hoisting RNG off the device hot path changes nothing
algorithmically; it is what makes the reference-faithful java.util.Random
mode exact (see cocoa_tpu/utils/prng.py).

Row squared norms arrive precomputed per shard (``sq_norms``): the reference
recomputes ‖x‖² every step (CoCoA.scala:173) — same values, wasted FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from cocoa_tpu.ops import losses
from cocoa_tpu.ops.rows import get_row, row_axpy, row_dot

MODES = ("cocoa", "plus", "frozen", "prox")


def coef_divisor(mode: str, lam_n: float) -> float:
    """The Δw axpy coefficient is y·(α_new − α)/(λn) for the dual-ascent
    modes (CoCoA.scala:181) but the raw coordinate delta for the primal
    prox mode (Δv += a_j·δ)."""
    return 1.0 if mode == "prox" else lam_n


def _coef_staging(mode: str, lam, n, lam_n, dtype):
    """The one λn/coefficient staging shared by :func:`local_sdca` and
    :func:`local_sdca_fast` (bit-parity-critical — a fix to one path
    must never miss the other).  Returns ``(lam_n, coef_of)``:

    - static path (``lam_n is None``): λn and the divisor are baked-in
      constants from ``lam * n`` — the original arithmetic, untouched;
    - traced path: ``lam_n`` arrives precomputed (possibly per-tenant,
      solvers/fleet.py) and ``coef_of`` MIRRORS XLA's
      divide-by-constant rewrite — the static path's jit folds /λn into
      ·(1/λn) (one f32 reciprocal), so the traced twin multiplies by
      the same f32 reciprocal, computed once at the kernel head, which
      is what keeps a traced-λn fleet lane bit-identical to the solo
      executable (tests/test_fleet.py)."""
    if lam_n is None:
        lam_n = jnp.asarray(lam * n, dtype)
        coef_div = jnp.asarray(coef_divisor(mode, lam * n), dtype)

        def coef_of(y, delta):
            return y * delta / coef_div
    else:
        lam_n = jnp.asarray(lam_n, dtype)
        inv = (jnp.asarray(1.0, dtype) if mode == "prox"
               else jnp.asarray(1.0, dtype) / lam_n)

        def coef_of(y, delta):
            return y * delta * inv
    return lam_n, coef_of


def local_sdca(
    w_init: jax.Array,     # (d,) shared primal vector (replicated)
    alpha: jax.Array,      # (n_shard,) local dual variables
    shard: dict,           # labels, sq_norms, X | sp_indices+sp_values
    idxs: jax.Array,       # (H,) int32 sampled local coordinates
    lam: float,
    n: int,                # GLOBAL example count (primal-dual correspondence)
    mode: str = "cocoa",
    sigma: float = 1.0,    # sigma' = K * gamma, used by mode=="plus"
    loss: str = "hinge",
    smoothing: float = 1.0,
    lam_n=None,
):
    """Run H sequential SDCA steps.  Returns (delta_alpha, delta_w).

    With ``loss="hinge"`` matches the reference bit-for-bit in x64 given the
    same index sequence (validated against tests/oracle.py); the dual-ascent
    coordinate update for other losses comes from ops/losses.py.

    ``lam_n`` (the fleet path, solvers/fleet.py): a precomputed —
    possibly TRACED — λ·n scalar overriding the ``lam * n`` computed
    here, so ONE compiled kernel can serve every tenant of a vmapped
    fleet; ``sigma`` may then be traced too.  The host computes the
    override as float32(float64(λ)·n) — exactly the value the static
    path's cast produces — which is what keeps a T=1 fleet run
    bit-identical to the solo path.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    losses.validate(loss, smoothing)
    labels = shard["labels"]
    sq_norms = shard["sq_norms"]
    dtype = w_init.dtype
    lam_n, coef_of = _coef_staging(mode, lam, n, lam_n, dtype)
    sigma_c = jnp.asarray(sigma, dtype)
    one = jnp.asarray(1.0, dtype)

    def step(i, carry):
        w, dw, a_vec = carry
        idx = idxs[i]
        row = get_row(shard, idx)
        y = labels[idx]
        a = a_vec[idx]

        if mode in ("plus", "prox"):
            margin = row_dot(row, w) + sigma_c * row_dot(row, dw)
        else:
            margin = row_dot(row, w)

        qii = sq_norms[idx] * (sigma_c if mode in ("plus", "prox") else one)
        new_a = losses.alpha_step(loss, a, y * margin, qii, lam_n,
                                  smoothing=smoothing)

        coef = coef_of(y, new_a - a)
        dw = row_axpy(row, coef, dw)
        if mode == "cocoa":
            w = row_axpy(row, coef, w)  # local view advances (CoCoA.scala:182-184)
        a_vec = a_vec.at[idx].set(new_a)
        return w, dw, a_vec

    dw0 = jnp.zeros_like(w_init)
    w_final, dw, alpha_final = lax.fori_loop(
        0, idxs.shape[0], step, (w_init, dw0, alpha)
    )
    del w_final
    return alpha_final - alpha, dw


def mode_factors(mode: str, sigma: float):
    """(sig_eff, qii_factor) for the margin decomposition used by the fast
    kernels: x·w_step = margins0[idx] + sig_eff·(x·Δw), where margins0 = X·w₀
    is precomputed once per round (one MXU matvec).

    - cocoa:  w_step = w₀ + Δw exactly (the local w advance accumulates the
      same updates as Δw, CoCoA.scala:182-185) ⇒ sig_eff = 1, qii = ‖x‖².
    - plus:   w frozen, subproblem reads σ′·Δw (CoCoA.scala:158-160)
      ⇒ sig_eff = σ′, qii = ‖x‖²·σ′.
    - frozen: w frozen, no Δw term (MinibatchCD.scala:104)
      ⇒ sig_eff = 0, qii = ‖x‖².
    - prox:   same read structure as plus (r₀ frozen, σ′-scaled Δv reads)
      ⇒ sig_eff = σ′, qii = ‖a_j‖²·σ′.
    """
    if mode == "cocoa":
        return 1.0, 1.0
    if mode in ("plus", "prox"):
        return sigma, sigma
    if mode == "frozen":
        return 0.0, 1.0
    raise ValueError(f"mode must be one of {MODES}, got {mode!r}")


def local_sdca_fast(
    margins0: jax.Array,   # (n_shard,) precomputed x_i·w₀
    alpha: jax.Array,      # (n_shard,)
    shard: dict,
    idxs: jax.Array,       # (H,) int32
    lam: float,
    n: int,
    dw_init: jax.Array,    # (d,) zeros, built from w by the caller so its
                           # varying-axes type matches under shard_map
    mode: str = "cocoa",
    sigma: float = 1.0,
    loss: str = "hinge",
    smoothing: float = 1.0,
    lam_n=None,
):
    """Fast-math variant of :func:`local_sdca`: the per-step w dot is
    replaced by the precomputed round margin plus an incremental Δw dot
    (see :func:`mode_factors`).  Exactly equal in real arithmetic; floating
    point rounds differently than the reference order, so trajectories agree
    to ~1e-6 rather than bit-exactly.  Returns (delta_alpha, delta_w).

    The frozen mode skips the Δw dot entirely — its only sequential state is
    alpha itself.  ``lam_n``: the fleet path's traced λ·n override — same
    contract as on :func:`local_sdca` (``sigma`` may then be traced too;
    ``mode_factors`` passes a traced σ′ through untouched for the plus
    mode the fleet runs).
    """
    losses.validate(loss, smoothing)
    sig_eff, qii_factor = mode_factors(mode, sigma)
    labels = shard["labels"]
    sq_norms = shard["sq_norms"]
    dtype = margins0.dtype
    lam_n, coef_of = _coef_staging(mode, lam, n, lam_n, dtype)
    sig_c = jnp.asarray(sig_eff, dtype)
    qf = jnp.asarray(qii_factor, dtype)

    def step(i, carry):
        dw, a_vec = carry
        idx = idxs[i]
        row = get_row(shard, idx)
        y = labels[idx]
        a = a_vec[idx]

        margin = margins0[idx]
        if mode != "frozen":
            margin = margin + sig_c * row_dot(row, dw)
        qii = sq_norms[idx] * qf
        new_a = losses.alpha_step(loss, a, y * margin, qii, lam_n,
                                  smoothing=smoothing)

        coef = coef_of(y, new_a - a)
        dw = row_axpy(row, coef, dw)
        a_vec = a_vec.at[idx].set(new_a)
        return dw, a_vec

    dw, alpha_final = lax.fori_loop(0, idxs.shape[0], step, (dw_init, alpha))
    return alpha_final - alpha, dw


def local_sdca_block(
    margins0: jax.Array,   # (n_shard,) precomputed x_i·w₀
    alpha: jax.Array,      # (n_shard,)
    shard: dict,
    idxs: jax.Array,       # (H,) int32
    lam: float,
    n: int,
    dw_init: jax.Array,    # (d,) zeros (see local_sdca_fast)
    mode: str = "cocoa",
    sigma: float = 1.0,
    loss: str = "hinge",
    smoothing: float = 1.0,
    block: int = 16,
):
    """Block-coordinate variant of :func:`local_sdca_fast` — same sampled
    index stream, same math, restructured for the MXU.

    The sequential kernels pay a data-dependent O(d) dot + axpy per
    coordinate step (the latency chain the reference's hot loop imposes,
    CoCoA.scala:148-188).  This kernel processes the H steps in ⌈H/B⌉
    blocks of B consecutive draws: per block it gathers the B rows as one
    (B, d) tile, computes the block's Δw margins ``X_B·Δw`` and Gram matrix
    ``G = X_B·X_Bᵀ`` as two MXU matmuls, then replays the B coordinate
    updates as a *scalar* sequential loop in which step j's margin is

        margins0[idx_j] + sig_eff·(X_B·Δw)[j] + sig_eff·Σ_{i<j} c_i·G[i, j]

    — exactly the sequential recurrence, with the running Δw dot replaced
    by cached pairwise dots (identical in real arithmetic; floating point
    reassociates, so trajectories agree to fp tolerance like the fast
    path).  Δw advances once per block via ``cᵀ·X_B``.  The critical path
    per coordinate drops from O(d) memory-bound work to O(B) scalar work;
    the O(B·d) tile work is parallel MXU/VPU traffic.

    Duplicate draws inside a block are exact: α is read/written through the
    shard vector every scalar step, and the Gram term carries the earlier
    occurrence's contribution to the later one's margin.  H is padded up to
    a multiple of B with masked no-op steps.

    The sparse (padded-CSR) layout densifies each block's rows into the
    (B, d) tile first — padded slots carry index 0 / value 0 and scatter
    harmlessly — then runs the identical dense block math.

    This is the portable XLA form (each chained step still pays XLA's ~µs
    loop overhead); the TPU production form is
    :func:`local_sdca_block_batched`, which runs the recurrence as a Pallas
    kernel and serves as the ``--blockSize`` hot path.

    Flag-gated (``--blockSize``); the default path stays the
    reference-faithful strictly-sequential kernel.
    """
    losses.validate(loss, smoothing)
    if block <= 0:
        raise ValueError(f"block must be positive, got {block}")
    sig_eff, qii_factor = mode_factors(mode, sigma)
    labels = shard["labels"]
    sq_norms = shard["sq_norms"]
    dtype = margins0.dtype
    lam_n = jnp.asarray(lam * n, dtype)
    coef_div = jnp.asarray(coef_divisor(mode, lam * n), dtype)
    sig_c = jnp.asarray(sig_eff, dtype)
    qf = jnp.asarray(qii_factor, dtype)
    d = dw_init.shape[0]

    h = idxs.shape[0]
    nb = -(-h // block)
    idxs_b = jnp.pad(idxs, (0, nb * block - h)).reshape(nb, block)
    mask_b = (jnp.arange(nb * block) < h).reshape(nb, block)

    def block_step(carry, inp):
        dw, a_vec = carry
        bidx, bmask = inp
        if "X" in shard:
            xb = shard["X"][bidx]                              # (B, d)
        else:
            spi = shard["sp_indices"][bidx]                    # (B, nnz)
            spv = shard["sp_values"][bidx]
            xb = jnp.zeros((block, d), dtype).at[
                jnp.arange(block)[:, None], spi].add(spv)
            if "X_hot" in shard:
                # hybrid layout: the residual scatter above misses the
                # hot-panel nonzeros — add them at their column ids
                # (disjoint from every residual id, so adds never collide)
                xb = xb.at[jnp.arange(block)[:, None],
                           shard["hot_cols"][None, :]].add(
                    shard["X_hot"][bidx])
        yb = labels[bidx]
        m0b = margins0[bidx]
        qb = sq_norms[bidx] * qf
        if mode != "frozen":
            mb = xb @ dw                                       # (B,)
            gram = xb @ xb.T                                   # (B, B)

        def scalar_step(j, sc):
            coefs, a_vec = sc
            idx = bidx[j]
            a = a_vec[idx]
            margin = m0b[j]
            if mode != "frozen":
                margin = margin + sig_c * (mb[j] + coefs @ gram[:, j])
            new_a = losses.alpha_step(loss, a, yb[j] * margin, qb[j], lam_n,
                                      smoothing=smoothing)
            keep = bmask[j]
            coef = jnp.where(keep, yb[j] * (new_a - a) / coef_div,
                             jnp.asarray(0.0, dtype))
            a_vec = a_vec.at[idx].set(jnp.where(keep, new_a, a))
            return coefs.at[j].set(coef), a_vec

        # init the coef carry from varying data (yb) so its VMA type matches
        # the loop output under shard_map, like the callers do for dw_init
        coefs, a_vec = lax.fori_loop(
            0, block, scalar_step, (yb * jnp.asarray(0.0, dtype), a_vec)
        )
        return (dw + coefs @ xb, a_vec), None

    (dw, alpha_final), _ = lax.scan(
        block_step, (dw_init, alpha), (idxs_b, mask_b)
    )
    return alpha_final - alpha, dw


def resolve_block_form(*, sparse: bool, hybrid: bool, k: int, block: int,
                       d: int, n_shard: int, width: int, itemsize: int,
                       sparse_gram: "bool | None" = None) -> str:
    """Which form of the batched block round a configuration runs —
    ``fused`` | ``split`` | ``sparse_gram`` | ``hybrid``.  The ONE
    decision :func:`local_sdca_block_batched` dispatches on and the
    solver-path record (solvers/cocoa.resolve_solver_path) reports, so
    what a run says it ran is what it ran.

    ``sparse_gram=None`` (auto): the sparse Gram path is the sparse-layout
    block default whenever the fused kernel cannot hold the densified
    tile (the rcv1 regime) and the CSR streams fit the SMEM segmentation;
    on a hybrid layout (``--hotCols``) that path is the hybrid branch and
    ``width`` is the cold residual's."""
    from cocoa_tpu.ops.pallas_chain import fused_fits
    from cocoa_tpu.ops.pallas_sparse import sparse_chain_fits

    fused_ok = fused_fits(k, block, d, itemsize, n_shard)
    if sparse_gram is None:
        sparse_gram = (
            sparse
            and itemsize == 4
            and not fused_ok
            and sparse_chain_fits(k, n_shard, d, width, block, itemsize)
        )
    if sparse_gram:
        if not sparse:
            raise ValueError("sparse_gram=True requires the padded-CSR "
                             "(sparse) layout")
        return "hybrid" if hybrid else "sparse_gram"
    return "fused" if fused_ok else "split"


def local_sdca_block_batched(
    w: jax.Array,          # (d,) shared primal vector (replicated)
    alpha: jax.Array,      # (K, n_shard)
    shards: dict,          # leaves with leading K dim
    idxs_kh: jax.Array,    # (K, H) int32
    lam: float,
    n: int,
    mode: str = "cocoa",
    sigma: float = 1.0,
    loss: str = "hinge",
    smoothing: float = 1.0,
    block: int = 128,
    interpret: bool = False,
    distinct: bool = False,
    sparse_gram: "bool | None" = None,
    pipeline: "bool | None" = None,
):
    """All-K-shards block-coordinate round on one chip — the TPU-native
    shape of :func:`local_sdca_block`, and the ``--blockSize`` hot path.

    Hot configs run ops/pallas_chain.fused_block: ONE kernel per block
    computing the sampled rows' margins, the K Gram matrices, the
    duplicate-equality tile, the B-step lockstep chain, and the Δw update
    entirely in VMEM (see the design note in pallas_chain.py — profiling
    showed the XLA einsum/concat/scatter materialization around the
    chain-only kernel cost ~4 ms/round at epsilon scale, an order of
    magnitude more than the chain itself).  Per block the XLA side does
    only the truly XLA-shaped work: the row-tile gather, the α
    gather/scatter (TPU has no cheap in-kernel vector gather), and two
    (K, d) adds.  Configs whose half-tile does not fit VMEM
    (``fused_fits``) fall back to the split form: per-block XLA einsums
    feeding the chain-only kernel (chain_block_batched).

    Unlike the sequential fast path there is NO whole-shard margins matvec:
    only the H sampled rows' margins are ever computed, from the same row
    tiles the Gram matrices need — at localIterFrac = 0.1 the full-shard
    X·w pass the other paths pay per round reads 10x more of X than the
    round touches (at epsilon scale that pass alone is ~4 ms/round of pure
    HBM traffic).

    Identical real arithmetic to K independent :func:`local_sdca_fast`
    runs.  Precision policy (f32 on TPU): margins/Gram at DEFAULT — the
    precision the fast path's ``shard_margins`` matvec uses — and the Δw
    update accumulated in f32 so the primal-dual correspondence
    ``w = (1/λn)·Σyαx`` the gap certificate rests on stays tight over
    thousands of accumulated blocks.  Returns (delta_alpha (K, n_shard),
    delta_w (K, d)).

    ``distinct=True`` asserts the round's H indices are pairwise distinct
    within every shard (the caller's obligation — true for permuted
    sampling whenever n_local % H == 0, because each round then sits
    inside one epoch's permutation).  That license removes the hottest
    XLA glue around the fused kernel (measured round 5: the per-block α
    scatter was 23% of device time, more than half the kernel itself):
    the α₀ gather hoists to ONE (K, H) gather per round, the per-block
    scatters collapse to ONE batched scatter-add after the scan, and the
    scan carry drops α entirely.  Bit-identical to the per-block path
    under the distinctness precondition: no earlier block of the same
    round can have touched a later block's coordinates, so every chain
    reads exactly the values it would have read, and each coordinate
    receives exactly one add.  Fused path only (the split fallback keeps
    the per-block scatter).

    ``sparse_gram`` selects the SPARSE block-chain path (padded-CSR
    layouts only): the (B, B) block Gram and the margin base are computed
    IN-KERNEL from SMEM-scalar-prefetched CSR streams and the Δw apply is
    a sparse scatter (ops/pallas_sparse.sparse_block_gram/_apply) — no
    (K, B, d) densify.  ``None`` (auto) picks it for sparse layouts the
    fused kernel cannot hold (the rcv1 regime, where the densified tile is
    ~650x the rows' nonzero bytes) whenever the CSR streams fit the SMEM
    segmentation (sparse_chain_fits); ``True`` forces it (tests),
    ``False`` disables.  Same math as the split path — the chain kernel
    consumes the identical (scal, gq) contract — so trajectory parity
    carries over; the α update stays per-block (``distinct`` is a fused-
    path-only license).  On a HYBRID layout (the ``--hotCols`` hot/cold
    column split — ``X_hot``/``hot_cols`` in the shard dict, docs/DESIGN.md
    §3b-vi) this path becomes the hybrid branch: the streams carry only
    the cold residual, and the hot-panel majority of nonzeros joins the
    Gram as one MXU (B, n_hot)·(n_hot, B) panel matmul, the margin base
    as a panel matvec, and the apply as coefᵀ·panel into a separate hot
    Δw — same chain, same contract, exact column-partitioned split.

    ``pipeline`` (None = auto: on whenever the round spans more than one
    block) software-pipelines the dense block scan into a two-phase
    schedule: the row tile for block b+1 is gathered by block b's scan
    iteration — as an op with NO data dependence on block b's chain
    kernel — and rides the scan carry into iteration b+1.  The serial
    schedule runs the row-tile gather and a tile copy strictly
    SERIALIZED with the chain kernel; the pipelined schedule (a) hands
    XLA's scheduler a gather whose DMA traffic can overlap the Pallas
    kernel's execution window, and (b) lands the gather directly in the
    loop-carried tile buffer instead of a fresh per-iteration
    allocation, which is what fed the tile copy's relayout.  The
    prefetch reorders memory traffic ONLY — every kernel invocation
    consumes a tile gathered from the same indices by the same gather
    op, so the pipelined and serial schedules are bit-identical (pinned
    by tests/test_block.py); the last block prefetches block 0's tile
    and discards it (one dead gather per round, ~1/nb of the gather
    budget).  ``False`` is the serial schedule a single-block round
    takes; no driver passes a value.  Scope: the fused and split
    (dense/densified) paths only — the ``sparse_gram`` CSR path returns
    before the pipeline machinery and always runs its serial schedule
    (its streams are SMEM-prefetched inside the kernels; an explicit
    ``pipeline`` value is inert there).
    """
    from cocoa_tpu.ops.pallas_chain import chain_block_batched, fused_block

    losses.validate(loss, smoothing)
    sig_eff, qii_factor = mode_factors(mode, sigma)
    labels = shards["labels"]
    sq_norms = shards["sq_norms"]
    dtype = w.dtype
    qf = jnp.asarray(qii_factor, dtype)
    sig_c = jnp.asarray(sig_eff, dtype)
    k = alpha.shape[0]
    h = idxs_kh.shape[-1]
    d = w.shape[-1]
    mm = jax.lax.Precision.DEFAULT
    hi = jax.lax.Precision.HIGH

    nb = -(-h // block)
    idxs_b = jnp.pad(idxs_kh, ((0, 0), (0, nb * block - h))) \
        .reshape(k, nb, block).transpose(1, 0, 2)             # (nb, K, B)
    mask_b = (jnp.arange(nb * block) < h).reshape(nb, block)  # (nb, B)

    def gather_rows(bidx):
        """(K, B, d) dense row tile for one block (sparse rows densify —
        padded slots carry index 0 / value 0 and scatter harmlessly; the
        hybrid layout's hot panel scatters at its disjoint column ids)."""
        if "X" in shards:
            return jnp.take_along_axis(shards["X"], bidx[:, :, None], axis=1)
        spi = jnp.take_along_axis(shards["sp_indices"], bidx[:, :, None],
                                  axis=1)
        spv = jnp.take_along_axis(shards["sp_values"], bidx[:, :, None],
                                  axis=1)
        tile = jnp.zeros((k, block, d), dtype).at[
            jnp.arange(k)[:, None, None],
            jnp.arange(block)[None, :, None], spi].add(spv)
        if "X_hot" in shards:
            xh = jnp.take_along_axis(shards["X_hot"], bidx[:, :, None],
                                     axis=1)
            tile = tile.at[jnp.arange(k)[:, None, None],
                           jnp.arange(block)[None, :, None],
                           shards["hot_cols"][:, None, :]].add(xh)
        return tile

    gat = lambda v, bidx: jnp.take_along_axis(v, bidx, axis=1)  # noqa: E731

    form = resolve_block_form(
        sparse="sp_indices" in shards, hybrid="X_hot" in shards, k=k,
        block=block, d=d, n_shard=alpha.shape[1],
        width=(int(shards["sp_indices"].shape[-1])
               if "sp_indices" in shards else 0),
        itemsize=jnp.dtype(dtype).itemsize, sparse_gram=sparse_gram,
    )
    if form in ("sparse_gram", "hybrid"):
        from cocoa_tpu.ops.pallas_sparse import (
            GROUP, row_lengths, sparse_block_apply, sparse_block_gram,
            wd_delta, wd_stack,
        )

        sp_idx, sp_val = shards["sp_indices"], shards["sp_values"]
        w_nnz = sp_idx.shape[-1]
        group = min(GROUP, max(1, w_nnz))
        w_r = -(-w_nnz // group) * group
        row_len = shards.get("sp_row_len")
        if row_len is None:
            row_len = row_lengths(sp_val)
        frozen = mode == "frozen"
        wd0 = wd_stack(w, k)
        # HYBRID branch (hot/cold column split, docs/DESIGN.md §3b-vi):
        # the CSR streams above are the COLD RESIDUAL only; the hot-panel
        # majority of the nonzeros rides the MXU — per block one
        # (B, n_hot)·(n_hot, B) panel Gram matmul, one panel margin-base
        # matvec against [w_hot + σ′Δw_hot], and one coefᵀ·panel apply
        # into a separately-carried (K, n_hot) hot Δw.  Columns partition
        # between panel and streams, so gram/mbase/Δw each split exactly
        # (hot + cold permutes the per-nonzero sums; parity pinned by
        # tests/test_hybrid_sparse.py).
        hybrid = form == "hybrid"
        if hybrid:
            xh_all = shards["X_hot"]                  # (K, n_shard, n_hot)
            hot_cols_k = shards["hot_cols"]           # (K, n_hot)
            wh = jnp.take_along_axis(
                jnp.broadcast_to(w[None], (k, d)), hot_cols_k, axis=1)
            dwh0 = jnp.zeros_like(wh)                 # (K, n_hot)

        def sparse_block_step(carry, inp):
            if hybrid:
                wd, dwh, a_vec = carry
            else:
                wd, a_vec = carry        # (K, d/128, 2·128), (K, n_shard)
            bidx, bmask = inp            # (K, B), (B,)
            gidx = jnp.take_along_axis(sp_idx, bidx[:, :, None], axis=1)
            gvals = jnp.take_along_axis(sp_val, bidx[:, :, None], axis=1) \
                .astype(dtype)
            if w_r != w_nnz:
                # pad the slot axis to the GROUP-rounded width the trip
                # counts assume (zero slots are inert)
                pad3 = ((0, 0), (0, 0), (0, w_r - w_nnz))
                gidx = jnp.pad(gidx, pad3)
                gvals = jnp.pad(gvals, pad3)
            cnts = jnp.where(bmask[None, :],
                             jnp.take_along_axis(row_len, bidx, axis=1),
                             jnp.int32(-1))
            gram, mbase = sparse_block_gram(
                wd, gidx, gvals, cnts, sig_eff=sig_eff, frozen=frozen,
                interpret=interpret,
            )
            if hybrid:
                xh_b = jnp.take_along_axis(xh_all, bidx[:, :, None],
                                           axis=1)   # (K, B, n_hot)
                v_hot = wh if frozen else wh + sig_c * dwh
                mbase = mbase + jnp.einsum("kbh,kh->kb", xh_b, v_hot,
                                           precision=mm)
                if not frozen:
                    # full panel Gram; the chain reads only i < j entries,
                    # exactly as the split dense path's full einsum Gram
                    gram = gram + jnp.einsum("kjh,kih->jki", xh_b, xh_b,
                                             precision=mm)
            eq_t = (bidx.T[:, :, None] == bidx[None, :, :]).astype(dtype)
            gq = eq_t if frozen else jnp.concatenate([gram, eq_t], axis=1)
            scal = jnp.stack([
                mbase, gat(labels, bidx), gat(sq_norms, bidx) * qf,
                gat(a_vec, bidx),
                jnp.zeros_like(mbase),  # within-block Δw margin is in gram
                jnp.broadcast_to(bmask[None].astype(dtype), (k, block)),
            ], axis=1)                                    # (K, 6, B)
            delta, coefs = chain_block_batched(
                scal, gq,
                lam_n=float(lam * n),
                coef_div=float(coef_divisor(mode, lam * n)),
                sig_eff=float(sig_eff), frozen=frozen,
                loss=loss, smoothing=smoothing, interpret=interpret,
            )
            a_vec = a_vec.at[jnp.arange(k)[:, None], bidx].add(delta)
            wd = sparse_block_apply(wd, gidx, gvals, cnts, coefs,
                                    interpret=interpret)
            if hybrid:
                dwh = dwh + jnp.einsum("kb,kbh->kh", coefs, xh_b,
                                       precision=hi)
                return (wd, dwh, a_vec), None
            return (wd, a_vec), None

        if hybrid:
            (wd, dwh, alpha_final), _ = lax.scan(
                sparse_block_step, (wd0, dwh0, alpha), (idxs_b, mask_b)
            )
            dw = wd_delta(wd, d)
            # hot and cold columns are disjoint; panel-padding lanes carry
            # value 0 at column 0, so this scatter-add is exact
            dw = dw.at[jnp.arange(k)[:, None], hot_cols_k].add(dwh)
            return alpha_final - alpha, dw
        (wd, alpha_final), _ = lax.scan(
            sparse_block_step, (wd0, alpha), (idxs_b, mask_b)
        )
        return alpha_final - alpha, wd_delta(wd, d)

    # software pipeline (see the ``pipeline`` docstring note): block b's
    # scan iteration also issues block b+1's row-tile gather — the one
    # per-block input with no dependence on b's kernel — so the gather's
    # HBM traffic can hide behind the chain kernel instead of serializing
    # with it.  The last block prefetches block 0's tile (discarded).
    if pipeline is None:
        pipeline = nb > 1
    idxs_next = jnp.roll(idxs_b, -1, axis=0) if pipeline else None

    def pipelined_scan(body, carry0, xs):
        """Run ``body(carry, xb, *x_leaves) -> carry, out`` over the
        blocks with the row tile double-buffered through the scan carry
        (pipelined) or gathered in-iteration (serial).  Bit-identical
        either way: the same gather feeds the same kernel."""
        if not pipeline:
            def step(carry, inp):
                return body(carry, gather_rows(inp[0]), *inp)

            return lax.scan(step, carry0, xs)

        def step(carry, inp):
            inner, xb = carry
            bnext = inp[-1]
            xb_next = gather_rows(bnext)    # block b+1: independent of
            inner, out = body(inner, xb, *inp[:-1])   # block b's kernel
            return (inner, xb_next), out

        (carry, _), outs = lax.scan(
            step, (carry0, gather_rows(idxs_b[0])), (*xs, idxs_next)
        )
        return carry, outs

    if form == "fused":
        dw0 = jnp.zeros((k, d), dtype) + 0.0 * w[None]

        def fused_call(dw, xb, bidx, yb, qb, live, a0b):
            if mode == "frozen":
                v = jnp.broadcast_to(w[None], (k, d)).astype(dtype)
            else:
                v = w[None] + sig_c * dw
            return fused_block(
                xb, bidx.astype(dtype), yb, qb, a0b, live, v,
                lam_n=float(lam * n),
                coef_div=float(coef_divisor(mode, lam * n)),
                sig_eff=float(sig_eff), frozen=(mode == "frozen"),
                loss=loss, smoothing=smoothing, interpret=interpret,
            )

        def live_of(bmask):
            return jnp.broadcast_to(bmask[None].astype(dtype), (k, block))

        if distinct:
            # pairwise-distinct indices (caller-checked): the per-block α
            # gather/scatter (the hottest glue in the round-5 trace)
            # vanishes — α₀ comes from the per-round (K, ns, 3) stack, the
            # per-step deltas ride out as scan outputs, and α takes ONE
            # batched scatter-add per round.  The y/q/α₀ gathers also
            # merge into ONE width-3 row gather per block: TPU scalar
            # gathers pay per index fetched, and three fetches from the
            # same index vector are pure waste.  The stack costs one
            # streaming write per round (~6 µs at epsilon scale) against
            # ~0.6 ms of saved gather.  Gathering per BLOCK (not one
            # hoisted per-round gather) keeps the gather carry-independent
            # — so it pipelines — and kills the (nb, K, B, 3) transposed
            # staging copy the hoisted form materialized as scan inputs.
            yqa = jnp.stack([labels, sq_norms * qf, alpha], axis=-1)

            def body(dw, xb, bidx, bmask):
                g = jnp.take_along_axis(yqa, bidx[:, :, None], axis=1)
                yb, qb, a0b = g[..., 0], g[..., 1], g[..., 2]
                delta, dwu = fused_call(dw, xb, bidx, yb, qb,
                                        live_of(bmask), a0b)
                # (a0+δ)−a0 on the gathered values == what the old
                # alpha.at[].add(δ)−alpha computed at these coordinates,
                # bit for bit — but scattered into ZEROS below, so α is
                # never copied to preserve the subtrahend (the donation
                # miss behind the round-5 trace's copy glue)
                return dw + dwu, (a0b + delta) - a0b

            dw, dvals = pipelined_scan(body, dw0, (idxs_b, mask_b))
            flat = idxs_b.transpose(1, 0, 2).reshape(k, nb * block)
            dval_flat = dvals.transpose(1, 0, 2).reshape(k, nb * block)
            da = jnp.zeros_like(alpha).at[
                jnp.arange(k)[:, None], flat].add(dval_flat)
            return da, dw

        # non-distinct: α must ride the carry (a later block may re-draw
        # an earlier block's coordinate), but y/q still merge into one
        # width-2 per-block gather from a per-round stack
        yq = jnp.stack([labels, sq_norms * qf], axis=-1)

        def body(carry, xb, bidx, bmask):
            dw, a_vec = carry            # (K, d), (K, n_shard)
            g = jnp.take_along_axis(yq, bidx[:, :, None], axis=1)
            delta, dwu = fused_call(dw, xb, bidx, g[..., 0], g[..., 1],
                                    live_of(bmask), gat(a_vec, bidx))
            a_vec = a_vec.at[jnp.arange(k)[:, None], bidx].add(delta)
            return (dw + dwu, a_vec), None

        (dw, alpha_final), _ = pipelined_scan(
            body, (dw0, alpha), (idxs_b, mask_b)
        )
        return alpha_final - alpha, dw

    # legacy split path: per-block XLA einsums feeding the chain-only
    # kernel (configs whose half-tile does not fit VMEM); the row-tile
    # prefetch applies unchanged — the gather is the same op

    def body(carry, xb, bidx, bmask):
        dw, a_vec = carry            # (K, d), (K, n_shard)
        # the equality tile, directly in the kernel's (B, K, B)
        # j-sliceable layout: eq_t[j, k, i] = (idx_i == idx_j) in shard k
        eq_t = (bidx.T[:, :, None] == bidx[None, :, :]).astype(dtype)
        if mode == "frozen":
            # frozen margins never see Δw: base = X_B·w, no Gram needed
            mbase = jnp.einsum("kbd,d->kb", xb, w, precision=mm)
            gq = eq_t
        else:
            # one matvec carries both margin terms:
            # x·w + sig_eff·(x·Δw_blockstart)
            mbase = jnp.einsum("kbd,kd->kb", xb, w[None] + sig_c * dw,
                               precision=mm)
            gq = jnp.concatenate(
                [jnp.einsum("kjd,kid->jki", xb, xb, precision=mm), eq_t],
                axis=1,
            )                                             # (B, 2K, B)
        scal = jnp.stack([
            mbase, gat(labels, bidx), gat(sq_norms, bidx) * qf,
            gat(a_vec, bidx),
            jnp.zeros_like(mbase),  # within-block Δw margin lives in gram
            jnp.broadcast_to(bmask[None].astype(dtype), (k, block)),
        ], axis=1)                                        # (K, 6, B)
        delta, coefs = chain_block_batched(
            scal, gq,
            lam_n=float(lam * n),
            coef_div=float(coef_divisor(mode, lam * n)),
            sig_eff=float(sig_eff), frozen=(mode == "frozen"),
            loss=loss, smoothing=smoothing, interpret=interpret,
        )
        a_vec = a_vec.at[jnp.arange(k)[:, None], bidx].add(delta)
        dw = dw + jnp.einsum("kb,kbd->kd", coefs, xb, precision=hi)
        return (dw, a_vec), None

    dw0 = jnp.zeros((k, d), dtype) + 0.0 * w[None]  # inherit w's VMA type
    (dw, alpha_final), _ = pipelined_scan(
        body, (dw0, alpha), (idxs_b, mask_b)
    )
    return alpha_final - alpha, dw
