"""T class models over DENSE rows, the class axis on the lanes, a block of
B sampled rows a step: the local solve of a one-vs-rest job whose T models
are too many for the sublanes of the dense class kernel's state tile
(ops/pallas_sdca.classes_fit says no; ``SolverPath.inner`` ``block``,
``class_axis`` ``lanes``).

The state follows the lanes' convention (data/sharding.class_tile_shape,
as ops/pallas_sparse_lanes.py on sparse rows): W (d, R, 128) and alpha (K,
n_shard, R, 128), R = T_pad / 128, class t at [t // 128, t % 128] — one
row's T alphas are one contiguous 4 KB line, one column's T weights too, and
(d, R, 128) IS (d, T_pad) row-major, the right-hand side of a matrix
product.

**A round.**  Every class takes the job's one table of sampled rows (K, H).
A shard's H steps are cut into ceil(H / B) blocks of B consecutive draws
(the tail padded with steps that do nothing), the K shards' blocks advance
side by side, and one block is

1. *products* (``cocoa_wide_products``, the matrix unit): the block's rows
   X_B (B, d) gathered once; the margins at the block's start M0 = X_B . V_k
   (B, T_pad), V_k = W + sig_eff dW_k the shard's running vector; ONE Gram
   matrix G = X_B . X_B^T (B, B) for all T classes;
2. *replay*, in two levels.  Step j's margin is M0[j] + sig_eff sum_{i<j}
   G[j, i] c_i — exactly what x_j . (W + sig_eff dW_k) is once steps i < j
   have moved dW_k by c_i x_i, c_i the (R, 128) vector of the T classes' y
   (alpha' - alpha) / (lambda n).  The block's B steps are cut into
   sub-blocks of b consecutive steps (``BlockLanesPlan.sub``), and that sum
   is split where the sub-block starts:

   - what the steps of the EARLIER sub-blocks owe a sub-block's margins is
     one matrix product before it runs (``cocoa_wide_products``; the
     *cross* product, left-looking): M[s] = M0[s] + sig_eff G[s, :] . C,
     (b, B) . (B, T_pad) with C's rows still zero from the sub-block's
     first step on, so no mask cuts the triangle;
   - the b steps of the sub-block then run in order (``cocoa_wide_replay``),
     each on one (R, 128) lane vector: a step adds only the EARLIER STEPS
     OF ITS OWN SUB-BLOCK to its margin, from the diagonal (b, b) piece of
     G, solves all T steps side by side through ``losses.alpha_step`` and
     keeps c_j.

   A row drawn twice in a block is exact, in one sub-block or in two: the
   later step starts from the alpha the earlier one left (``prev``: inside
   the sub-block's chain, or gathered from an earlier sub-block's result
   before the chain runs), and its margin carries the earlier one's c
   through G, by the chain's sum or by the cross product.  A B that b does
   not divide ends in padded steps (``code`` -1: they move nothing), as a
   round's last block does;
3. *products* again: V_k += sig_eff X_B^T . C (d, T_pad).

In real arithmetic this is T runs of the sequential solve
(ops/local_sdca.local_sdca) over the same indices — the restructuring
``local_sdca_block`` documents at T = 1; in floating point the dots
reassociate.  At the round's end dW = sum_k (V_k - W) / sig_eff (the form
the stream's chain has had since PR 31: ops/pallas_longrows.margin_form
``combined``); mini-batch CD (sig_eff = 0) reads no dW_k: its margins are X_B
. W, it needs no Gram matrix and no cross product (its B steps are one
chain with no sum in it), and dW is summed as it goes.

**Where each part runs.**  The products are XLA's (``jnp.einsum`` with a
stated precision each: :data:`MARGINS_PRECISION`, :data:`GRAM_PRECISION`,
:data:`CROSS_PRECISION`, :data:`UPDATE_PRECISION`), the loop over a block's
sub-blocks a ``lax.fori_loop`` (:func:`_replay_two_level`: one traced chain,
one traced cross product).  A sub-block's chain is a Pallas kernel on a TPU
(:func:`_replay_kernel`: a shard a grid step, the sub-block's (b, b) piece
of G and the steps' scalars in SMEM, a step's vectors one (R, 128) tile
each) and a ``lax.fori_loop`` of the same steps anywhere else
(:func:`_replay_xla`); ``SolverPath.chain`` says which.

**B** comes from the shapes and the fit (:func:`block_lanes_plan`): four
(B, T_pad) arrays twice in VMEM, and B <= 256, the largest block whose
WHOLE Gram matrix the one-level kernel held twice in SMEM (Pallas
double-buffers a blocked operand).  Since the replay is two-level the
kernel holds a (b, b) piece only, so that second bound is a choice and no
longer a fit: a larger B is fewer, larger products against a cross product
that grows with B^2, a change with a prediction of its own (ROADMAP C5 h).
The largest B under both is cut so that the blocks of a round are equally
full.  **b** comes from B alone (:func:`sub_steps`): the fewest sub-blocks
of at most :data:`SUB_STEPS` steps, cut to what fills them evenly in whole
sublane groups.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from cocoa_tpu.ops import losses
from cocoa_tpu.ops.local_sdca import coef_divisor, mode_factors
from cocoa_tpu.telemetry.tracing import (SCOPE_WIDE_PRODUCTS,
                                         SCOPE_WIDE_REPLAY)

# The precision of each of the four products, by name (``PRECISIONS``).  The
# update's product is what W = w(alpha) rests on, summed over thousands of
# blocks: ``highest`` (float32 by six bfloat16 passes on a TPU).  The margins'
# and the Gram's only steer a step (an inexact step is still a feasible
# one: alpha stays in its box and W follows alpha exactly), so theirs are
# chosen from what they cost and buy on the chip (PERF.md section 6, PR 53,
# ilsvrc1k's shapes, one seed): margins and Gram at highest / high /
# default reach the certificate in the same 10 rounds, the worst class's
# gap there 7.64787e-4 / 7.64787e-4 / 7.64966e-4, in 0.5692 / 0.5125 /
# 0.4883 s a job; a block's margins take 0.70 / 0.44 / 0.30 ms and its Gram
# matrix 0.33 ms at any of the three.  ``high`` (three passes: float32 to
# ~1e-6 of a margin) for the margins is the fastest that leaves a tight
# target what it was; one pass would cap what gap a job can reach at what a
# margin's ~4e-3 error allows, for every wide job and not this cell's 1e-3
# alone.  The Gram's costs nothing at ``highest``.  The cross product of the
# two-level replay (module docstring) stands where the one-level kernel made
# exact float32 multiply-adds of G[j, i] c_i: ``highest``.
MARGINS_PRECISION = "high"
GRAM_PRECISION = "highest"
CROSS_PRECISION = "highest"
UPDATE_PRECISION = "highest"
PRECISIONS = {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
              "highest": lax.Precision.HIGHEST}

SMEM_BUDGET = 512 << 10        # a whole G (B, B), twice: B <= 256 in float32
VMEM_BUDGET = 48 << 20         # M0, alpha in, alpha out, C: (B, T_pad) twice
VMEM_LIMIT = 64 << 20          # asked of Mosaic (a v5e core has 128 MiB)
BLOCK_ALIGN = 8                # B in whole sublane groups
GROUP = 8                      # earlier steps a trip of a margin's sum adds
SUB_STEPS = 32                 # the most steps of a sub-block (PERF.md
                               # section 6, PR 54: the chain and the cross
                               # product timed at 16, 32 and 64)


@dataclasses.dataclass(frozen=True)
class BlockLanesPlan:
    """What a round of the block solve runs, from the shapes alone:
    ``block`` = B rows a step, ``blocks`` of them a shard's round (the last
    one's tail padded: ``blocks`` x ``block`` >= H), ``sub`` = b steps a
    sub-block of the replay (the last one's tail padded too), and the
    precision of each of the four products by name (``cross``: what a
    sub-block's margins are owed by the sub-blocks before it)."""
    block: int
    blocks: int
    sub: int
    margins: str = MARGINS_PRECISION
    gram: str = GRAM_PRECISION
    cross: str = CROSS_PRECISION
    update: str = UPDATE_PRECISION


def block_fits(block: int, t_pad: int, itemsize: int) -> bool:
    """Whether a block of ``block`` rows is taken: a whole G twice in SMEM
    (the one-level kernel's fit, kept as the bound on B: module docstring),
    the four (block, T_pad) arrays twice in VMEM."""
    return (2 * block * block * itemsize <= SMEM_BUDGET
            and 8 * block * t_pad * itemsize <= VMEM_BUDGET)


def _even_cut(steps: int, most: int) -> tuple:
    """``steps`` in the fewest runs of at most ``most``, every run as long:
    (the run's length, in whole sublane groups; the runs)."""
    runs = -(-steps // most)
    return -(-(-(-steps // runs)) // BLOCK_ALIGN) * BLOCK_ALIGN, runs


def sub_steps(block: int) -> int:
    """b, the steps of a sub-block of a block of ``block`` rows: the fewest
    sub-blocks of at most :data:`SUB_STEPS` steps, cut to what fills them
    evenly (B = 256: 8 of 32; B = 152: 5 of 32 for 8 padded steps; B = 8:
    the block itself)."""
    return _even_cut(block, SUB_STEPS)[0]


def block_lanes_plan(local_iters: int, t_pad: int,
                     itemsize: int = 4) -> BlockLanesPlan:
    """The plan of a round of ``local_iters`` steps a shard, T_pad lanes
    wide: the largest B that fits (:func:`block_fits`), then the fewest
    blocks that hold H at that B, then B cut to what fills those blocks
    evenly (H = 4,004: 16 blocks of 256 for 92 padded steps, not 15 full
    ones and a sixteenth of 164)."""
    fit = BLOCK_ALIGN
    while block_fits(fit + BLOCK_ALIGN, t_pad, itemsize):
        fit += BLOCK_ALIGN
    block, blocks = _even_cut(local_iters, fit)
    return BlockLanesPlan(block=block, blocks=blocks, sub=sub_steps(block))


def _index(i):
    """A dynamic index in the default integer type (the tests run with x64
    on; ``pallas_sparse_hbm._index``)."""
    return i.astype(jnp.asarray(0).dtype)


def _lane_ids(tile) -> jax.Array:
    """(R, 128) int32: the class id each position of a tile holds."""
    return (lax.broadcasted_iota(jnp.int32, tile, 0) * tile[1]
            + lax.broadcasted_iota(jnp.int32, tile, 1))


def _one_step(a, m, code, q, lane, *, classes, lam_n, coef_div, loss,
              smoothing):
    """One row's T coordinate steps on (.., R, 128) vectors: alpha ``a``,
    margins ``m``, the row's class id ``code`` (-1: a padded step, which
    moves nothing) and its sigma'-scaled squared norm ``q``, both
    broadcastable against them; ``lane`` the class id a position holds
    (the lanes past T hold no model and stay as they are).  Returns
    (alpha', c)."""
    y = jnp.where(lane == code, 1.0, -1.0).astype(a.dtype)
    new_a = losses.alpha_step(loss, a, y * m, q, lam_n, smoothing=smoothing)
    new_a = jnp.where((lane < classes) & (code >= 0), new_a, a)
    return new_a, y * (new_a - a) / coef_div


def _replay_xla(m0, gram, a0, code, q, prev, *, sig_eff, **consts):
    """One chain: B steps in order for K shards side by side, each step's
    margin summing ALL the chain's earlier steps (a sub-block of the
    two-level replay, or a whole block in one level), in plain XLA: ``m0``,
    ``a0`` (K, B, R, 128), ``gram`` (K, B, B) or None, ``code``, ``prev``
    (K, B) int32, ``q`` (K, B).  Returns (alpha' rows, C), both (K, B, R,
    128)."""
    lane = _lane_ids(m0.shape[2:])

    def take(a, j):
        return lax.dynamic_index_in_dim(a, j, 1, keepdims=False)

    def step(j, carry):
        anew, c = carry
        m = take(m0, j)
        if gram is not None:
            # c_i = 0 for i >= j: nothing past the steps made is added
            m = m + sig_eff * (take(gram, j)[:, :, None, None] * c).sum(1)
        p = take(prev, j)
        earlier = jnp.take_along_axis(
            anew, jnp.maximum(p, 0)[:, None, None, None], axis=1)[:, 0]
        a = jnp.where((p >= 0)[:, None, None], earlier, take(a0, j))
        new_a, coef = _one_step(a, m, take(code, j)[:, None, None],
                                take(q, j)[:, None, None], lane, **consts)
        return (lax.dynamic_update_index_in_dim(anew, new_a, j, 1),
                lax.dynamic_update_index_in_dim(c, coef, j, 1))

    return lax.fori_loop(0, m0.shape[1], step, (a0, jnp.zeros_like(m0)))


def _replay_kernel(*refs, sig_eff, use_gram: bool, **consts):
    """One shard's chain of B steps (a grid step): ``code``, ``prev`` (1, 1,
    B) int32 and ``q`` (1, 1, B), ``g`` (1, B, B) in SMEM; ``m0``, ``a0``
    (1, B, R, 128) in VMEM; outputs alpha' rows and C of that shape.  Step
    j's margin adds the earlier steps' G[j, i] c_i GROUP at a time (c is
    zeroed first, so a group may reach past j)."""
    code_ref, prev_ref, q_ref = refs[:3]
    g_ref = refs[3] if use_gram else None
    m0_ref, a0_ref, anew_ref, c_ref = refs[3 + use_gram:]
    b, tile = m0_ref.shape[1], m0_ref.shape[2:]
    dtype = m0_ref.dtype
    lane = _lane_ids(tile)
    c_ref[...] = jnp.zeros_like(c_ref)

    def step(j, _):
        m = m0_ref[0, _index(j)]
        if use_gram:
            def add(g, acc):
                for u in range(GROUP):
                    i = g * GROUP + u
                    acc = acc + g_ref[0, _index(j), _index(i)] \
                        * c_ref[0, _index(i)]
                return acc

            m = m + sig_eff * lax.fori_loop(
                jnp.int32(0), (j + GROUP - 1) // GROUP, add,
                jnp.zeros(tile, dtype))
        p = prev_ref[0, 0, _index(j)]
        a = jnp.where(p >= 0, anew_ref[0, _index(jnp.maximum(p, 0))],
                      a0_ref[0, _index(j)])
        new_a, coef = _one_step(
            a, m, code_ref[0, 0, _index(j)],
            jnp.full(tile, q_ref[0, 0, _index(j)], dtype), lane, **consts)
        anew_ref[0, _index(j)] = new_a
        c_ref[0, _index(j)] = coef
        return 0

    lax.fori_loop(jnp.int32(0), jnp.int32(b), step, 0)


def _replay_pallas(m0, gram, a0, code, q, prev, *, interpret: bool, **consts):
    """:func:`_replay_xla` as the Pallas kernel, a shard a grid step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, b = code.shape
    tile = m0.shape[2:]
    table = pl.BlockSpec((1, 1, b), lambda s: (s, 0, 0),
                         memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((1, b) + tile, lambda s: (s, 0, 0, 0))
    use_gram = gram is not None
    out = jax.ShapeDtypeStruct(m0.shape, m0.dtype)
    return pl.pallas_call(
        functools.partial(_replay_kernel, use_gram=use_gram, **consts),
        grid=(k,),
        in_specs=[table] * 3
        + ([pl.BlockSpec((1, b, b), lambda s: (s, 0, 0),
                         memory_space=pltpu.SMEM)] if use_gram else [])
        + [rows, rows],
        out_specs=[rows, rows], out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pallas_block_lanes_replay",
    )(code[:, None], prev[:, None], q[:, None],
      *((gram,) if use_gram else ()), m0, a0)


def _replay_two_level(chain, m0, gram, a0, code, q, prev, *, sub: int,
                      cross, sig_eff, **consts):
    """A block's B steps in order in sub-blocks of ``sub`` (module
    docstring), K shards side by side: ``chain`` is :func:`_replay_xla` or
    the Pallas kernel's, run on ``sub`` steps at a time, the other
    arguments as its own but ``m0`` (K, B, T_pad), a matrix product's
    result as it comes; ``cross`` the precision of what the earlier
    sub-blocks owe.  Returns (alpha' rows (K, B, R, 128), C (K, B, T_pad),
    a matrix product's operand as it goes): only a sub-block's margins and
    its c change form.  One chain over all B steps where no margin sums
    anything (``gram`` None) or B is one sub-block."""
    k, b = code.shape
    tile, t_pad = a0.shape[2:], m0.shape[2]
    if gram is None or sub >= b:
        with jax.named_scope(SCOPE_WIDE_REPLAY):
            anew, c = chain(m0.reshape(a0.shape), gram, a0, code, q, prev,
                            sig_eff=sig_eff, **consts)
            return anew, c.reshape(m0.shape)
    subs = -(-b // sub)
    tail = subs * sub - b
    if tail:
        def padded(a, fill=0):
            return jnp.pad(a, ((0, 0), (0, tail)) + ((0, 0),) * (a.ndim - 2),
                           constant_values=fill)

        m0, a0, q = padded(m0), padded(a0), padded(q)
        gram = jnp.pad(gram, ((0, 0), (0, tail), (0, tail)))
        code, prev = padded(code, -1), padded(prev, -1)
    sh = jnp.arange(k)[:, None]

    def one_sub(s, carry):
        anew, c = carry
        lo = s * sub

        def cut(a):
            return lax.dynamic_slice_in_dim(a, lo, sub, 1)

        with jax.named_scope(SCOPE_WIDE_PRODUCTS):
            # c's rows are zero from step lo on: the whole (sub, B) strip of
            # G takes the earlier sub-blocks' steps and nothing else
            strip = cut(gram)
            m = cut(m0) + jnp.asarray(sig_eff, m0.dtype) * jnp.einsum(
                "kbc,kct->kbt", strip, c, precision=cross)
        with jax.named_scope(SCOPE_WIDE_REPLAY):
            p = cut(prev)
            before = (p >= 0) & (p < lo)    # drawn in an earlier sub-block
            a = jnp.where(before[:, :, None, None],
                          anew[sh, jnp.maximum(p, 0)], cut(a0))
            new_a, coef = chain(
                m.reshape((k, sub) + tile),
                lax.dynamic_slice_in_dim(strip, lo, sub, 2), a, cut(code),
                cut(q), jnp.where(p >= lo, p - lo, -1), sig_eff=sig_eff,
                **consts)
            return (lax.dynamic_update_slice_in_dim(anew, new_a, lo, 1),
                    lax.dynamic_update_slice_in_dim(
                        c, coef.reshape(k, sub, t_pad), lo, 1))

    anew, c = lax.fori_loop(jnp.int32(0), jnp.int32(subs), one_sub,
                            (a0, jnp.zeros_like(m0)))
    return anew[:, :b], c[:, :b]


def block_lanes_round(w, alpha, shards: dict, idxs, lam: float, n: int,
                      classes: int, plan: BlockLanesPlan, *,
                      mode: str = "plus", sigma: float = 1.0,
                      scaling: float = 1.0, loss: str = "hinge",
                      smoothing: float = 1.0, replay: str = "xla"):
    """One round of all K shards (module docstring): ``w`` (d, R, 128),
    ``alpha`` (K, n_shard, R, 128), ``shards`` the dense arrays with one
    class id a row, ``idxs`` (K, H) the round's sampled rows.  ``replay``:
    ``xla`` | ``pallas`` | ``pallas_interpret``.  Returns (dw (d, R, 128),
    the K shards' summed; alpha after the round, the scaling law
    applied)."""
    x, cls = shards["X"], shards["classes"]
    k, n_shard, d = x.shape
    tile, dtype = w.shape[1:], w.dtype
    t_pad = tile[0] * tile[1]
    h, b, nb = idxs.shape[1], plan.block, plan.blocks
    sig_eff, qii_factor = mode_factors(mode, sigma)
    prec = {name: PRECISIONS[getattr(plan, name)]
            for name in ("margins", "gram", "cross", "update")}
    run_replay = functools.partial(
        _replay_two_level,
        _replay_xla if replay == "xla" else functools.partial(
            _replay_pallas, interpret=replay == "pallas_interpret"),
        sub=plan.sub, cross=prec["cross"], classes=classes, sig_eff=sig_eff,
        lam_n=lam * n, coef_div=coef_divisor(mode, lam * n), loss=loss,
        smoothing=smoothing)
    w2 = w.reshape(d, t_pad)
    sq_norms, qf = shards["sq_norms"], jnp.asarray(qii_factor, dtype)
    sh = jnp.arange(k)[:, None]
    pad = nb * b - h
    idx_b = jnp.pad(idxs.astype(jnp.int32), ((0, 0), (0, pad))).reshape(
        k, nb, b).swapaxes(0, 1)                        # (blocks, K, B)
    keep_b = (jnp.arange(nb * b) < h).reshape(nb, 1, b)
    at = jnp.arange(b, dtype=jnp.int32)
    earlier = at[None, :] < at[:, None]                 # [j, i]: i < j

    def one_block(carry, xs):
        vec, alpha = carry
        bidx, keep = xs
        with jax.named_scope(SCOPE_WIDE_PRODUCTS):
            xb = x[sh, bidx]                            # (K, B, d)
            if sig_eff:
                m0 = jnp.einsum("kbd,kdt->kbt", xb, vec,
                                precision=prec["margins"])
                gram = jnp.einsum("kbd,kcd->kbc", xb, xb,
                                  precision=prec["gram"])
            else:
                m0 = jnp.einsum("kbd,dt->kbt", xb, w2,
                                precision=prec["margins"])
                gram = None
        with jax.named_scope(SCOPE_WIDE_REPLAY):
            # a row drawn twice in the block: the later step starts from
            # what the earlier left, and only the last one's alpha goes back
            same = bidx[:, :, None] == bidx[:, None, :]
            prev = jnp.max(jnp.where(same & earlier, at, -1), axis=2)
            last = ~(same & earlier.T).any(axis=2)
            a0, code = alpha[sh, bidx], jnp.where(keep, cls[sh, bidx], -1)
            q = sq_norms[sh, bidx] * qf
        # (the two levels name their own scopes: the cross product is the
        # products', a sub-block's chain the replay's)
        anew, c = run_replay(m0, gram, a0, code, q, prev)
        with jax.named_scope(SCOPE_WIDE_REPLAY):
            alpha = alpha.at[sh, jnp.where(last, bidx, n_shard + at)].set(
                anew, mode="drop", unique_indices=True)
        with jax.named_scope(SCOPE_WIDE_PRODUCTS):
            if sig_eff:
                vec = vec + jnp.asarray(sig_eff, dtype) * jnp.einsum(
                    "kbd,kbt->kdt", xb, c, precision=prec["update"])
            else:
                vec = vec + jnp.einsum("kbd,kbt->dt", xb, c,
                                       precision=prec["update"])
        return (vec, alpha), None

    vec0 = (jnp.broadcast_to(w2, (k, d, t_pad)) if sig_eff
            else jnp.zeros_like(w2))
    (vec, alpha_new), _ = lax.scan(one_block, (vec0, alpha),
                                   (idx_b, keep_b))
    dw = ((vec - w2).sum(0) / jnp.asarray(sig_eff, dtype) if sig_eff
          else vec)
    if scaling != 1.0:
        # CoCoA's averaging: alpha + scaling (alpha_chain - alpha) on the
        # sampled rows (at 1, CoCoA+ with gamma = 1, the chain's alpha)
        before = alpha[sh, idxs]
        alpha_new = alpha_new.at[sh, idxs].set(
            before + scaling * (alpha_new[sh, idxs] - before))
    return dw.reshape(w.shape), alpha_new
