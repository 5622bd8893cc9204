"""Pluggable loss objectives for the primal-dual solvers.

The reference is hinge-only but explicitly designed for swappable local
solvers/objectives (README.md:14, CoCoA.scala:13-14); BASELINE.md lists the
smoothed-hinge / logistic local-solver variant as an evaluation config.  This
module is the single place a loss is defined; solvers and evals look
everything up by ``params.loss`` name so adding a loss means adding one entry
here plus an oracle for the tests.

Each loss ℓ acts on the margin z = y·(x·w) and ships four pieces:

- ``primal(z)``      — elementwise loss value (the avg-loss term of the
                        primal objective, OptUtils.scala:65-75 shape)
- ``dual_term(a)``   — per-example −ℓ*(−α) so the dual objective is
                        −(λ/2)‖w‖² + Σ dual_term(αᵢ)/n (OptUtils.scala:80-84
                        generalized; for hinge this is Σα/n exactly)
- ``grad_factor(z)`` — g(z) = −ℓ'(z) ∈ [0,1]; (sub)gradient methods
                        accumulate y·g(z)·x (SGD.scala:124-127 generalized,
                        where hinge's g is the 0/1 "active" indicator)
- ``alpha_step(a, z, qii, lam_n)`` — the SDCA single-coordinate dual ascent
                        update (CoCoA.scala:166-178 generalized), with qii
                        already σ′-scaled by the caller

Closed forms (α ∈ [0,1] throughout; derivations in the docstrings):

- hinge           ℓ(z) = max(0, 1−z);      −ℓ*(−α) = α
- smooth_hinge(s) ℓ(z) = 0 | 1−z−s/2 | (1−z)²/(2s);  −ℓ*(−α) = α − s·α²/2
                  (quadratically smoothed hinge, SDCA smoothing parameter s)
- logistic        ℓ(z) = log(1+e^{−z});    −ℓ*(−α) = entropy
                  −α·log α − (1−α)·log(1−α); coordinate step has no closed
                  form → damped Newton on the scalar subproblem
"""

from __future__ import annotations

import jax.numpy as jnp

LOSSES = ("hinge", "smooth_hinge", "logistic")
# scalar prox rules for the primal (ProxCoCoA+) solvers — valid for
# alpha_step but NOT classification losses (no primal/dual_term/grad_factor)
PROX_RULES = ("lasso",)

# α clamp for logistic: the entropy dual needs α ∈ (0,1) strictly
_EPS = 1e-12
_U_MAX = 35.0  # |logit| cap: σ(±35) is exact 0/1 in f32, underflow-safe
_NEWTON_ITERS = 10

# losses whose ``alpha_step`` is an iterative solve: a chain of dependent
# transcendentals (logistic: a log, _NEWTON_ITERS x (exp, two divides, a
# clip), a last sigmoid), where the others are a handful of selects.  A
# kernel that advances K chains in lockstep reads this, never a loss's
# name, to solve the K steps as one vector (ops/pallas_sdca.py).
ITERATIVE_STEPS = frozenset({"logistic"})


def step_is_iterative(loss: str) -> bool:
    """Whether ``alpha_step(loss, ...)`` iterates (see ITERATIVE_STEPS)."""
    return loss in ITERATIVE_STEPS


def validate(loss: str, smoothing=None) -> str:
    if loss not in LOSSES + PROX_RULES:
        raise ValueError(
            f"loss must be one of {LOSSES + PROX_RULES}, got {loss!r}"
        )
    if loss == "smooth_hinge" and smoothing is not None and smoothing <= 0.0:
        # s ≤ 0 flips the ascent denominator's sign / divides by zero
        raise ValueError(
            f"smooth_hinge needs smoothing > 0, got {smoothing}"
        )
    if loss == "lasso" and smoothing is not None and smoothing < 0.0:
        raise ValueError(
            f"lasso's smoothing is the elastic-net l2 weight, needs >= 0, "
            f"got {smoothing}"
        )
    return loss


def primal(loss: str, z, smoothing: float = 1.0):
    """Elementwise ℓ(z) on the margin z = y·(x·w)."""
    if loss == "hinge":
        return jnp.maximum(1.0 - z, 0.0)  # OptUtils.scala:57-61
    if loss == "smooth_hinge":
        s = smoothing
        # 0 for z≥1; 1−z−s/2 for z≤1−s; (1−z)²/(2s) between
        gap = 1.0 - z
        return jnp.where(
            gap <= 0.0,
            0.0,
            jnp.where(gap >= s, gap - 0.5 * s, 0.5 * gap * gap / s),
        )
    if loss == "logistic":
        # stable softplus(−z)
        return jnp.logaddexp(0.0, -z)
    raise ValueError(f"unknown loss {loss!r}")


def dual_term(loss: str, a, smoothing: float = 1.0):
    """Per-example −ℓ*(−α): the dual objective is
    −(λ/2)‖w‖² + Σ dual_term(αᵢ)/n."""
    if loss == "hinge":
        return a  # Σα/n term, OptUtils.scala:82-83
    if loss == "smooth_hinge":
        return a - 0.5 * smoothing * a * a
    if loss == "logistic":
        # xlogy gives the correct 0·log0 = 0 limit at the box corners — an
        # eps-clip is NOT enough: in f32, 1 − 1e-12 rounds to exactly 1.0 and
        # (1−α)·log1p(−α) becomes 0·(−inf) = NaN once a coordinate saturates
        from jax.scipy.special import xlogy

        ac = jnp.clip(a, 0.0, 1.0)
        return -(xlogy(ac, ac) + xlogy(1.0 - ac, 1.0 - ac))
    raise ValueError(f"unknown loss {loss!r}")


def grad_factor(loss: str, z, smoothing: float = 1.0):
    """g(z) = −ℓ'(z) ∈ [0,1]; (sub)gradient methods accumulate y·g·x.
    Hinge's subgradient choice matches the reference exactly: active iff
    1 − z > 0 (SGD.scala:115,124 — the flat side takes 0 at z=1)."""
    if loss == "hinge":
        return jnp.where(1.0 - z > 0.0, 1.0, 0.0)
    if loss == "smooth_hinge":
        return jnp.clip((1.0 - z) / smoothing, 0.0, 1.0)
    if loss == "logistic":
        return jnp.where(z >= 0.0, jnp.exp(-z) / (1.0 + jnp.exp(-z)),
                         1.0 / (1.0 + jnp.exp(z)))  # σ(−z), stable both tails
    raise ValueError(f"unknown loss {loss!r}")


def alpha_step(loss: str, a, z, qii, lam_n, smoothing: float = 1.0):
    """Single-coordinate update: SDCA dual ascent → new α ∈ [0,1] for the
    classification losses; prox-CD → new (unbounded) coordinate value for
    the ``PROX_RULES``.

    ``z`` is the margin the subproblem sees (mode-dependent: w, w+Δw, or
    w+σ′Δw — the caller computes it); ``qii`` is the σ′-scaled ‖x‖².

    Elementwise over ``a``, ``z``, ``qii`` of any ONE shape (pure ``jnp``,
    no reduction, no data-dependent control flow): 0-d values give one
    step, a vector gives one independent step per element with the same
    operations in the same order.  Where the solve runs is the caller's
    choice: the ``fori`` kernels, the VMEM-resident sparse kernel, the
    stream's chain and the block kernels call it on one coordinate's
    scalars; the dense Pallas kernel calls a closed-form step chain by
    chain on (1, 1) vectors (no value of its step is 0-d: pallas_sdca
    ``_advance``) and an iterative loss's step (``step_is_iterative``)
    once for all the shards it advances in lockstep, one shard per lane
    (pallas_sdca ``_solve_in_lanes``); the sparse kernel whose state stays
    in HBM runs one chain at a time and calls every loss's step on that
    chain's (1, 1) vectors (pallas_sparse_hbm ``_chain_kernel``).

    - hinge: the reference's exact sequence — projected gradient against the
      box's active face, vanishing-gradient no-op, qii==0 → 1, clip
      (CoCoA.scala:166-178).
    - smooth_hinge: maximizing δ in the smoothed dual adds an s·λn quadratic
      to the denominator and an s·α term to the gradient:
      α ← clip(α − ((z−1+s·α)·λn) / (qii + s·λn), 0, 1); s→0 recovers hinge
      (and qii==0 no longer needs a special case — the denominator is >0).
    - logistic: ∂δ of [entropy(α+δ)/n − z·δ/n − δ²·qii/(2λn²)] = 0 has no
      closed form.  Solved in logit space u = log(α′/(1−α′)) where the
      stationarity condition becomes g(u) = u + z + q·(σ(u) − α) = 0 with
      q = qii/λn: g is strictly increasing with g′ = 1 + q·σ′(u) ≥ 1, so
      Newton is well-conditioned everywhere and the box constraint is
      enforced by the sigmoid itself (no boundary clamping that can stall).
    """
    if loss == "hinge":
        grad = (z - 1.0) * lam_n
        proj_grad = jnp.where(
            a <= 0.0,
            jnp.minimum(grad, 0.0),
            jnp.where(a >= 1.0, jnp.maximum(grad, 0.0), grad),
        )
        safe_qii = jnp.where(qii != 0.0, qii, 1.0)
        new_a = jnp.where(
            qii != 0.0, jnp.clip(a - grad / safe_qii, 0.0, 1.0), 1.0
        )
        return jnp.where(proj_grad != 0.0, new_a, a)
    if loss == "smooth_hinge":
        s = smoothing
        grad = (z - 1.0 + s * a) * lam_n
        return jnp.clip(a - grad / (qii + s * lam_n), 0.0, 1.0)
    if loss == "logistic":
        ac = jnp.clip(a, _EPS, 1.0 - _EPS)
        q = qii / lam_n
        u = jnp.clip(jnp.log(ac / (1.0 - ac)), -_U_MAX, _U_MAX)
        for _ in range(_NEWTON_ITERS):
            sig = 1.0 / (1.0 + jnp.exp(-u))
            g = u + z + q * (sig - ac)
            gp = 1.0 + q * sig * (1.0 - sig)
            u = jnp.clip(u - g / gp, -_U_MAX, _U_MAX)
        return 1.0 / (1.0 + jnp.exp(-u))
    if loss == "lasso":
        # ProxCoCoA+ primal coordinate step (mode="prox"): ``a`` is the
        # working coordinate value x_j + Δx_j, ``z`` the σ′-corrected
        # gradient a_jᵀ(r₀ + σ′Δv), ``qii`` = σ′·‖a_j‖², ``lam_n`` the L1
        # weight λ, ``smoothing`` the elastic-net l2 weight s (0 = lasso).
        # Exact minimizer over t = a + δ of
        #   (z − qii·a)·t + (qii + s)/2·t² + λ|t|
        # is the soft-threshold t* = S_{λ/(qii+s)}((qii·a − z)/(qii + s));
        # a zero column with s=0 (qii==0) is a no-op.
        denom = qii + smoothing
        safe = jnp.where(denom > 0.0, denom, 1.0)
        u = (qii * a - z) / safe
        thr = lam_n / safe
        t = jnp.sign(u) * jnp.maximum(jnp.abs(u) - thr, 0.0)
        return jnp.where(denom > 0.0, t, a)
    raise ValueError(f"unknown loss {loss!r}")
