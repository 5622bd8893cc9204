"""Literal NumPy oracle of the reference update rules.

Transcribes the *math* of the Scala reference (with file:line citations) as
plainly as possible — deliberately unvectorized and slow, so the production
JAX kernels have an independent ground truth to match bit-closely in x64.

All functions operate on dense numpy rows; padding/masking concerns of the
device layouts do not exist here.
"""

from __future__ import annotations

import numpy as np


def local_sdca(
    X: np.ndarray,          # (n_local, d) dense rows of this shard
    y: np.ndarray,          # (n_local,) labels in {-1, +1}
    w_init: np.ndarray,     # (d,) shared primal vector
    alpha: np.ndarray,      # (n_local,) local dual variables (copied, not mutated)
    idxs: np.ndarray,       # (H,) sampled coordinates for this round
    lam: float,
    n: int,                 # GLOBAL example count (primal-dual correspondence)
    plus: bool,
    sigma: float,           # sigma' = K * gamma (CoCoA.scala:45)
):
    """Reference localSDCA (CoCoA.scala:130-192). Returns (delta_alpha, delta_w)."""
    w = w_init.copy()
    alpha = alpha.copy()
    alpha_old = alpha.copy()
    delta_w = np.zeros_like(w_init)
    lam_n = lam * n

    for idx in idxs:
        x = X[idx]
        yy = y[idx]
        # hinge-loss gradient (CoCoA.scala:157-163)
        if plus:
            grad = (yy * (x @ w + sigma * (x @ delta_w)) - 1.0) * lam_n
        else:
            grad = (yy * (x @ w) - 1.0) * lam_n
        # projection onto the box-constraint active set (CoCoA.scala:166-170)
        proj_grad = grad
        if alpha[idx] <= 0.0:
            proj_grad = min(grad, 0.0)
        elif alpha[idx] >= 1.0:
            proj_grad = max(grad, 0.0)
        if abs(proj_grad) != 0.0:
            xnorm2 = float(x @ x)
            qii = xnorm2 * sigma if plus else xnorm2  # CoCoA.scala:173-174
            new_alpha = 1.0
            if qii != 0.0:
                new_alpha = min(max(alpha[idx] - grad / qii, 0.0), 1.0)
            update = x * (yy * (new_alpha - alpha[idx]) / lam_n)  # :181
            if not plus:
                w = w + update               # local view advances (:182-184)
            delta_w = delta_w + update       # :185
            alpha[idx] = new_alpha           # :186
    return alpha - alpha_old, delta_w


def minibatch_cd_partition(
    X, y, w_init, alpha, idxs, lam, n, scaling
):
    """Reference MinibatchCD.partitionUpdate (MinibatchCD.scala:76-132).

    Like localSDCA but the gradient always reads the frozen w (:104) and the
    local w never advances; alpha *does* advance within the batch (:123).
    Returns (delta_w, alpha_scaled) where alpha_scaled = alpha_old +
    scaling * delta_alpha (:127-128).
    """
    alpha = alpha.copy()
    alpha_old = alpha.copy()
    delta_w = np.zeros_like(w_init)
    lam_n = lam * n
    for idx in idxs:
        x = X[idx]
        yy = y[idx]
        grad = (yy * (x @ w_init) - 1.0) * lam_n
        proj_grad = grad
        if alpha[idx] <= 0.0:
            proj_grad = min(grad, 0.0)
        elif alpha[idx] >= 1.0:
            proj_grad = max(grad, 0.0)
        if abs(proj_grad) != 0.0:
            qii = float(x @ x)
            new_alpha = 1.0
            if qii != 0.0:
                new_alpha = min(max(alpha[idx] - grad / qii, 0.0), 1.0)
            delta_w = delta_w + x * (yy * (new_alpha - alpha[idx]) / lam_n)
            alpha[idx] = new_alpha
    return delta_w, alpha_old + scaling * (alpha - alpha_old)


def sgd_partition(X, y, w_init, idxs, lam, t_global, local):
    """Reference SGD.partitionUpdate (SGD.scala:87-139).

    local=True: Pegasos-style steps on a private w copy, eta = 1/(lam*(t+i)),
    returns w - w_init (:117-134).  local=False: sum of raw hinge
    subgradients x*y over the draws (:124-127).
    """
    w = w_init.copy()
    delta_w = np.zeros_like(w_init)
    for i, idx in enumerate(idxs, start=1):
        step = 1.0 / (lam * (t_global + i))
        x = X[idx]
        yy = y[idx]
        evaluation = 1.0 - yy * (x @ w)
        if local:
            w = w * (1.0 - step * lam)
        if evaluation > 0:
            delta_w = delta_w + x * yy
            if local:
                w = w + x * (yy * step)
        if local:
            delta_w = w - w_init
    return delta_w


def dist_gd_partition(X, y, w_init, lam, include_oob_bug: bool = False):
    """Reference DistGD.partitionUpdate (DistGD.scala:67-102).

    Deterministic pass over the shard accumulating active-hinge subgradients,
    then the per-worker regularizer term -lam*w_init (:98).  The reference's
    inclusive loop bound (`0 to nLocal`, :82) reads one element past the end —
    we fix that (SURVEY.md reference bug #1); ``include_oob_bug`` exists only
    to document the deviation, not to reproduce a JVM crash.
    """
    if include_oob_bug:
        raise NotImplementedError("the out-of-bounds read is a reference bug")
    delta_w = np.zeros_like(w_init)
    for i in range(X.shape[0]):
        x = X[i]
        yy = y[i]
        if 1.0 - yy * (x @ w_init) > 0:
            delta_w = delta_w + x * yy
    return delta_w - lam * w_init


# ---- objectives (OptUtils.scala:57-98) ----

def hinge_loss(X, y, w):
    return np.maximum(1.0 - y * (X @ w), 0.0)


def primal_objective(X, y, w, lam):
    return hinge_loss(X, y, w).mean() + 0.5 * lam * float(w @ w)


def dual_objective(w, alpha_total_sum, n, lam):
    return -0.5 * lam * float(w @ w) + alpha_total_sum / n


def duality_gap(X, y, w, alpha_total_sum, lam):
    return primal_objective(X, y, w, lam) - dual_objective(
        w, alpha_total_sum, X.shape[0], lam
    )


def classification_error(X, y, w):
    return float(np.mean((X @ w) * y <= 0))


# ---- outer loops (driver-side math only) ----

def cocoa_outer(
    shards,              # list of (X_k, y_k) per shard
    w0, lam, n, num_rounds, h, beta, gamma, seed, plus,
    sample_fn,           # (seed, t, n_local) -> (H,) idx array
):
    """Reference runCoCoA (CoCoA.scala:22-66): per-round local SDCA on every
    shard, sum-reduce delta_w, w += scaling * sum, alpha_k += scaling * da_k."""
    k = len(shards)
    scaling = gamma if plus else beta / k
    sigma = k * gamma
    w = w0.copy()
    alphas = [np.zeros(Xk.shape[0]) for Xk, _ in shards]
    for t in range(1, num_rounds + 1):
        dw_sum = np.zeros_like(w)
        for s, (Xk, yk) in enumerate(shards):
            idxs = sample_fn(seed, t, Xk.shape[0])
            da, dw = local_sdca(Xk, yk, w, alphas[s], idxs, lam, n, plus, sigma)
            alphas[s] = alphas[s] + scaling * da
            dw_sum += dw
        w = w + scaling * dw_sum
    return w, alphas


# --- T one-vs-rest chains over one X (tests/test_wide_classes.py) ----------


def _logistic_step(a, z, qii, lam_n):
    """The logistic coordinate step to convergence, in float64: the root
    u of g(u) = u + z + (qii / lam_n) (sigmoid(u) - a) = 0, g increasing
    with g' >= 1, by 60 Newton steps from the current alpha's logit."""
    ac = np.clip(a, 1e-12, 1 - 1e-12)
    q = qii / lam_n
    u = np.clip(np.log(ac / (1 - ac)), -35.0, 35.0)
    for _ in range(60):
        sig = 1.0 / (1.0 + np.exp(-u))
        u = np.clip(u - (u + z + q * (sig - ac)) / (1 + q * sig * (1 - sig)),
                    -35.0, 35.0)
    return 1.0 / (1.0 + np.exp(-u))


def ovr_local_sdca(X, cls, W, alpha, idxs, lam, n, sigma, loss="hinge",
                   dtype=np.float32):
    """T sequential CoCoA+ chains of one shard over ONE index stream, class
    t against the rest, written out: no blocks, no Gram matrix, a row dot
    and a row axpy a step and a class.  ``X`` (n_local, d), ``cls``
    (n_local,) class ids, ``W`` (T, d), ``alpha`` (T, n_local), ``idxs``
    (H,).  The T chains are independent, so a step advances them side by
    side (one NumPy expression over the class axis); the arithmetic of a
    chain is ``local_sdca``'s, in ``dtype``.  Returns (alpha', dW (T, d))."""
    t_count = W.shape[0]
    X = X.astype(dtype)
    W = W.astype(dtype)
    alpha = alpha.astype(dtype).copy()
    dW = np.zeros_like(W)
    lam_n = dtype(lam * n)
    sigma = dtype(sigma)
    for idx in idxs:
        x = X[idx]
        y = np.where(np.arange(t_count) == cls[idx], 1, -1).astype(dtype)
        a = alpha[:, idx]
        z = y * (W @ x + sigma * (dW @ x))
        qii = dtype(x @ x) * sigma
        if loss == "hinge":
            grad = (z - 1) * lam_n
            proj = np.where(a <= 0, np.minimum(grad, 0),
                            np.where(a >= 1, np.maximum(grad, 0), grad))
            step = (np.clip(a - grad / qii, 0, 1) if qii != 0
                    else np.ones_like(a))
            new_a = np.where(proj != 0, step, a).astype(dtype)
        elif loss == "logistic":
            new_a = _logistic_step(a.astype(np.float64), z.astype(np.float64),
                                   float(qii), float(lam_n)).astype(dtype)
        else:
            raise ValueError(loss)
        dW += np.outer(y * (new_a - a) / lam_n, x).astype(dtype)
        alpha[:, idx] = new_a
    return alpha, dW


def ovr_cocoa_plus(X_shards, cls_shards, tables, lam, n, t_count,
                   loss="hinge", gamma=1.0, dtype=np.float32):
    """CoCoA+ (adding, sigma' = K gamma) over K shards for T classes from
    alpha = 0, W = 0: ``tables`` (rounds, K, H) local row ids, the job's
    index stream.  Returns (W (T, d), [alpha_k (T, n_k)])."""
    k = len(X_shards)
    W = np.zeros((t_count, X_shards[0].shape[1]), dtype)
    alphas = [np.zeros((t_count, x.shape[0]), dtype) for x in X_shards]
    for table in tables:
        dW = np.zeros_like(W)
        for s in range(k):
            alphas[s], dws = ovr_local_sdca(
                X_shards[s], cls_shards[s], W, alphas[s], table[s], lam, n,
                k * gamma, loss, dtype)
            dW += dws
        W = W + dtype(gamma) * dW
    return W, alphas


def labelset_cocoa_plus(indptr, indices, values, label_ids, bounds, tables,
                        lam, n, t_count, d, loss="hinge", gamma=1.0):
    """One-vs-rest CoCoA+ over LABEL SETS from CSR rows, in float64: T
    chains side by side, class t against the rest, y_ti = +1 where t is
    among row i's ids (``label_ids`` (n, L), -1 fills a short set).  Shard
    s holds rows [bounds[s], bounds[s + 1]); ``tables`` (rounds, K, H) are
    the job's sampled local row ids, ``d`` the columns.  A step is ``ovr_local_sdca``'s over a
    sparse row: a dot and an axpy over the row's own nonzeros.  Returns (W
    (d, T), [alpha_k (n_k, T)])."""
    k = len(bounds) - 1
    lam_n, sigma = lam * n, k * gamma
    W = np.zeros((d, t_count))
    alphas = [np.zeros((bounds[s + 1] - bounds[s], t_count))
              for s in range(k)]
    classes = np.arange(t_count)
    for table in tables:
        dW = np.zeros_like(W)
        for s in range(k):
            dws = np.zeros_like(W)
            for idx in table[s]:
                i = bounds[s] + idx
                c = indices[indptr[i]:indptr[i + 1]]
                x = values[indptr[i]:indptr[i + 1]].astype(np.float64)
                y = np.where((label_ids[i][:, None] == classes).any(0), 1.0,
                             -1.0)
                a = alphas[s][idx]
                z = y * (x @ W[c] + sigma * (x @ dws[c]))
                qii = float(x @ x) * sigma
                if loss == "hinge":
                    grad = (z - 1) * lam_n
                    proj = np.where(a <= 0, np.minimum(grad, 0),
                                    np.where(a >= 1, np.maximum(grad, 0),
                                             grad))
                    step = (np.clip(a - grad / qii, 0, 1) if qii != 0
                            else np.ones_like(a))
                    new_a = np.where(proj != 0, step, a)
                elif loss == "logistic":
                    new_a = _logistic_step(a, z, qii, lam_n)
                else:
                    raise ValueError(loss)
                dws[c] += np.outer(x, y * (new_a - a) / lam_n)
                alphas[s][idx] = new_a
            dW += dws
        W = W + gamma * dW
    return W, alphas


def labelset_gaps(indptr, indices, values, label_ids, W, alpha, lam,
                  loss="hinge"):
    """Every class's (primal, dual, gap) at (W (d, T), alpha (n, T)) over
    CSR rows that carry label sets, in float64."""
    n, t_count = alpha.shape
    classes = np.arange(t_count)
    loss_sum, dual_sum = np.zeros(t_count), np.zeros(t_count)
    for i in range(n):
        c = indices[indptr[i]:indptr[i + 1]]
        x = values[indptr[i]:indptr[i + 1]].astype(np.float64)
        y = np.where((label_ids[i][:, None] == classes).any(0), 1.0, -1.0)
        z, a = y * (x @ W[c]), alpha[i]
        if loss == "hinge":
            loss_sum += np.maximum(0.0, 1.0 - z)
            dual_sum += a
        else:
            loss_sum += np.logaddexp(0.0, -z)
            ac = np.clip(a, 1e-300, 1.0)
            bc = np.clip(1.0 - a, 1e-300, 1.0)
            dual_sum += -(a * np.log(ac) + (1 - a) * np.log(bc))
    reg = 0.5 * lam * (W * W).sum(0)
    primal, dual = loss_sum / n + reg, dual_sum / n - reg
    return primal, dual, primal - dual
