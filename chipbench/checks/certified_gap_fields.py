"""A logistic job on padded-CSR rows of one length that ends on the
duality-gap certificate: ``certified_gap_sparse``'s audit (the check beside
this file: gap, primal, the certificate and w = w(alpha) against
``reference_sparse.py``, here under ``loss="logistic"``: the entropy dual),
and on top of it what these rows decide, taken from the job's file:

    job["audit"]["w_tol"]      max |w - w(alpha)| allowed, as a share of
                               max(1, |w|_inf).  It sits between the widest
                               ``w_err`` the audits of whole jobs read on
                               the chip and what the same w rounded once to
                               bfloat16 reads, which must fail: a limit a
                               bfloat16 w passes is itself a problem.
    job["audit"]["alpha_eps"]  every real row's alpha lies in
                               [eps, 1 - eps]: the entropy dual's domain,
                               strictly inside the box (the Newton step
                               ends in a sigmoid and never clamps).

The reference hands back ``w_err`` = max |w - w(alpha)| and not w(alpha), so
the rounded w is read without a second pass over the rows:
max |bf16(w) - w(alpha)| >= max |bf16(w) - w| - ``w_err`` (the triangle
inequality), reported as ``w_err_bf16_least``; the rounding itself is
~2^-9 |w|_inf, a hundred times any ``w_err`` seen.  The argument for each
value is the job file's (``audit_why``)."""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from chipbench import registry

# the sparse cells' audit and stop rule (the check beside this file,
# wherever the benchmark's copy lives)
_sparse = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap_sparse")
job_problem = _sparse.job_problem


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain sparse reference, as
    kddb's check audits it, and beside that: every real row's alpha in
    [eps, 1 - eps], w = w(alpha) within the job's ``w_tol`` (at most the
    sparse check's own) and the same w rounded once to bfloat16 outside
    it."""
    args = cell["job"]["audit"]
    out = _sparse.audit(cell, ds, run)
    limit = args["w_tol"] * max(1.0, out["w_scale"])
    w = jnp.asarray(run["w"], jnp.float32)
    rounding = float(jnp.abs(w.astype(jnp.bfloat16).astype(jnp.float32)
                             - w).max())
    alpha = np.asarray(run["alpha"])[np.asarray(ds.mask) > 0]
    out.update(w_err_bf16_least=rounding - out["w_err"], w_limit=limit,
               alpha_min_real=float(alpha.min()),
               alpha_max_real=float(alpha.max()))
    problems, eps = out["problems"], args["alpha_eps"]
    if not (eps <= out["alpha_min_real"]
            and out["alpha_max_real"] <= 1.0 - eps):
        problems.append(f"alpha left [{eps}, 1 - {eps}]: "
                        f"[{out['alpha_min_real']}, {out['alpha_max_real']}]")
    if not out["w_err"] <= limit:
        problems.append(f"w != (1/(lam n)) sum y alpha x: max |diff| "
                        f"{out['w_err']:.3e} over the job's limit "
                        f"{limit:.3e}")
    if not out["w_err_bf16_least"] > limit:
        problems.append(f"the limit on w passes a bfloat16 w: at least "
                        f"{out['w_err_bf16_least']:.3e} <= {limit:.3e}")
    out["ok"] = not problems
    return out
