"""A ProxCoCoA+ job on dense column shards that ends on the lasso's
duality-gap certificate."""

from __future__ import annotations

import os

import numpy as np

from chipbench import reference_lasso, registry

# the same stop rule as the other cells': the certificate (the check beside
# this file, wherever the benchmark's copy lives)
job_problem = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap").job_problem

# max |r - (A x - b)| allowed, as a share of max(1, |r|_inf): the job's
# residual is the sum of ~600 rounds' float32 updates r += sum_k dv_k, the
# reference's one product with the returned x.  The two readings the limit
# sits between (PERF.md §6, PR 34; every audit reports both): the widest the
# float32 path reads over whole jobs on the v5e (``r_err``: 1.16e-6 to
# 1.90e-6 over 53 seeds), and what the same x rounded once to bfloat16
# reads (``r_err_bf16``: 1.04e-3 to 1.55e-3; 2^-9 of each of 100 coordinates
# of size ~3 on columns of norm ~14), which must fail and did at every seed.
R_TOL = 5e-5

# |gap recomputed - gap recorded| allowed, as a share of the TARGET: both
# are float32 differences of objectives near 1.06e5 (an ulp there is
# 0.0078).  The two readings it sits between (PERF.md §6, PR 34; target
# 20.0): the float32 path's 0.002 to 0.058 over 53 seeds, and ``gap_bf16``,
# the gap with A^T r through one bf16 pass, which moves the dual point's
# scaling by 1e-4 to 6e-4 and read 1.78 to 15.68 off the recorded gap at 50
# seeds of 51.  NOT at every seed: the certificate reads A^T r only through
# its largest entry, s = min(1, lambda / |A^T r|_inf), and at one the
# rounding left that entry where it was (72.2572 for 72.2571, the gap 0.018
# off).  A job's record holds nothing else of A^T r, so no limit on it can
# tell a bf16 pass apart where the pass did not move the certificate
# (PERF.md §7).
GAP_TOL = 0.0125


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (x, r) against the plain lasso reference, from x
    alone: r = A x - b within ``R_TOL``; the recomputed gap at or under the
    target and not under zero by more than ``GAP_TOL`` of it; the job's
    recorded gap within ``GAP_TOL`` of the target of the recomputed one,
    its primal within 1e-5 relative; x zero on every padding column."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target = job["stop"]["target"]
    ref = reference_lasso.recompute(ds, run["w"], lam)
    r_ref, r_bf16 = ref.pop("r_ref"), ref.pop("r_ref_bf16")
    r = np.asarray(run["alpha"], np.float64)
    scale = max(1.0, float(np.abs(r_ref).max()))
    ref.update(r_scale=scale, r_err=float(np.abs(r - r_ref).max()) / scale,
               r_err_bf16=float(np.abs(r - r_bf16).max()) / scale)
    last = run["traj"].records[-1]
    why = job_problem(job, run)
    problems = [why] if why else []
    if not ref["r_err"] <= R_TOL:
        problems.append(f"r != A x - b: max |diff| {ref['r_err']:.3e} of "
                        f"max(1, |r|_inf) = {scale:.3e}")
    if abs(ref["gap"] - last.gap) > GAP_TOL * target:
        problems.append(f"gap: program {last.gap!r}, reference {ref['gap']!r}")
    if abs(ref["primal"] - last.primal) > 1e-5 * abs(ref["primal"]):
        problems.append(f"primal: program {last.primal!r}, reference "
                        f"{ref['primal']!r}")
    if not -GAP_TOL * target <= ref["gap"] <= target:
        problems.append(f"reference gap {ref['gap']!r} outside [0, target "
                        f"{target}]")
    if ref["x_on_padding"] > 0:
        problems.append(f"x is nonzero on {ref['x_on_padding']} padding "
                        f"column(s)")
    # what the two bf16 counter-readings would have been refused for
    ref["bf16_x_fails"] = bool(ref["r_err_bf16"] > R_TOL)
    ref["bf16_atr_fails"] = bool(
        abs(ref["gap_bf16"] - last.gap) > GAP_TOL * target)
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal,
                        "x_nnz": run["traj"].meta.get("x_nnz")}, **ref}
