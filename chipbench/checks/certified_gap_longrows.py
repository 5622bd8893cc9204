"""A job on sparse rows kept as a stream that ends on the duality-gap
certificate."""

from __future__ import annotations

import os

from chipbench import reference_longrows, registry

# the same stop rule as the other cells': the certificate (the check beside
# this file, wherever the benchmark's copy lives)
job_problem = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap").job_problem

# max |w - w(alpha)| allowed, as a share of max(1, |w|_inf).  kddb's
# argument (checks/certified_gap_sparse.py), redone for these rows.  Every
# row's first column is column 0 (the dense head of a long row), so w[0]
# gets a term from every one of the K x H = 35,000 steps of a round (17,500
# at the half share) where a dense cell's column gets 5,000 and kddb's
# hottest 1.4 million: a float32 sum of 35,000 x rounds terms, made once by
# the kernel's running += and once by the reference's scatter-add, in two
# orders: typical float32 error sqrt(terms) * 6e-8 of the sum of their
# sizes, 1e-5 to 1e-4 of |w|_inf over 10 to 100 rounds.  The two readings
# the limit sits between (PERF.md §6, PR 30): the widest the audits of whole
# jobs read on the v5e, and ``w_err_bf16``, what w rounded once to bfloat16
# reads against the same w(alpha) (2^-9 |w|_inf = 2e-3 |w|_inf, reported by
# every audit beside ``w_err``), which must fail.
W_TOL = 2e-4


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain long-row reference:
    gap within 5% of the target, primal within 1e-5 relative, alpha in
    [0, 1], w = w(alpha) within ``W_TOL``, no value outside a row."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target = job["stop"]["target"]
    ref = reference_longrows.recompute(ds, run["w"], run["alpha"], lam,
                                       registry.loss_of(cell))
    last = run["traj"].records[-1]
    why = job_problem(job, run)
    problems = [why] if why else []
    if abs(ref["gap"] - last.gap) > 0.05 * target:
        problems.append(f"gap: program {last.gap!r}, reference {ref['gap']!r}")
    if abs(ref["primal"] - last.primal) > 1e-5 * abs(ref["primal"]):
        problems.append(f"primal: program {last.primal!r}, reference "
                        f"{ref['primal']!r}")
    if not ref["gap"] <= target:
        problems.append(f"reference gap {ref['gap']!r} > target {target}")
    if ref["alpha_min"] < -1e-6 or ref["alpha_max"] > 1 + 1e-6:
        problems.append(f"alpha left [0, 1]: [{ref['alpha_min']}, "
                        f"{ref['alpha_max']}]")
    if not ref["w_err"] <= W_TOL * max(1.0, ref["w_scale"]):
        problems.append(f"w != (1/(lam n)) sum y alpha x: max |diff| "
                        f"{ref['w_err']:.3e} at |w|_inf {ref['w_scale']:.3e}")
    if ref["stray_values"] > 0:
        problems.append(f"{ref['stray_values']} values sit outside every row")
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal}, **ref}
