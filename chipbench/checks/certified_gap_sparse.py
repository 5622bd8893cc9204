"""A job on padded-CSR shards that ends on the duality-gap certificate."""

from __future__ import annotations

import os

from chipbench import reference_sparse, registry

# the same stop rule as the dense cells': the certificate (the check beside
# this file, wherever the benchmark's copy lives)
job_problem = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap").job_problem

# max |w - w(alpha)| allowed, as a share of max(1, |w|_inf).  Why it is not
# the dense cells' 1e-5: w's hottest column gets a term from most rows (a
# Zipf column law: column 0 is in 3/4 of the rows), so it is a float32 sum
# of ~10^6 terms a round where a dense column's is 5,000, made once by the
# kernel's running += and once by the reference's scatter-add, in two
# orders: typical float32 error sqrt(10^6) * 6e-8 = 6e-5 of the sum.
# Measured on the v5e (PERF.md §6, PR 26): one round's dw against one
# scatter-add of the same coefficients differs by 1.4e-4 at |dw|_inf 7.7
# (1.8e-5 of it); the audits of whole jobs read 4.6e-4 to 6.7e-4 at |w|_inf
# 7.7 to 9.6 (5.9e-5 to 6.9e-5 of it) under the stand-in's first label law
# and 2.9e-5 to 6.4e-5 at 3.2 to 3.3 (0.9e-5 to 2.0e-5) under its last.  The
# tolerance is three times the wider band and a twentieth of what one bf16
# pass over w would give (4e-3 * |w|_inf).
W_TOL = 2e-4


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain sparse reference:
    gap within 5% of the target, primal within 1e-5 relative, alpha in
    [0, 1], w = w(alpha) within ``W_TOL`` — ``certified_gap``'s audit with
    the reference and the w tolerance of sparse rows."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target = job["stop"]["target"]
    ref = reference_sparse.recompute(ds, run["w"], run["alpha"], lam,
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
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal}, **ref}
