"""A job on sparse rows kept as a stream that ends on the duality-gap
certificate, audited by the long-row check's comparisons against the same
plain reference (``reference_longrows.py``, which reads any stream) with
the two numbers a set's rows decide taken from the job's file, so that
another stream cell brings a job file and not a check:

    job["audit"]["w_tol"]        max |w - w(alpha)| allowed, as a share of
                                 max(1, |w|_inf): it sits between the
                                 widest ``w_err`` the audits of whole jobs
                                 read on the chip and ``w_err_bf16``, what
                                 the same w rounded once to bfloat16 reads
                                 (2^-9 |w|_inf), which must fail — a limit
                                 a bfloat16 w passes is itself a problem
    job["audit"]["block_slots"]  slots of a shard the reference takes at a
                                 time (its compile time follows the rows a
                                 shard holds; the sums are the same sums,
                                 added on the host in float64 either way)

The argument for each value is the job file's (``audit_why``)."""

from __future__ import annotations

import os

from chipbench import reference_longrows, registry

# the same stop rule as the other cells': the certificate (the check beside
# this file, wherever the benchmark's copy lives)
job_problem = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap").job_problem


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain long-row reference:
    gap within 5% of the target, primal within 1e-5 relative, alpha in
    [0, 1], w = w(alpha) within the job's ``w_tol`` and the same w rounded
    once to bfloat16 outside it, no value outside a row."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target, args = job["stop"]["target"], job["audit"]
    ref = reference_longrows.recompute(ds, run["w"], run["alpha"], lam,
                                       registry.loss_of(cell),
                                       block_slots=args["block_slots"])
    last = run["traj"].records[-1]
    limit = args["w_tol"] * max(1.0, ref["w_scale"])
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
    if not ref["w_err"] <= limit:
        problems.append(f"w != (1/(lam n)) sum y alpha x: max |diff| "
                        f"{ref['w_err']:.3e} at |w|_inf {ref['w_scale']:.3e}")
    if not ref["w_err_bf16"] > limit:
        problems.append(f"the limit on w passes a bfloat16 w: "
                        f"{ref['w_err_bf16']:.3e} <= {limit:.3e}")
    if ref["stray_values"] > 0:
        problems.append(f"{ref['stray_values']} values sit outside every row")
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal},
            "w_limit": limit, **ref}
