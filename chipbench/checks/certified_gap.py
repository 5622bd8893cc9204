"""A job that ends on the duality-gap certificate (CoCoA / CoCoA+)."""

from __future__ import annotations

from chipbench import reference, registry


def job_problem(job: dict, run: dict):
    """Why a timed job counts as failed, or None."""
    target = job["stop"]["target"]
    traj = run["traj"]
    gap = traj.records[-1].gap if traj.records else None
    if traj.stopped != "target" or gap is None or not gap <= target:
        return (f"no certificate: stopped={traj.stopped!r}, gap={gap} after "
                f"{run['rounds']} rounds (target {target})")
    return None


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain reference.

    Tolerances, and why: the program's gap is a float32 difference of two
    objectives near 0.1-1, each a sum over up to 4e5 rows, so it carries an
    absolute error of a few 1e-7; the reference adds its K partial sums in
    float64.  ``gap_tol`` = 5% of the target (5e-6 at 1e-4) leaves that
    room and no more — margins through one bf16 pass move the primal by
    ~1e-3, two hundred times the tolerance.  w: the chip's float32
    accumulation differed from a float64 recomputation by 2.3e-7 to 7.1e-6
    at |w|_inf 1.2 to 4.0 (PR 21's chip runs), so 1e-5 * max(1, |w|_inf)
    is about twice that band; bf16 would be off by 4e-3 * |w|_inf."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target = job["stop"]["target"]
    ref = reference.recompute(ds, run["w"], run["alpha"], lam,
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
    if not ref["w_err"] <= 1e-5 * max(1.0, ref["w_scale"]):
        problems.append(f"w != (1/(lam n)) sum y alpha x: max |diff| "
                        f"{ref['w_err']:.3e} at |w|_inf {ref['w_scale']:.3e}")
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal}, **ref}
