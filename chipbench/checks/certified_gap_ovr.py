"""A one-vs-rest job — T class models over one set of rows — that ends when
EVERY class holds its duality-gap certificate."""

from __future__ import annotations

from chipbench import reference_ovr, registry

# max |w_t - w_t(alpha_t)| allowed, over every class, as a share of
# max(1, |w_t(alpha_t)|_inf).  The dense cells' argument
# (checks/certified_gap.py: 1e-5 there, over 20 rounds of 5,000 steps),
# redone for this job: a coordinate of w_t is a float32 sum of K x H x
# rounds = 1e5 x 40 rank-one terms, made once by the kernel's running +=
# (a shard's chain, then the shards left to right, then the rounds) and
# once by the reference's sums over whole shards, in two orders.  The two
# readings the limit sits between (PERF.md section 6, PR 38; every audit
# reports both): the widest the float32 path read over whole jobs on the
# v5e (``w_err``: 1.8e-6 over 23 seeds x ten classes, 40 rounds),
# and what the same w rounded once to bfloat16 reads (``w_err_bf16``: up
# to 2^-9 |w_t|_inf; 1.3e-3 at the least over the same 230), which must
# fail and did at every seed.
W_TOL = 1e-4

# |gap_t recomputed - gap_t recorded| allowed, for every class, as a share
# of the TARGET.  The other checks allow 5% (checks/certified_gap.py); this
# one sits between the two readings every audit reports (PERF.md section
# 6, PR 38; v5e, target 1e-4): the float32 program against this float32
# reference (``gap_off_max``: both are differences of two objectives near
# 0.84, each a sum over 1e6 rows, in two orders) and what the margins
# X . W^T through ONE bfloat16 pass of the matrix unit would move the worst
# of the ten gaps by (``gap_off_bf16_max``: a hinge primal is a mean over
# 1e6 rows, so the margins' 1e-3 errors mostly cancel and what is left is
# a few 1e-6).  Over 23 seeds the first read 0.86e-7 to 2.2e-7, the
# second 4.1e-6 to 1.33e-5; 1% of the target is 1e-6, 4.5 times the
# largest of the one and 4.1 times under the smallest of the other.  5%
# (5e-6) would have passed the bf16 certificate at three seeds of the 23.
GAP_TOL = 0.01


def job_problem(job: dict, run: dict):
    """Why a timed job counts as failed, or None: no certificate on some
    class, or a stop that is not at an evaluation."""
    target = job["stop"]["target"]
    traj = run["traj"]
    last = traj.records[-1] if traj.records else None
    gaps = getattr(last, "class_gaps", None)
    if (traj.stopped != "target" or not gaps
            or not all(g <= target for g in gaps)
            or not last.gap <= target or last.gap != max(gaps)):
        return (f"no certificate on every class: stopped={traj.stopped!r}, "
                f"gap={getattr(last, 'gap', None)}, per class {gaps} after "
                f"{run['rounds']} rounds (target {target})")
    every = job.get("debug", {}).get("debug_iter", 1)
    if run["rounds"] % every:
        return f"stopped at round {run['rounds']}, not at an evaluation"
    return None


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (w, alpha) against the plain one-vs-rest reference,
    from alpha and the class ids alone, for EVERY class: w_t = w_t(alpha_t)
    within ``W_TOL``; the recomputed gap at or under the target and within
    ``GAP_TOL`` of the target of the recorded one; alpha in [0, 1]; the
    worst class's primal within 1e-5 relative."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target = job["stop"]["target"]
    why = job_problem(job, run)
    if why:         # no per-class record to hold the reference against
        return {"ok": False, "problems": [why]}
    ref = reference_ovr.recompute(ds, run["w"], run["alpha"], lam,
                                  registry.loss_of(cell))
    last = run["traj"].records[-1]
    problems = []
    if run["w"].shape[0] != cell["config"]["num_classes"]:
        problems.append(f"{run['w'].shape[0]} models for "
                        f"{cell['config']['num_classes']} classes")
    off = [abs(r - g) for r, g in zip(ref["gaps"], last.class_gaps)]
    off_bf16 = [abs(r - g) for r, g in zip(ref["gaps_bf16"],
                                           last.class_gaps)]
    for t, (gap, d_gap, w_err) in enumerate(zip(ref["gaps"], off,
                                                ref["w_err"])):
        if d_gap > GAP_TOL * target:
            problems.append(f"class {t} gap: program {last.class_gaps[t]!r},"
                            f" reference {gap!r}")
        if not gap <= target:
            problems.append(f"class {t}: reference gap {gap!r} > target "
                            f"{target}")
        if not w_err <= W_TOL:
            problems.append(f"class {t}: w != (1/(lam n)) sum y alpha x: "
                            f"max |diff| {w_err:.3e} of max(1, |w|_inf = "
                            f"{ref['w_scale'][t]:.3e})")
    worst = max(range(len(off)), key=last.class_gaps.__getitem__)
    if abs(ref["primal"][worst] - last.primal) > 1e-5 * abs(
            ref["primal"][worst]):
        problems.append(f"primal of class {worst}: program {last.primal!r}, "
                        f"reference {ref['primal'][worst]!r}")
    if ref["alpha_min"] < -1e-6 or ref["alpha_max"] > 1 + 1e-6:
        problems.append(f"alpha left [0, 1]: [{ref['alpha_min']}, "
                        f"{ref['alpha_max']}]")
    # what the two bf16 counter-readings would have been refused for
    ref["gap_off_max"] = max(off)
    ref["gap_off_bf16_max"] = max(off_bf16)
    ref["bf16_margins_fail"] = bool(max(off_bf16) > GAP_TOL * target)
    ref["bf16_w_fails"] = bool(max(ref["w_err_bf16"]) > W_TOL)
    return {"ok": not problems, "problems": problems,
            "program": {"gap": last.gap, "primal": last.primal,
                        "class_gaps": last.class_gaps}, **ref}
