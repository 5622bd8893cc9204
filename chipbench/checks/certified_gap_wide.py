"""A one-vs-rest job over DENSE rows with a WIDE class axis — T class
models on the lanes, a block of rows a step — that ends when EVERY class
holds its duality-gap certificate: ``certified_gap_ovr``'s audit (the check
beside this file) in row blocks, against ``reference_wide.py``, with its
limits taken from the job's file:

    job["audit"]["w_tol"]    max |w_t - w_t(alpha_t)| allowed over every
                             class, as a share of max(1, |w_t(alpha_t)|_inf).
                             It sits between the widest ``w_err`` the audits
                             of whole jobs read on the chip and the least
                             ``w_err_bf16`` (the same W rounded once to
                             bfloat16), which must fail: a limit a bfloat16
                             W passes on every class is itself a problem.
    job["audit"]["gap_tol"]  |gap_t recomputed - gap_t recorded| allowed,
                             for every class, as a share of the target.

The program returns W (d, R, 128) and alpha (K, n_shard, R, 128), class t
at [t // 128, t % 128]; the lanes past T hold no model and must hold
zeros.  The argument for each value is the job file's (``audit_why``)."""

from __future__ import annotations

import os

from chipbench import reference_wide, registry

# the stop rule of a job over T classes: every class's certificate (the
# check beside this file, wherever the benchmark's copy lives)
_stop_rule = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap_ovr").job_problem


def job_problem(job: dict, run: dict):
    """Why a timed job counts as failed, or None (``certified_gap_ovr``'s
    rule, read from the job's records).  The caller then lets go of the
    job's (W, alpha), as ``certified_gap_labels`` does: the harness keeps
    ``run`` bound while the next job starts, and a finished job's 1.3 GB
    beside the running job's is a reading of how many jobs a window held,
    not of what a trainer holds."""
    why = _stop_rule(job, run)
    run["w"] = run["alpha"] = None
    return why


def audit(cell: dict, ds, run: dict) -> dict:
    """The warm-up job's (W, alpha) against the plain wide reference, from
    alpha and the rows' class ids alone, for EVERY class: w_t =
    w_t(alpha_t) within ``w_tol`` and the same W rounded to bfloat16
    outside it; the recomputed gap at or under the target and within
    ``gap_tol`` of the target of the recorded one; alpha in [0, 1]; the
    worst class's primal within 1e-5 relative; nothing on the lanes past
    T."""
    job, lam = cell["job"], cell["config"]["lambda"]
    target, args = job["stop"]["target"], job["audit"]
    t_count = cell["config"]["num_classes"]
    why = _stop_rule(job, run)
    if why:         # no per-class record to hold the reference against
        return {"ok": False, "problems": [why]}
    ref = reference_wide.recompute(ds, run["w"], run["alpha"], lam,
                                   registry.loss_of(cell))
    last = run["traj"].records[-1]
    problems = []
    if len(last.class_gaps) != t_count or ds.num_classes != t_count:
        problems.append(f"{len(last.class_gaps)} certificates over a set of "
                        f"{ds.num_classes} classes for {t_count}")
    off = [abs(r - g) for r, g in zip(ref["gaps"], last.class_gaps)]
    off_bf16 = [abs(r - g) for r, g in zip(ref["gaps_bf16"],
                                           last.class_gaps)]
    for t, (gap, d_gap, w_err) in enumerate(zip(ref["gaps"], off,
                                                ref["w_err"])):
        if d_gap > args["gap_tol"] * target:
            problems.append(f"class {t} gap: program {last.class_gaps[t]!r},"
                            f" reference {gap!r}")
        if not gap <= target:
            problems.append(f"class {t}: reference gap {gap!r} > target "
                            f"{target}")
        if not w_err <= args["w_tol"]:
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
    if ref["pad_lanes_max"] != 0.0:
        problems.append(f"the lanes past T = {t_count} hold "
                        f"{ref['pad_lanes_max']!r}, not zeros")
    if not max(ref["w_err_bf16"]) > args["w_tol"]:
        problems.append(f"the limit on w passes a bfloat16 W on every "
                        f"class: at most {max(ref['w_err_bf16']):.3e} <= "
                        f"{args['w_tol']:.3e}")
    # the readings the limits sit between, and what the two bf16
    # counter-readings would have been refused for
    summary = dict(
        w_err_max=max(ref["w_err"]), w_err_bf16_least=min(ref["w_err_bf16"]),
        w_err_bf16_max=max(ref["w_err_bf16"]), gap_off_max=max(off),
        gap_off_bf16_max=max(off_bf16), gap_off_bf16_least=min(off_bf16),
        gap_max=max(ref["gaps"]), worst_class=worst,
        w_scale_min=min(ref["w_scale"]), w_scale_max=max(ref["w_scale"]),
        bf16_margins_fail=bool(max(off_bf16) > args["gap_tol"] * target),
        bf16_w_fails=bool(max(ref["w_err_bf16"]) > args["w_tol"]))
    return {"ok": not problems, "problems": problems[:20],
            "program": {"gap": last.gap, "primal": last.primal,
                        "class_gaps": last.class_gaps}, **summary, **ref}
