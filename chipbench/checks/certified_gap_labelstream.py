"""A one-vs-rest job over label sets on sparse rows kept as a STREAM — T
class models, the class axis on the lanes — that ends when EVERY class
holds its duality-gap certificate: ``certified_gap_labels``'s audit and
stop rule (the check beside this file), word for word, held against
``reference_labelstream.py`` — the reference that reads streamed rows —
with its limits taken from the job's file:

    job["audit"]["w_tol"]    max |w_t - w_t(alpha_t)| allowed over every
                             class, as a share of max(1, |w_t(alpha_t)|_inf),
                             between the widest ``w_err`` the audits of
                             whole jobs read on the chip and the least
                             ``w_err_bf16``, which must fail.
    job["audit"]["gap_tol"]  |gap_t recomputed - gap_t recorded| allowed,
                             for every class, as a share of the target.

The argument for each value is the job file's (``audit_why``).  A judged
job's (W, alpha) is let go of, as there: a window holds one job's 4.0 GB of
state, not two."""

from __future__ import annotations

import os

from chipbench import reference_labelstream, registry

# this file's OWN copy of the labels check (``load_module`` executes the
# file anew: the copy amazoncat13k's cell loads is another object), reading
# the stream's reference where that one reads the rectangle's
_labels = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "checks", "certified_gap_labels")
_labels.reference_labels = reference_labelstream

job_problem = _labels.job_problem
audit = _labels.audit
