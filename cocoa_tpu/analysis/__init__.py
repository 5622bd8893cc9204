"""jaxlint: repo-native static analysis + runtime sanitizers.

``python -m cocoa_tpu.analysis`` lints the package against this repo's
proven JAX failure classes (donation misses, silent host syncs, f64
leaks, Pallas budget drift) and exits
nonzero on any finding that is neither inline-suppressed
(``# jaxlint: allow=<rule> -- reason``) nor carried by the committed
baseline with a justification.  See docs/DESIGN.md §10.

Submodules import lazily: ``analysis.sanitize`` is wired into the hot
drivers (solvers/base.py) and must not drag the ops/AST machinery in
with it.
"""

from __future__ import annotations

__all__ = ["run_analysis", "RULES"]


def __getattr__(name):
    # RULES lives in rules.py (single source of truth); resolve lazily so
    # importing the package — the drivers import analysis.sanitize on the
    # hot path — never pays for the AST machinery
    if name == "RULES":
        from cocoa_tpu.analysis.rules import RULES

        return RULES
    raise AttributeError(name)


def run_analysis(root=None, targets=None, baseline_path=None,
                 with_budget_checks=True):
    """Run every rule; returns (findings, sources, stale_baseline_entries).
    Findings come back fingerprinted, with inline suppressions and the
    baseline applied.  On a targeted run (``targets`` an explicit subset)
    baseline staleness is scoped to the scanned files."""
    from cocoa_tpu.analysis import core, rules

    root = root or core.repo_root()
    scoped = targets is not None and list(targets) != list(core.DEFAULT_SCAN)
    targets = tuple(targets) if targets else core.DEFAULT_SCAN
    sources = {}
    for rel in core.iter_py_files(root, targets):
        src = core.load_source(root, rel)
        if src is not None:
            sources[src.path] = src
    findings = rules.run_static_rules(sources)
    if with_budget_checks:
        from cocoa_tpu.analysis import pallas_budget

        findings += pallas_budget.run_budget_checks()
    core.fingerprint_findings(findings, sources)
    core.apply_suppressions(findings, sources)
    baseline = core.load_baseline(baseline_path or core.BASELINE_PATH)
    stale = core.apply_baseline(
        findings, baseline,
        scanned_paths=set(sources) if scoped else None)
    return findings, sources, stale
