"""Device time by the program's own scope names (``jax.named_scope``,
``phases.py``): self seconds of the ops under ``scope`` over busy time, in
percent; ``scope=None`` is the ops under no program scope (compiler-made
copies, loop shells and bookkeeping).  ``per_round=True``: milliseconds per
outer round instead (the window's seconds over the rounds its jobs ran).
Nothing where no op of the trace carries a program scope."""

from chipbench import phases


def scope_s(trace, cell, scope):
    """Seconds of the traced window under ``scope``, or None."""
    ph = phases.load(cell)
    if ph is None or not ph.scoped or not trace.busy_s:
        return None
    return phases.scope_seconds(ph, trace.ops).get(scope, 0.0)


def round_s(trace, jobs, cell, scope):
    """Seconds per outer round under ``scope``, or None."""
    s, rounds = scope_s(trace, cell, scope), sum(j["rounds"] for j in jobs)
    return None if s is None or not rounds else s / rounds


def read(trace, jobs, cell, scope=None, per_round=False):
    if per_round:
        s = round_s(trace, jobs, cell, scope)
        return None if s is None else 1e3 * s
    s = scope_s(trace, cell, scope)
    return None if s is None else 100.0 * s / trace.busy_s
