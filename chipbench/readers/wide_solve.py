"""L2-L1_local_solve, one-vs-rest with a wide class axis on dense rows: the
device time of one outer round's WHOLE local solve — every op whose scope
path holds ``cocoa_local_solve``, its own and those of the scopes nested in
it (``cocoa_wide_products``, ``cocoa_wide_replay``: ``scope_share`` puts an
op under its innermost scope alone, so the block round's two halves are
not in the solve scope's own seconds) — as ``part`` says:

    ms             milliseconds a round
    class_step_ns  a round over K x H steps x T class models, in ns: what
                   one class's coordinate step costs (mnist8m's
                   ``ovr_class_step_ns`` at T = 10 on the sublanes,
                   amazoncat13k's ``xmc_class_step_ns`` on sparse rows)
    roofline       the least time of a round (``cost_model_wide.py``: the
                   larger of 4 K H d T operations over the chip's peak and
                   the round's bytes over its bandwidth) over that time,
                   in percent

Nothing where the run's record states no block solve on the lanes, or where
the trace carries no program scope."""

from chipbench import cost_model, cost_model_wide, phases

SCOPE = "cocoa_local_solve"


def round_s(trace, jobs, cell, scope=SCOPE):
    """Seconds a round under ``scope`` and everything nested in it."""
    path = cell["solver_path"] or {}
    ph = phases.load(cell)
    rounds = sum(j["rounds"] for j in jobs)
    if ((path.get("inner"), path.get("class_axis")) != ("block", "lanes")
            or ph is None or not ph.scoped or not rounds):
        return None

    def inside(op):
        return any((m := phases.SCOPE.match(part)) and m.group(1) == scope
                   for part in ph.paths.get(op, "").rstrip(":").split("/"))

    return sum(s for op, s in trace.ops.items() if inside(op)) / rounds


def read(trace, jobs, cell, part="ms"):
    s = round_s(trace, jobs, cell)
    if not s:
        return None
    cfg, path = cell["config"], cell["solver_path"]
    if part == "ms":
        return 1e3 * s
    if part == "class_step_ns":
        return 1e9 * s / (cfg["num_splits"] * cell["local_iters"]
                          * path["classes"])
    if part == "roofline":
        floor = cost_model.round_floor_s(
            cost_model_wide.solve_round(cfg["num_splits"],
                                        cell["local_iters"], cfg["d"],
                                        path["classes"]),
            cost_model.peaks_for(cell["device_kind"]), cell["chips"])
        return 100.0 * floor["floor_s"] / s
    raise ValueError(f"wide_solve reads ms, class_step_ns or roofline, "
                     f"not {part!r}")
