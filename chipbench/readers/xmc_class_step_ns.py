"""L2-L1_local_solve, one-vs-rest with the class axis on the lanes: device
time of the local-solve scope per outer round over its K x H coordinate
steps (the shards run one after another) and over T class models again, in
ns — what one class's coordinate step costs once T of them share the
sampled row's fetch, to set beside ``ctr_step_ns`` (the same over K x H
alone), kddb's ns a step and mnist8m's ``ovr_class_step_ns``.  T is what
the run's record says; nothing where it states no class axis on the
lanes."""

from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_local_solve"):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if path.get("class_axis") != "lanes" or not path.get("classes"):
        return None
    s = scope_share.round_s(trace, jobs, cell, scope)
    return (1e9 * s / (cfg["num_splits"] * cell["local_iters"]
                       * path["classes"]) if s else None)
