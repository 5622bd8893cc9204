"""L2-L1_local_solve: the round's floor from the shapes (``cost_model.py``:
the larger of FLOPs over peak and row bytes over HBM bandwidth) over the
device time of the local-solve scope alone, so the kernel's share and not
the round's (``round_roofline`` divides by eval, copies and reduce too).
Dense sequential Pallas path only, as ``round_roofline``.  Over 100% means
the model counts too few bytes."""

from chipbench.readers import round_roofline, scope_share


def read(trace, jobs, cell, scope):
    s = scope_share.round_s(trace, jobs, cell, scope)
    floor = round_roofline.floor_of(cell)
    return None if not s or floor is None else 100.0 * floor["floor_s"] / s
