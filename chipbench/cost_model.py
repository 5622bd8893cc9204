"""Operations and HBM bytes one outer round needs, from shapes alone.

The yardstick's own arithmetic for the one path a cell runs today: the
dense sequential SDCA kernel.  What counts is the reference's own work
(CoCoA.scala:148-188): per coordinate step one row.w dot and one row axpy,
4 * d FLOPs, plus the margin dot x.(w + sigma' dw) the kernel recomputes
from the sampled row, 2 * d more.  Bytes are the rows a round has to read
from HBM, each once.  A cell on another path (sparse rows, the block
chain, a primal method) brings a model and a reader of its own.  The peaks
these are divided by are in ``peaks.json``, keyed by JAX's
``device_kind``; a device that is not there is an error, not a default.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE}: add it with its source, the "
                       f"benchmark assumes none")
    return table[device_kind]


def sdca_round(d: int, k: int, h: int, itemsize: int = 4) -> dict:
    """``{"flops", "hbm_bytes"}`` of one round of K shards times H steps
    over dense rows of d entries."""
    steps = k * h
    return dict(flops=6.0 * d * steps, hbm_bytes=steps * d * itemsize)


def round_floor_s(model: dict, peaks: dict, chips: int = 1) -> dict:
    """The least time ``chips`` chips could take for ``model``: the larger
    of operations over peak FLOP/s and bytes over peak HBM bytes/s."""
    flop_s = model["flops"] / (peaks["flops_per_s"] * chips)
    hbm_s = model["hbm_bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    return {"floor_s": max(flop_s, hbm_s), "flop_s": flop_s, "hbm_s": hbm_s,
            "bound": "flops" if flop_s >= hbm_s else "hbm"}
