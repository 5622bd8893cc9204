"""HBM bytes one outer round of the sparse sequential SDCA solve has to
move, from the deployment's sizes alone.

The yardstick's own arithmetic for the padded-CSR path whose state stays in
HBM (``cost_model.py`` counts the dense kernel's).  What counts is the
reference's own work (CoCoA.scala:148-188) on sparse rows: per coordinate
step the row's nonzeros are read once (column and value, 8 bytes each),
w is read at each of them (4), dw is read and written at each of them (8);
and the step's scalars — y, |x|^2 and alpha read, alpha written — are 16
bytes.  Nothing else has to cross HBM: the remap to local ids, the sorts,
the tables that feed SMEM and the padding to W are the program's own
choices, and all count against it.  Divided by ``peaks.json``'s bandwidth
this is the floor a round cannot beat.
"""

from __future__ import annotations

NNZ_BYTES = 8 + 4 + 8           # row entry, w read, dw read and written
STEP_BYTES = 16                 # y, |x|^2, alpha in; alpha out


def round_bytes(steps: float, nonzeros: float) -> float:
    """Least HBM bytes of ``steps`` coordinate steps over rows that hold
    ``nonzeros`` nonzeros in all."""
    return NNZ_BYTES * nonzeros + STEP_BYTES * steps


def sparse_round(k: int, h: int, mean_nnz: float) -> dict:
    """``{"hbm_bytes", "steps", "nonzeros"}`` of one round of K shards
    times H steps over rows of ``mean_nnz`` nonzeros on average."""
    steps = k * h
    return dict(steps=steps, nonzeros=steps * mean_nnz,
                hbm_bytes=round_bytes(steps, steps * mean_nnz))
