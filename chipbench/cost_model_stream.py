"""HBM bytes one all-rows pass over sparse rows kept as a stream has to
move, from the deployment's sizes alone.

The yardstick's own arithmetic for the certificate's margins x_i . w over
every row (``cost_model_sparse.py`` counts a round's sampled rows).  What
counts is what the pass cannot do without: each nonzero's column and value
read once (8 bytes), each row's start, length, label and alpha read and
its margin written (20), w read once (4 d).  The padding to a row's next
slot group, the slots a chunk brings beyond the row and the spare at a
shard's end are the program's own choices, and all count against it.
Divided by ``peaks.json``'s bandwidth this is the floor a pass cannot
beat.
"""

from __future__ import annotations

NNZ_BYTES = 8                   # column, value
ROW_BYTES = 20                  # start, length, label, alpha in; margin out


def pass_bytes(n: int, d: int, mean_nnz: float, itemsize: int = 4) -> float:
    """Least HBM bytes of one pass over ``n`` rows of ``mean_nnz``
    nonzeros on average against a d-vector."""
    return NNZ_BYTES * n * mean_nnz + ROW_BYTES * n + itemsize * d
