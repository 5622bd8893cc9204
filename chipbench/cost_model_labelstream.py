"""Bytes no implementation avoids, for one-vs-rest over label sets on
sparse rows kept as a STREAM (T class models over one X whose rows lie end
to end, the class axis on the lanes): the floors
``labelstream_solve_roofline`` and ``labelstream_eval_roofline`` read.

``cost_model_labels``' law at the stream's 8 B a nonzero.  A step of the
local solve is charged its row's nonzeros as the stream holds them (column
and value, 8 B each), its scalars (16 B: the sampled index, the row's
start, its length, its squared norm) and the row's T alphas in and out
(8 T B).  What is NOT charged is the T-wide row of W and of dw a nonzero —
a chain that keeps the hot columns on the chip need not move them, so they
are an implementation's bytes, not the problem's — nor, here, the 12 B a
nonzero the rectangle's floor charges for ONE model's w and dw at the
column: with the class axis on the lanes there is no such model-wide
traffic a chain could not keep on the chip.  The share therefore reads the
same work whatever implements it and cannot pass 100%; a chain that moves
12 T B a nonzero (a DMA ring of 4 KB rows) reads three orders under it.

An evaluation reads every nonzero once (8 B), every row's scalars (20 B:
start, length, mask, norm, and the label ids' share) and its T alphas
(4 T B), and W once (4 d T B)."""

from __future__ import annotations

NNZ_BYTES = 8.0                 # column, value


def solve_round_bytes(shards: int, steps: int, mean_nnz: float,
                      classes: int) -> float:
    """One round of the local solve: K x H steps over rows of ``mean_nnz``
    nonzeros, T models wide."""
    n_steps = shards * steps
    return n_steps * mean_nnz * NNZ_BYTES + n_steps * (16.0 + 8.0 * classes)


def eval_pass_bytes(n: int, d: int, mean_nnz: float, classes: int) -> float:
    """One certificate evaluation: every row once, T margins a row."""
    return (NNZ_BYTES * n * mean_nnz + (20.0 + 4.0 * classes) * n
            + 4.0 * d * classes)
