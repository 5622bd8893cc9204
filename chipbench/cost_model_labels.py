"""Bytes no implementation avoids, for one-vs-rest over label sets on
sparse rows (T class models over one padded-CSR X, the class axis on the
lanes): the floors ``xmc_solve_roofline`` and ``xmc_eval_roofline`` read.

They are ``cost_model_sparse.sparse_round``'s floor with a class axis on
what must have one.  A step of the local solve is charged the sparse
floor's 20 B a nonzero of its row (column and value, 8 B; w once and dw in
and out at that column as ONE model's, 12 B), its scalars (16 B: the
sampled index, the row's length, its squared norm, its start) and the row's
T alphas in and out (8 T B).  What is NOT charged is the T-wide row of W
and of dw a nonzero: a chain that keeps the hot columns on the chip need
not move them, so they are an implementation's bytes, not the problem's.
The share therefore reads the same work whatever implements it and cannot
pass 100%; a chain that moves 12 T B a nonzero (the DMA ring) reads three
orders under it.

An evaluation reads every nonzero once (8 B), every row's scalars (20 B:
length, mask, norm, start, and the label ids' share) and its T alphas
(4 T B), and W once (4 d T B)."""

from __future__ import annotations


def solve_round_bytes(shards: int, steps: int, mean_nnz: float,
                      classes: int) -> float:
    """One round of the local solve: K x H steps over rows of ``mean_nnz``
    nonzeros, T models wide."""
    n_steps = shards * steps
    return n_steps * mean_nnz * 20.0 + n_steps * (16.0 + 8.0 * classes)


def eval_pass_bytes(n: int, d: int, mean_nnz: float, classes: int) -> float:
    """One certificate evaluation: every row once, T margins a row."""
    return (8.0 * n * mean_nnz + (20.0 + 4.0 * classes) * n
            + 4.0 * d * classes)
