"""Operations and bytes no implementation avoids, for one-vs-rest with a
WIDE class axis on DENSE rows (T class models over one dense X, the class
axis on the lanes, a block of rows a step): the floors
``wide_solve_roofline`` and ``wide_eval_roofline`` read.

A coordinate step of class t on row i is one dot x_i . (w_t + sigma' dw_t)
and one axpy dw_t += c x_i: 4 d floating-point operations, whatever batches
them — so a round of K shards x H steps x T classes is 4 K H d T, and that
is ``cost_model.sdca_round``'s reference count (its third dot is the
sequential kernel's own) with a class axis.  What is NOT charged is the
Gram matrix of a block (2 B d a step, shared by all T classes: an
implementation's way of replaying a block, not the problem's) nor any
second pass a precision takes (a float32 product is three to six bfloat16
passes of the matrix unit: the peak in ``peaks.json`` is the bfloat16 one,
so a round at ``highest`` reads at most a sixth).  The bytes are the sampled
rows once, their T alphas in and out, W in and dW out.  An evaluation is
one margin a row and a class, 2 n d T, over every row, every alpha and W
once.  The share therefore reads the same work whatever implements it and
cannot pass 100%; which of the two bounds it the reader says nowhere: at
these shapes both floors are the operations' (4,096-wide rows against a
thousand classes: 244 operations a byte of a row)."""

from __future__ import annotations


def solve_round(shards: int, steps: int, d: int, classes: int,
                itemsize: int = 4) -> dict:
    """``{"flops", "hbm_bytes"}`` of one round of the local solve: K x H
    steps over dense rows of d entries, T models wide."""
    n_steps = shards * steps
    return dict(
        flops=4.0 * n_steps * d * classes,
        hbm_bytes=float(n_steps * (d + 2 * classes) * itemsize
                        + 2 * d * classes * itemsize))


def eval_pass(n: int, d: int, classes: int, itemsize: int = 4) -> dict:
    """``{"flops", "hbm_bytes"}`` of one certificate evaluation: every
    row's T margins, every row, its T alphas and W read once."""
    return dict(flops=2.0 * n * d * classes,
                hbm_bytes=float((n * (d + classes) + d * classes)
                                * itemsize))
