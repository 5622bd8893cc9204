"""A step's walk over a row's slots in the HBM-state sparse chain
(ops/pallas_sparse_hbm.py, PR 47), interpret mode on the CPU: every way
the kernel covers a row's slots gives the same round to the bit.  A file
of its own beside tests/test_sparse_hbm.py, whose helpers it uses: each
case traces three kernels, and the suite hands a file to one worker.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.ops import pallas_sparse_hbm as ph

from test_sparse_hbm import F32, LAM, PLAN, _fori

WALK_LENGTHS = (1, 7, 8, 9, 31, 32, 33, 39, 40, 63, 64)
WALK_ROWS = {
    # every length a slot loop can end on, a group's last slot and the
    # first of the next: the grouped walk's trip counts all differ
    "mixed_64": lambda r, shape: r.choice(WALK_LENGTHS, shape),
    # criteo's: every row 39 nonzeros in a rectangle 40 wide
    "all_39_of_40": lambda r, shape: np.full(shape, 39),
}
WALK_ALGS = [(loss, mode) for loss in ("hinge", "logistic", "smooth_hinge")
             for mode in ("plus", "frozen")]


def _exact_rows(lengths, width, d, seed):
    """(K, n, W) padded-CSR rows of the given lengths, no column twice in
    a row, every value +- a power of two: with sigma' a power of two as
    well every product of the two slot loops is exact, so a multiply and
    an add round the same fused or apart (XLA's CPU backend fuses them by
    where its fusions fall, and two forms of one kernel fall apart
    differently: 1 ulp of a dw entry, which is not the kernel's)."""
    r = np.random.RandomState(seed)
    cols = np.zeros(lengths.shape + (width,), np.int32)
    vals = np.zeros(lengths.shape + (width,), F32)
    for at in np.ndindex(*lengths.shape):
        n = lengths[at]
        cols[at][:n] = np.sort(r.choice(d, n, replace=False))
        vals[at][:n] = r.choice([-1.0, 1.0], n) * 2.0 ** -r.randint(2, 5, n)
    y = np.where(r.randn(*lengths.shape) >= 0, 1.0, -1.0).astype(F32)
    return cols, vals, y, (vals.astype(np.float64) ** 2).sum(-1).astype(F32)


@pytest.mark.parametrize("ids", ["direct", "sorted"])
@pytest.mark.parametrize("loss,mode", WALK_ALGS,
                         ids=[f"{lo}-{m}" for lo, m in WALK_ALGS])
@pytest.mark.parametrize("rows", list(WALK_ROWS))
def test_every_walk_of_a_rows_slots_gives_the_same_round(rows, loss, mode,
                                                         ids, monkeypatch):
    """A step's two passes over a row's slots, three ways: all ``w_r``
    slots written out (``HbmPlan.unrolled``: rows of one length); the
    first 32 written out and past them the row's whole 32-slot groups and
    then its 8-slot groups (the plan ``hbm_plan`` answers otherwise); and
    the parent's count of slots, whole 32-slot groups over tables of the
    32-rounded width.  A slot past a row's length adds ``row x 0.0`` to
    the margin and stores under a false mask, so (dw, alpha) of a round
    are equal to the bit, whatever the row's length, on rows sampled twice
    in a segment (their alpha is the earlier step's output) and in a
    segment that ends on padded steps (``cnt`` -1: no trip, and the
    written-out slots store nothing); and equal to the ``fori`` path as
    every round of this file is."""
    k, n_shard, d, h = 2, 48, 300, 50
    r = np.random.RandomState(7)
    lengths = WALK_ROWS[rows](r, (k, n_shard))
    width = ph._w_round(int(lengths.max()))
    assert width == (64 if rows == "mixed_64" else 40)
    cols, vals, y, sq = _exact_rows(lengths, width, d, seed=8)
    idxs = r.randint(0, n_shard, size=(k, h)).astype(np.int32)
    w = (r.randn(d) * 0.1).astype(F32)
    alpha = r.rand(k, n_shard).astype(F32)
    n, sigma = k * n_shard, (float(k) if mode == "plus" else 1.0)
    args = [jnp.asarray(a) for a in (w, alpha, cols, vals, y, sq, idxs)]

    def plan_at(w_r, unrolled):
        if ids == "direct":
            return dataclasses.replace(ph.hbm_plan(d, w_r, h, 4),
                                       unrolled=unrolled)
        return PLAN(t=2, s=32, m=-(-32 * w_r // 1024) * 1024, w_r=w_r,
                    chunk=32, direct=False, unrolled=unrolled)

    def round_of(plan, tail=ph.TAIL_GROUP):
        # (the module's constant is read when the round is traced and the
        # jitted entry's cache is keyed on the plan alone: a jit of its own
        # around the function under it, traced here)
        monkeypatch.setattr(ph, "TAIL_GROUP", tail)
        return jax.jit(functools.partial(
            ph.pallas_sparse_hbm_round.__wrapped__, lam=LAM, n=n, mode=mode,
            sigma=sigma, interpret=True, loss=loss, smoothing=0.5,
            plan=plan))(*args)

    plan = plan_at(width, False)
    assert plan.direct == (ids == "direct")
    assert plan.t * plan.s > h                              # padded steps
    segs = np.pad(idxs, ((0, 0), (0, plan.t * plan.s - h)),
                  constant_values=-1).reshape(-1, plan.s)
    assert max(np.unique(seg[seg >= 0], return_counts=True)[1].max()
               for seg in segs) >= 2                        # sampled twice
    grouped = round_of(plan)
    unrolled = round_of(plan_at(width, True))
    parents = round_of(plan_at(-(-width // ph.GROUP) * ph.GROUP, False),
                       tail=ph.GROUP)
    for other in (unrolled, parents):
        for a, b in zip(other, grouped):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    dw_f, a_f = _fori(*args, n, mode, sigma, loss, smoothing=0.5)
    assert float(jnp.abs(a_f - args[1]).max()) > 0.02       # the round moved
    np.testing.assert_allclose(grouped[0], dw_f, atol=2e-6, rtol=0)
    np.testing.assert_allclose(grouped[1], a_f, atol=2e-6, rtol=0)
