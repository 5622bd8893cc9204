"""T one-vs-rest models over DENSE rows with the class axis on the LANES, a
block of rows a step (ops/block_lanes.py), on the CPU.

- the system against ``tests/oracle.py``'s T sequential float32 chains over
  the job's own index stream after 1, 2 and 5 rounds at T = 17, 40 and 130
  (d no multiple of 128, n no multiple of B, H no multiple of B), hinge and
  logistic, rows drawn twice inside a block, through both replays (XLA's
  loop and the interpreted Pallas kernel) — tight enough that a W or a
  certificate through one bfloat16 rounding fails;
- the two-level replay of a block (sub-blocks of b steps in order, what the
  earlier sub-blocks owe as a matrix product) against the one-level chain
  over all B steps, on a block that holds a row drawn twice in two
  sub-blocks, one drawn twice inside a sub-block and a padded tail;
- alpha in its box, W = w(alpha), nothing on the lanes past T, the
  worst-class stop; CoCoA's averaging and mini-batch CD against the class
  axis on the sublanes (``fori``); the same job through the CLI;
- what the resolver answers: a set the sublane kernel holds stays there, one
  it does not goes to the lanes, from the shapes alone (the cell's own
  shapes compile for a described v5e in tests/test_device_layout.py);
- the certificate in row blocks against the one-``einsum`` certificate.

A test steers the resolver the way the guide asks: it shrinks the sublane
kernel's VMEM budget (``pallas_sdca.CLASS_VMEM_BUDGET``), which is what a
wide set outgrows at the cell's size; the program has no flag for it."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import oracle  # noqa: E402

LAM = 1e-2
SEED = 3

# What the program's W and alpha may differ from the oracle's by, as a share
# of max(1, |.|_inf).  Both run the same steps on the same rows in float32;
# the program's margin is M0[j] + sigma' sum_i G[j, i] c_i where the oracle
# reduces x . (w + sigma' dw) anew, so a margin moves by a few float32 ulps
# of its terms (d <= 70 products, up to B Gram terms), a step by that times
# lambda n / (sigma' |x|^2) <= 10, and five rounds of <= 900 steps add up:
# 1.1e-6 is the widest read over the cases below (logistic, whose oracle is
# the root itself and not ten Newton steps, reads 4e-6).  One bfloat16
# rounding of W is 2^-9 |w| = 2e-3 |w|_inf at the largest coordinate.
TOL = 2e-5


@pytest.fixture
def wide(monkeypatch):
    """The sublane kernel holds nothing: every dense multi-class set of the
    test resolves to the lanes, as T = 1,000 does at any size."""
    from cocoa_tpu.ops import pallas_sdca

    monkeypatch.setattr(pallas_sdca, "CLASS_VMEM_BUDGET", 0)


def standin(n, d, k, t, seed=0):
    """Seeded unit rows with one class id each, as a ``ShardedDataset``."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)

    r = np.random.default_rng(seed)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    x = np.zeros((k, n_shard, d), np.float32)
    cls = np.zeros((k, n_shard), np.int32)
    mask = np.zeros((k, n_shard), np.float32)
    for s, c in enumerate(sizes):
        rows = r.normal(size=(c, d))
        x[s, :c] = rows / np.linalg.norm(rows, axis=1, keepdims=True)
        cls[s, :c] = r.integers(0, t, c)
        mask[s, :c] = 1
    y = np.where(cls == 0, 1.0, -1.0).astype(np.float32) * mask
    return ShardedDataset(
        layout="dense", n=n, num_features=d, counts=sizes.astype(np.int64),
        labels=jnp.asarray(y), mask=jnp.asarray(mask),
        sq_norms=jnp.asarray((x * x).sum(-1)), X=jnp.asarray(x),
        classes=jnp.asarray(cls), num_classes=t)


def run_job(ds, *, rounds, loss="hinge", rng="reference", chain="xla",
            frac=0.3, target=None, every=None, **kw):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.solvers import run_cocoa

    h = max(1, int(frac * ds.n / ds.k))
    return h, run_cocoa(
        ds, Params(n=ds.n, num_rounds=rounds, local_iters=h, lam=LAM,
                   loss=loss),
        DebugParams(debug_iter=every or rounds, seed=SEED), quiet=True,
        math="fast", device_loop=True, rng=rng, gap_target=target,
        accel="off", block_chain=chain, **{"plus": True, **kw})


def by_class(ds, w, alpha):
    """The program's (W (d, R, 128), alpha (K, n_shard, R, 128)) as the
    oracle holds them: W (T, d), [alpha_k (T, n_k)]."""
    from cocoa_tpu.data.sharding import class_vector

    t = ds.num_classes
    return (np.asarray(class_vector(w, t)).T,
            [np.asarray(class_vector(alpha[s, :c], t)).T
             for s, c in enumerate(ds.counts)])


def oracle_job(ds, h, rounds, loss, rng):
    """The oracle over the job's own index stream."""
    from cocoa_tpu.solvers import base

    sampler = base.IndexSampler(rng, SEED, h, ds.counts)
    tables = np.stack([np.asarray(sampler.round_indices(r))
                       for r in range(1, rounds + 1)])
    xs = [np.asarray(ds.X[s, :c]) for s, c in enumerate(ds.counts)]
    cs = [np.asarray(ds.classes[s, :c]) for s, c in enumerate(ds.counts)]
    return tables, oracle.ovr_cocoa_plus(xs, cs, tables, LAM, ds.n,
                                         ds.num_classes, loss)


def as_bf16(a):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32))


# --- against the oracle ------------------------------------------------------

CASES = [
    # n, d, t, rounds, loss, rng, chain
    (2003, 37, 17, 1, "hinge", "reference", "xla"),
    (2003, 37, 17, 2, "hinge", "reference", "pallas_interpret"),
    (2003, 37, 17, 5, "hinge", "permuted", "xla"),
    (1801, 70, 40, 1, "logistic", "reference", "pallas_interpret"),
    (1801, 70, 40, 2, "hinge", "permuted", "pallas_interpret"),
    (1801, 70, 40, 5, "logistic", "reference", "xla"),
    (1901, 50, 130, 1, "hinge", "permuted", "xla"),
    (1901, 50, 130, 2, "logistic", "permuted", "xla"),
    (1901, 50, 130, 5, "hinge", "reference", "pallas_interpret"),
    # B = 232: eight sub-blocks of 32 steps, the last one's tail padded
    (6007, 37, 17, 2, "hinge", "reference", "xla"),
    (6007, 37, 17, 1, "logistic", "permuted", "pallas_interpret"),
]


@pytest.mark.parametrize("n, d, t, rounds, loss, rng, chain", CASES)
def test_the_block_solve_is_t_sequential_chains(wide, n, d, t, rounds, loss,
                                                rng, chain):
    ds = standin(n, d, 2, t)
    h, (w, alpha, traj) = run_job(ds, rounds=rounds, loss=loss, rng=rng,
                                  chain=chain)
    path = traj.meta["solver_path"]
    assert (path["inner"], path["kernel"], path["class_axis"],
            path["classes"]) == ("block", "products", "lanes", t)
    assert path["chain"] == ("xla" if chain == "xla" else "pallas")
    block, blocks = path["plan"]["block"], path["plan"]["blocks"]
    # the shapes the issue asks for: several blocks a round, neither n nor H
    # whole blocks, d no whole lane tile
    assert blocks >= 2 and h % block and n % block and d % 128
    assert blocks * block >= h > (blocks - 1) * block
    # the replay in two levels: several sub-blocks a block, and B no whole
    # number of them (the last one ends in padded steps)
    sub = path["plan"]["sub"]
    assert -(-block // sub) >= (8 if n == 6007 else 3) and block % sub
    assert path["plan"]["cross"] == "highest"
    tables, (w_ref, alphas_ref) = oracle_job(ds, h, rounds, loss, rng)
    if rng == "reference":      # drawn with replacement: rows twice a block
        first = tables[0, 0, :block]
        assert len(set(first.tolist())) < len(first)
    w_got, alphas = by_class(ds, w, alpha)
    scale = max(1.0, np.abs(w_ref).max())
    assert np.abs(w_got - w_ref).max() <= TOL * scale
    for got, ref in zip(alphas, alphas_ref):
        assert np.abs(got - ref).max() <= TOL
        assert got.min() >= 0.0 and got.max() <= 1.0
    # what one bfloat16 rounding of W reads: outside the tolerance
    assert np.abs(as_bf16(w_got) - w_ref).max() > 10 * TOL * scale
    # nothing on the lanes past T
    assert not np.asarray(w).reshape(d, -1)[:, t:].any()
    assert not np.asarray(alpha).reshape(2, ds.n_shard, -1)[..., t:].any()


# --- the replay in two levels -------------------------------------------------

def a_block(k, b, d, t, loss, seed=11):
    """One block of K x B sampled rows as ``block_lanes_round`` hands it to
    the replay, from real rows (so G IS the rows' Gram matrix and a row
    drawn twice meets its own squared norm there): row 5 again at step 50
    (two sub-blocks at b = 8 and at b = 32), row 17 again at 19 and at 22
    (inside one sub-block at both), row 3 again at 30 (two at b = 8, one at
    b = 32), the last six steps padded (``code`` -1)."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import class_tile_shape

    r = np.random.default_rng(seed)
    n_rows = 400
    tile = class_tile_shape(t)
    t_pad = tile[0] * tile[1]
    x = r.normal(size=(k, n_rows, d)).astype(np.float32)
    x /= np.linalg.norm(x, axis=-1, keepdims=True)
    cls = r.integers(0, t, (k, n_rows)).astype(np.int32)
    idx = np.stack([r.permutation(n_rows)[:b] for _ in range(k)])
    for again, first in ((50, 5), (19, 17), (22, 17), (30, 3)):
        idx[:, again] = idx[:, first]
    alpha = np.zeros((k, n_rows, t_pad), np.float32)
    alpha[..., :t] = r.uniform(0, 1, (k, n_rows, t)) * (
        r.uniform(size=(k, n_rows, t)) < 0.5)
    if loss == "logistic":      # the open box
        alpha[..., :t] = np.clip(alpha[..., :t], 1e-3, 1 - 1e-3)
    vec = np.zeros((k, d, t_pad), np.float32)
    vec[..., :t] = 0.5 * r.normal(size=(k, d, t))
    sh = np.arange(k)[:, None]
    xb = x[sh, idx]
    at = np.arange(b)
    same = (idx[:, :, None] == idx[:, None, :]) & (at[None, :] < at[:, None])
    keep = at < b - 6
    return dict(
        m0=jnp.asarray(np.einsum("kbd,kdt->kbt", xb, vec)),
        gram=jnp.asarray(np.einsum("kbd,kcd->kbc", xb, xb)),
        a0=jnp.asarray(alpha[sh, idx].reshape((k, b) + tile)),
        code=jnp.asarray(np.where(keep, cls[sh, idx], -1).astype(np.int32)),
        q=jnp.asarray((xb * xb).sum(-1)),
        prev=jnp.asarray(np.where(same, at, -1).max(2).astype(np.int32)))


@pytest.mark.parametrize("sig_eff", [0.0, 2.0])
@pytest.mark.parametrize("loss", ["hinge", "logistic"])
@pytest.mark.parametrize("chain", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("sub", [8, 32, 72])
def test_the_two_level_replay_is_the_one_level_one(sub, chain, loss, sig_eff):
    """A block's B = 72 steps in sub-blocks of 8 (nine of them), of 32
    (three, the last one's 24 steps past B padded) and of B (one chain),
    through XLA's loop and the interpreted kernel, against ONE chain over
    all B steps whose every margin sums every earlier step (the replay as
    it was in one level): the same alpha' and the same c to float32
    rounding of a margin's sum, reassociated — the later draw of a row
    starts from what the earlier left whether the two share a sub-block
    (``prev`` inside the chain) or not (gathered between chains), and its
    margin carries the earlier c through the chain's sum or through the
    cross product.  With sigma' = 0 (mini-batch CD: no Gram matrix, no
    sum) the two-level replay IS one chain, and there is no cross
    product to run."""
    import functools

    import jax

    from cocoa_tpu.ops import block_lanes as bl

    k, b, t = 2, 72, 17
    blk = a_block(k, b, 24, t, loss)
    if not sig_eff:
        blk["gram"] = None
    # lambda n = 0.5: half of the hinge steps end inside the box (at the
    # jobs' lambda n = 20 every step of these unit rows ends on its edge)
    consts = dict(sig_eff=sig_eff, classes=t, lam_n=0.5, coef_div=0.5,
                  loss=loss, smoothing=1.0)
    one_a, one_c = bl._replay_xla(
        **dict(blk, m0=blk["m0"].reshape(blk["a0"].shape)), **consts)
    run = bl._replay_xla if chain == "xla" else functools.partial(
        bl._replay_pallas, interpret=True)
    two = jax.jit(functools.partial(
        bl._replay_two_level, run, sub=sub,
        cross=bl.PRECISIONS[bl.CROSS_PRECISION], **consts))
    # the cross product is in the program where there is something to owe
    assert ("kbc,kct->kbt" in two.lower(**blk).as_text(debug_info=True)) == (
        bool(sig_eff) and sub < b)
    two_a, two_c = two(**blk)
    assert two_a.shape == one_a.shape and two_c.shape == blk["m0"].shape
    one_c = np.asarray(one_c).reshape(two_c.shape)
    # the steps moved something, a row's later draw too, the padded ones
    # nothing
    assert min(np.abs(one_c[:, j]).max() for j in (5, 50, 19, 22, 30)) > 1e-3
    assert not one_c[:, b - 6:].any() and not np.asarray(two_c)[:, b - 6:].any()
    np.testing.assert_array_equal(np.asarray(two_a)[:, b - 6:],
                                  np.asarray(blk["a0"])[:, b - 6:])
    scale = np.abs(one_c).max()
    assert np.abs(np.asarray(two_c) - one_c).max() <= 2e-6 * scale
    assert np.abs(np.asarray(two_a) - np.asarray(one_a)).max() <= 2e-6
    assert not np.asarray(two_c)[..., t:].any()


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_w_is_w_of_alpha_and_the_certificate_is_the_float64_one(wide, loss):
    """W = (1 / lambda n) sum alpha y x for every class, and every class's
    recorded gap is the float64 gap of the returned pair; margins through
    one bfloat16 pass (rows and W rounded once) miss it by more than the
    tolerance a float32 certificate keeps."""
    t, n = 40, 1801
    ds = standin(n, 70, 2, t)
    _, (w, alpha, traj) = run_job(ds, rounds=5, loss=loss, rng="permuted")
    w_got, alphas = by_class(ds, w, alpha)
    xs = [np.asarray(ds.X[s, :c], np.float64) for s, c in enumerate(ds.counts)]
    cs = [np.asarray(ds.classes[s, :c]) for s, c in enumerate(ds.counts)]
    ys = [np.where(np.arange(t)[:, None] == c[None], 1.0, -1.0) for c in cs]
    w_of_alpha = sum((a * y) @ x for a, y, x in zip(alphas, ys, xs)) / (
        LAM * n)
    assert np.abs(w_got - w_of_alpha).max() <= TOL * max(
        1.0, np.abs(w_of_alpha).max())

    def gaps(w_t, rows):
        z = [y * (w_t @ x.T) for y, x in zip(ys, rows)]
        if loss == "hinge":
            primal = sum(np.maximum(0, 1 - v).sum(1) for v in z)
            dual = sum(a.sum(1) for a in alphas)
        else:
            primal = sum(np.logaddexp(0, -v).sum(1) for v in z)
            ent = [-(np.where(a > 0, a * np.log(np.maximum(a, 1e-300)), 0)
                     + np.where(a < 1, (1 - a) * np.log(np.maximum(
                         1 - a, 1e-300)), 0)) for a in
                   (np.asarray(a, np.float64) for a in alphas)]
            dual = sum(e.sum(1) for e in ent)
        reg = 0.5 * LAM * (w_got.astype(np.float64) ** 2).sum(1)
        return primal / n + reg - (dual / n - reg)

    recorded = np.asarray(traj.records[-1].class_gaps)
    exact = gaps(w_got.astype(np.float64), xs)
    assert traj.records[-1].gap == recorded.max()
    off = np.abs(recorded - exact).max()
    rounded = gaps(as_bf16(w_got).astype(np.float64),
                   [as_bf16(x).astype(np.float64) for x in xs])
    off_bf16 = np.abs(rounded - exact).max()
    print(f"gap off: float32 {off:.3e}, one bfloat16 pass {off_bf16:.3e}")
    # a mean over 1,801 rows of float32 values near 1: a few 1e-7; the
    # bfloat16 margins' 1e-3 errors mostly cancel in that mean (hinge 4e-5,
    # logistic, whose loss is smooth, 6e-6)
    assert off <= 1e-6 < 3e-6 < off_bf16


def test_the_job_stops_when_the_worst_class_certifies(wide):
    ds = standin(1101, 50, 2, 17)
    _, (_, _, traj) = run_job(ds, rounds=200, rng="permuted", target=0.02,
                              every=1, frac=0.05)
    last, before = traj.records[-1], traj.records[-2]
    assert traj.stopped == "target" and last.round > 10
    assert len(last.class_gaps) == 17 and last.gap == max(last.class_gaps)
    assert last.gap <= 0.02 < before.gap
    # the round before, some class held its certificate and the job went on
    assert last.classes_done == 17 > before.classes_done > 0
    assert min(before.class_gaps) <= 0.02


@pytest.mark.parametrize("how", ["averaging", "minibatch_cd"])
def test_every_algorithm_agrees_with_the_class_axis_on_the_sublanes(
        monkeypatch, how):
    """CoCoA's averaging (the scaling law on alpha) and mini-batch CD (no
    dW in a margin, no Gram matrix) on the lanes against the same job with
    a class a sublane (``fori``: the T = 1 step under a vmap)."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.ops import pallas_sdca
    from cocoa_tpu.solvers import run_cocoa, run_minibatch_cd

    ds = standin(1101, 50, 2, 17)
    h = 160
    params = Params(n=ds.n, num_rounds=3, local_iters=h, lam=LAM)
    kw = dict(quiet=True, math="fast", device_loop=True, rng="reference")

    def job():
        if how == "averaging":
            return run_cocoa(ds, params, DebugParams(debug_iter=3, seed=SEED),
                             plus=False, accel="off", **kw)
        return run_minibatch_cd(ds, params,
                                DebugParams(debug_iter=3, seed=SEED), **kw)

    w_sub, alpha_sub, traj = job()
    assert traj.meta["solver_path"]["class_axis"] == "sublanes"
    monkeypatch.setattr(pallas_sdca, "CLASS_VMEM_BUDGET", 0)
    w, alpha, traj = job()
    assert traj.meta["solver_path"]["class_axis"] == "lanes"
    w_got, alphas = by_class(ds, w, alpha)
    assert np.abs(w_got - np.asarray(w_sub)).max() <= TOL
    for s, (got, c) in enumerate(zip(alphas, ds.counts)):
        assert np.abs(got - np.asarray(alpha_sub)[:, s, :c]).max() <= TOL


# --- the resolver ------------------------------------------------------------

def test_a_set_the_sublane_kernel_holds_stays_on_the_sublanes():
    from cocoa_tpu.solvers.cocoa import (class_state_on_lanes,
                                         resolve_solver_path)

    ds = standin(1101, 50, 2, 10)
    assert not class_state_on_lanes(ds, math="fast")
    path = resolve_solver_path(ds, 160, math="fast")
    assert (path.inner, path.class_axis, path.plan) == ("sequential",
                                                        "sublanes", None)
    path = resolve_solver_path(ds, 160, math="fast", pallas=True)
    assert (path.kernel, path.class_axis, path.class_state) == (
        "pallas", "sublanes", "tiles")


def test_a_set_it_does_not_hold_goes_to_the_lanes_from_its_shapes(wide):
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import ShardedDataset
    from cocoa_tpu.ops.block_lanes import BlockLanesPlan, block_lanes_plan
    from cocoa_tpu.solvers.cocoa import (class_state_on_lanes,
                                         resolve_solver_path)

    # shapes alone: nothing behind them
    k, n_shard, d, t = 8, 4096, 4096, 1000
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    rows = sds((k, n_shard))
    ds = ShardedDataset(
        layout="dense", n=k * n_shard, num_features=d,
        counts=np.full(k, n_shard, np.int64), labels=rows, mask=rows,
        sq_norms=rows, X=sds((k, n_shard, d)),
        classes=sds((k, n_shard), jnp.int32), num_classes=t)
    assert class_state_on_lanes(ds, math="fast")
    path = resolve_solver_path(ds, 4003, math="fast")
    assert (path.inner, path.kernel, path.chain, path.class_axis,
            path.class_tiles, path.step_solve) == (
        "block", "products", "xla", "lanes", 1, "lanes")
    assert path.lane_fill == 1000 / 1024
    # B from the fit: G twice in SMEM holds 256 rows; 16 even blocks of H
    assert path.plan == BlockLanesPlan(block=256, blocks=16, sub=32) == \
        block_lanes_plan(4003, 1024)
    assert (path.plan.update, path.plan.cross) == ("highest", "highest")
    said = path.describe()
    assert "block of 256 rows a step (16 a round) in sub-blocks of 32" in said
    assert "what a sub-block is owed (highest)" in said
    # what keeps a dense multi-class set off the lanes: exact math, a
    # kernel the caller forced
    assert not class_state_on_lanes(ds, math="exact")
    assert not class_state_on_lanes(ds, math="fast", pallas=True)
    assert resolve_solver_path(ds, 4003, math="exact").class_axis == \
        "sublanes"
    with pytest.raises(ValueError, match="block_size=0 with a multi-class"):
        resolve_solver_path(ds, 4003, math="fast", block_size=128)


@pytest.mark.parametrize("h, t_pad, block, blocks, sub", [
    (4003, 1024, 256, 16, 32), (12656, 1024, 256, 50, 32),
    (300, 1024, 152, 2, 32), (7, 1024, 8, 1, 8), (4003, 8192, 192, 21, 32),
    (40, 1024, 40, 1, 24), (30, 1024, 32, 1, 32), (100, 1024, 104, 1, 32)])
def test_the_block_comes_from_the_fit(h, t_pad, block, blocks, sub):
    from cocoa_tpu.ops.block_lanes import (SUB_STEPS, block_fits,
                                           block_lanes_plan)

    plan = block_lanes_plan(h, t_pad)
    assert (plan.block, plan.blocks, plan.sub) == (block, blocks, sub)
    assert block_fits(plan.block, t_pad, 4) and plan.block % 8 == 0
    assert plan.blocks * plan.block >= h > (plan.blocks - 1) * plan.block
    # b from B alone: whole sublane groups, the fewest sub-blocks of at most
    # SUB_STEPS steps, fewer than b padded steps behind the last
    subs = -(-block // sub)
    assert sub % 8 == 0 and sub <= SUB_STEPS
    assert subs == -(-block // SUB_STEPS) and 0 <= subs * sub - block < sub


# --- the certificate in row blocks ------------------------------------------

@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_the_blocked_certificates_are_the_one_einsum_ones(monkeypatch, loss):
    """At mnist8m's small test shape (tests/test_ovr.py: T = 10, d = 784):
    the T certificates from row blocks on the lanes form against the ones
    of one ``einsum`` over all of X . W^T on the sublanes form, same W and
    alpha.  They differ in the order of the sums alone (a row's margin is
    one product either way; the loss values are added a block at a time
    and not in one reduce), so they agree to a few float32 ulps of a sum
    of n terms near 1, not to the bit; with one block (the temporaries
    under ``DENSE_CLASS_BLOCK_BYTES``) as with several."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import class_tile_shape
    from cocoa_tpu.evals import objectives
    from cocoa_tpu.ops import rows

    t, d, k = 10, 784, 2
    ds = standin(1500, d, k, t, seed=5)
    r = np.random.default_rng(1)
    w = (r.normal(size=(t, d)) * 0.05).astype(np.float32)
    alpha = r.uniform(0, 1, (t, k, ds.n_shard)).astype(np.float32) \
        * np.asarray(ds.mask)
    arrays = ds.shard_arrays()
    one = np.asarray(objectives.eval_metrics(
        jnp.asarray(w), jnp.asarray(alpha), arrays, LAM, ds.n,
        test_shard_arrays=arrays, test_n=ds.n, loss=loss))
    tile = class_tile_shape(t)
    w3 = np.zeros((d, tile[0] * tile[1]), np.float32)
    w3[:, :t] = w.T
    a4 = np.zeros((k, ds.n_shard, tile[0] * tile[1]), np.float32)
    a4[..., :t] = np.moveaxis(alpha, 0, -1)
    lanes = (jnp.asarray(w3).reshape((d,) + tile),
             jnp.asarray(a4).reshape((k, ds.n_shard) + tile))

    def blocked():
        return np.asarray(objectives.eval_metrics(
            *lanes, arrays, LAM, ds.n, test_shard_arrays=arrays,
            test_n=ds.n, loss=loss, classes=t))

    assert rows.dense_class_row_block(ds.n_shard, 1024) == ds.n_shard
    whole = blocked()
    monkeypatch.setattr(rows, "DENSE_CLASS_BLOCK_BYTES", 1024 * 4 * 200)
    assert rows.dense_class_row_block(ds.n_shard, 1024) == 200
    assert ds.n_shard % 200         # the last block shares rows
    parts = blocked()
    assert one.shape == whole.shape == parts.shape == (3 + t,)
    for got in (whole, parts):
        np.testing.assert_allclose(got, one, rtol=2e-6, atol=2e-6)
        assert got[2] == one[2]     # the multi-class error: a count


def test_the_cell_block_holds_its_temporaries_under_the_stated_size():
    from cocoa_tpu.ops import rows

    # the cell: 40,048 rows a shard, 1,024 lanes: 16,384 rows a block, 64 MB
    assert rows.dense_class_row_block(40048, 1024) == 16384
    assert 16384 * 1024 * 4 == rows.DENSE_CLASS_BLOCK_BYTES


# --- through the CLI ---------------------------------------------------------

def test_the_same_job_through_the_cli(wide, tmp_path, capsys):
    import json

    from cocoa_tpu import cli
    from cocoa_tpu.data import load_libsvm, shard_dataset
    from cocoa_tpu.telemetry import events as tele

    r = np.random.default_rng(7)
    n, d, t = 700, 12, 17
    x = r.normal(size=(n, d))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    labels = r.integers(1, t + 1, n)
    path = str(tmp_path / "multi.dat")
    with open(path, "w") as f:
        for i in range(n):
            f.write(f"{labels[i]} " + " ".join(
                f"{j + 1}:{x[i, j]:.6f}" for j in range(d)) + "\n")
    out, events = str(tmp_path / "traj"), str(tmp_path / "ev.jsonl")
    try:
        assert cli.main([
            f"--trainFile={path}", f"--numFeatures={d}", "--numSplits=2",
            f"--lambda={LAM}", "--localIterFrac=0.3", "--mesh=1",
            "--justCoCoA=true", "--math=fast", "--deviceLoop",
            "--rng=permuted", "--accel=off", "--numRounds=5", "--debugIter=5",
            f"--seed={SEED}", "--classes=auto", "--layout=dense",
            f"--trajOut={out}", f"--events={events}"]) == 0
    finally:
        tele.get_bus().reset()
    said = capsys.readouterr().out
    assert "local solver: block of" in said
    assert "the class axis on the lanes" in said
    # H = 105 steps a shard: one block of 112 rows in four sub-blocks of 32
    # (16 padded steps), on the console's line, in ``run_start`` and on the
    # trajectory
    assert ("block of 112 rows a step (1 a round) in sub-blocks of 32, "
            "margins (high), Gram (highest), what a sub-block is owed "
            "(highest) and update (highest) as matrix products") in said
    with open(events) as f:
        (start,) = [e for e in map(json.loads, f)
                    if e["event"] == "run_start"]
    plan = dict(block=112, blocks=1, sub=32, margins="high", gram="highest",
                cross="highest", update="highest")
    assert start["manifest"]["solver_path"]["plan"] == plan
    names = sorted(f for f in os.listdir(tmp_path) if f.startswith("traj."))
    assert len(names) == 2 and "+" in names[0], names    # CoCoA+, CoCoA
    with open(tmp_path / names[0]) as f:
        records = [json.loads(line) for line in f if line.strip()]
    cli_gaps = [r for r in records if r.get("class_gaps")][-1]["class_gaps"]
    ds = shard_dataset(load_libsvm(path, d, classes="auto"), k=2,
                       layout="dense")
    _, (_, _, traj) = run_job(ds, rounds=5, rng="permuted")
    assert cli_gaps == traj.records[-1].class_gaps
    assert traj.meta["solver_path"]["plan"] == plan


# --- the cold account of a class job that resolves first --------------------

def test_a_first_class_job_accounts_its_resolve():
    """A dense multi-class job resolves its path BEFORE its start program
    goes out (the leaves take their shape from it), so no ``first_job`` is
    open yet when the kernels' modules are first imported: the job opens
    the ``resolve_path`` span itself, and a process's first ten-class job
    reads it inside ``first_job`` as it did (mnist8m's ``cold_job_s``)."""
    import subprocess

    code = """
import sys
sys.path.insert(0, %r); sys.path.insert(0, %r)
import test_wide_classes as t
from cocoa_tpu.telemetry import tracing
assert "cocoa_tpu.ops.pallas_sdca" not in sys.modules
ds = t.standin(400, 24, 2, 10)
_, (_, _, traj) = t.run_job(ds, rounds=2, rng="permuted")
assert traj.meta["solver_path"]["class_axis"] == "sublanes"
cold = tracing.get_tracer().cold
print("PHASES", [r["phase"] for r in cold])
first = [r for r in cold if r["phase"] == "first_job"][0]
res = [r for r in cold if r["phase"] == "resolve_path"][0]
assert res["job"] == first["job"] and res["dur_s"] <= first["dur_s"]
_, (_, _, traj) = t.run_job(ds, rounds=2, rng="permuted")
assert traj.meta["cold"] == [], traj.meta["cold"]
print("OK")
""" % (ROOT, os.path.join(ROOT, "tests"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
    phases = out.stdout.split("PHASES ")[1].splitlines()[0]
    assert "resolve_path" in phases and "build_start" in phases
