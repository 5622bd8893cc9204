"""T one-vs-rest models over label SETS on rows kept as a STREAM, the class
axis on the lanes (ops/pallas_longrows_lanes.py), on the CPU.

- the system (the Pallas chain, interpreted) against ``tests/oracle.py``'s
  float64 one-vs-rest CoCoA+ over CSR rows on seeded random data: rows of 1
  to a few hundred nonzeros with a tail, a row longer than the ring and
  one longer than a chunk, T = 24 and T = 130 (no multiple of 128), a
  label no row carries, K = 2 and 3 — alpha, W and every class's gap;
- a round handed a table that draws a row twice, against the same round
  in plain XLA and the oracle;
- the stream and the rectangle (``amazoncat13k``'s kernel on the same
  rows padded) give the same bits: one mathematics in two storages;
- hinge compared, logistic traced and run interpreted;
- no float of a step is 0-d (``tests/test_losses.py``'s rule on this
  kernel's body);
- the budget gate and its estimate agree;
- the resolver picks the path from the dataset alone, and still refuses
  the hybrid layout, a mesh and ``--accel`` at T > 1;
- ``import cocoa_tpu``, and a resolve of every other kind of dataset,
  imports no module this path brought;
- the CLI reaches the path from a multi-label LIBSVM file."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import oracle  # noqa: E402

LAM, SEED = 1e-2, 3
NEW = "cocoa_tpu.ops.pallas_longrows_lanes"


def standin(n=96, d=1500, t=24, slots=3, mean=110, longest=1300, seed=0):
    """Seeded stand-in rows as ``LibsvmData``: log-normal lengths (sigma 1)
    clipped to [1, longest], row 3 the longest (longer than a chunk's 1,024
    slots), row 5 of one nonzero, a bias column in every row, label sets of
    0 .. ``slots`` ids over the first t - 1 classes: class t - 1 is EMPTY,
    some row is in no label's set and some in several."""
    from cocoa_tpu.data.libsvm import LibsvmData

    r = np.random.default_rng(seed)
    lens = np.clip(np.round(np.exp(r.normal(np.log(mean) - 0.5, 1.0, n))),
                   1, longest).astype(int)
    lens[3], lens[5] = longest, 1
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices, values = [], []
    for length in lens:
        cols = np.sort(r.choice(d - 1, length - 1, replace=False))
        indices.append(np.append(cols, d - 1))
        values.append(np.full(length, 1.0 / np.sqrt(length)))
    ids = np.full((n, slots), -1, np.int32)
    for i in range(n):
        m = r.integers(0, slots + 1)
        ids[i, :m] = np.sort(r.choice(t - 1, m, replace=False))
    assert (ids[:, 0] < 0).any() and (ids[:, 1] >= 0).any()
    return LibsvmData(
        labels=np.where((ids == 0).any(1), 1.0, -1.0), indptr=indptr,
        indices=np.concatenate(indices).astype(np.int32),
        values=np.concatenate(values), num_features=d, classes=ids,
        num_classes=t)


@pytest.fixture
def small_ring(monkeypatch):
    """The kernel's ring at 64 slots: rows past 64 nonzeros read their dW
    rows a second time, as rows past 512 do at the real size."""
    from cocoa_tpu.ops import pallas_longrows_lanes as pll

    monkeypatch.setattr(pll, "RING_SLOTS", 64)
    return pll


def shard(data, k, **kw):
    from cocoa_tpu.data import shard_dataset

    return shard_dataset(data, k=k, layout="sparse", **kw)


def run_job(ds, *, loss="hinge", pallas=True, rounds=10, every=5,
            target=1e-9, frac=0.25, rng="permuted"):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.solvers import run_cocoa

    h = max(1, int(frac * ds.n / ds.k))
    w, alpha, traj = run_cocoa(
        ds, Params(n=ds.n, num_rounds=rounds, local_iters=h, lam=LAM,
                   loss=loss),
        DebugParams(debug_iter=every, seed=SEED), plus=True, quiet=True,
        math="fast", device_loop=True, rng=rng, gap_target=target,
        accel="off", pallas=pallas)
    return np.asarray(w), np.asarray(alpha), traj, h


def oracle_job(data, ds, h, rounds, loss="hinge", rng="permuted"):
    """The float64 oracle over the job's own index stream: ``(W (d, T),
    alpha (n, T), every class's gap)``."""
    from cocoa_tpu.solvers import base

    sampler = base.IndexSampler(rng, SEED, h, ds.counts)
    tables = np.stack([np.asarray(sampler.round_indices(r))
                       for r in range(1, rounds + 1)])
    bounds = np.concatenate([[0], np.cumsum(ds.counts)])
    w, alphas = oracle.labelset_cocoa_plus(
        data.indptr, data.indices, data.values, data.classes, bounds,
        tables, LAM, data.n, data.num_classes, data.num_features, loss)
    alpha = np.concatenate(alphas)
    return w, alpha, oracle.labelset_gaps(
        data.indptr, data.indices, data.values, data.classes, w, alpha, LAM,
        loss)[2]


def by_class(ds, w, alpha):
    """The program's tiles as the oracle holds them: W (d, T), alpha (n,
    T)."""
    from cocoa_tpu.data.sharding import class_vector

    t = ds.num_classes
    return (np.asarray(class_vector(w, t)),
            np.concatenate([np.asarray(class_vector(alpha[s, :c], t))
                            for s, c in enumerate(ds.counts)]))


# --- against the float64 reference -------------------------------------------

@pytest.mark.parametrize("k,t", [(2, 24), (3, 24), (2, 130)])
def test_the_interpreted_chain_meets_the_float64_reference(small_ring, k, t):
    data = standin(t=t)
    ds = shard(data, k)
    assert ds.sp_row_ptr is not None            # the loader's own rule
    rounds = 10
    w, alpha, traj, h = run_job(ds, rounds=rounds)
    path = traj.meta["solver_path"]
    assert (path["kernel"], path["storage"], path["class_axis"],
            path["state"], path["margin"], path["step_solve"],
            path["plan"]["ring"]) == ("pallas", "stream", "lanes", "hbm",
                                      "split", "lanes", 64)
    assert path["longest_row"] == 1300 and path["interpret"]
    w_ref, a_ref, gaps_ref = oracle_job(data, ds, h, rounds)
    w_t, a_t = by_class(ds, w, alpha)
    assert np.abs(w_t - w_ref).max() < 2e-6 * max(1, np.abs(w_ref).max())
    assert np.abs(a_t - a_ref).max() < 2e-5
    last = traj.records[-1]
    assert last.round == rounds and len(last.class_gaps) == t
    np.testing.assert_allclose(last.class_gaps, gaps_ref, atol=2e-6)
    # the empty label: every y is -1, its model is the zero model's
    assert not (data.classes == t - 1).any()
    assert a_t[:, t - 1].max() <= 1 and last.class_gaps[t - 1] >= -1e-6
    # the lanes past T hold nothing
    flat_w = w.reshape(w.shape[0], -1)
    assert not flat_w[:, t:].any()
    assert not alpha.reshape(alpha.shape[:2] + (-1,))[..., t:].any()
    # tight enough that a W rounded once to bfloat16 is far outside it
    import jax.numpy as jnp

    w16 = np.asarray(jnp.asarray(w_t).astype(jnp.bfloat16).astype(
        jnp.float32))
    assert np.abs(w16 - w_ref).max() > 1e-4


def test_a_row_drawn_twice_in_a_round_is_exact(small_ring):
    """One round handed its table: the longest row twice in a row, the row
    of one nonzero twice with a step between, against the round in plain
    XLA and one round of the oracle."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import class_pad, class_tile_shape

    data = standin()
    ds = shard(data, 2)
    arrays = ds.shard_arrays()
    t, d = data.num_classes, data.num_features
    table = np.array([[3, 3, 5, 0, 5, 7, 9, 3],
                      [1, 2, 2, 4, 6, 8, 1, 10]], np.int32)
    tile = class_tile_shape(t)
    w0 = jnp.zeros((d,) + tile, jnp.float32)
    a0 = jnp.zeros((2, ds.n_shard) + tile, jnp.float32)
    plan = small_ring.stream_lanes_plan(table.shape[1], 4, class_pad(t), 3)
    kw = dict(lam=LAM, n=data.n, classes=t, mode="plus", sigma=2.0)
    dw, alpha = small_ring.pallas_stream_lanes_round(
        w0, a0, arrays, jnp.asarray(table), plan=plan, interpret=True, **kw)
    from cocoa_tpu.ops.pallas_sparse_lanes import sparse_lanes_round_fori

    dw_x, alpha_x = sparse_lanes_round_fori(
        w0, a0, arrays, jnp.asarray(table), **kw)
    # (float32 in two orders: the chain adds a row's products up slot by
    # slot, the plain round as one reduce; lambda n is 0.96 here, so a
    # step moves alpha by what moves its margin)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_x), atol=2e-5)
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(alpha_x),
                               atol=2e-5)
    bounds = np.concatenate([[0], np.cumsum(ds.counts)])
    w_ref, alphas = oracle.labelset_cocoa_plus(
        data.indptr, data.indices, data.values, data.classes, bounds,
        table[None], LAM, data.n, t, d)
    w_t, a_t = by_class(ds, np.asarray(dw), np.asarray(alpha))
    assert np.abs(w_t - w_ref).max() < 2e-5 * max(1, np.abs(w_ref).max())
    assert np.abs(a_t - np.concatenate(alphas)).max() < 2e-5
    assert a_t[3].max() > 0 and a_t[bounds[1] + 2].max() > 0


def test_the_stream_and_the_rectangle_agree(small_ring):
    """The same rows padded to a rectangle run amazoncat13k's chain; kept
    as a stream, this one: the same steps in the same order on the same
    float32 values."""
    data = standin()
    got = {}
    for storage, kw in (("stream", {}), ("rectangle", {"rectangle": True})):
        ds = shard(data, 2, **kw)
        w, alpha, traj, _ = run_job(ds, rounds=5)
        assert traj.meta["solver_path"]["storage"] == storage
        assert traj.meta["solver_path"]["kernel"] == "pallas"
        got[storage] = (w, alpha, traj.records[-1].class_gaps)
    for a, b in zip(got["stream"][:2], got["rectangle"][:2]):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-7)
    np.testing.assert_allclose(got["stream"][2], got["rectangle"][2],
                               atol=2e-6)


def test_the_plain_round_is_the_chain(small_ring):
    """The path off the TPU (``kernel: fori``) and the interpreted chain."""
    data = standin()
    ds = shard(data, 2)
    w, alpha, traj, _ = run_job(ds, rounds=5)
    w_x, alpha_x, traj_x, _ = run_job(ds, rounds=5, pallas=False)
    assert traj_x.meta["solver_path"]["kernel"] == "fori"
    assert traj_x.meta["solver_path"]["storage"] == "stream"
    np.testing.assert_allclose(w, w_x, atol=2e-6)
    np.testing.assert_allclose(alpha, alpha_x, atol=2e-6)
    np.testing.assert_allclose(traj.records[-1].class_gaps,
                               traj_x.records[-1].class_gaps, atol=2e-6)


def test_logistic_traces_and_runs_interpreted(small_ring):
    data = standin(n=64, d=700, longest=500, mean=60)
    ds = shard(data, 2)
    rounds = 5
    w, alpha, traj, h = run_job(ds, loss="logistic", rounds=rounds)
    assert traj.meta["solver_path"]["kernel"] == "pallas"
    w_ref, a_ref, gaps_ref = oracle_job(data, ds, h, rounds, "logistic")
    w_t, a_t = by_class(ds, w, alpha)
    # (the program's Newton solve stops at ten steps, the oracle's at sixty)
    assert np.abs(w_t - w_ref).max() < 1e-4 * max(1, np.abs(w_ref).max())
    assert np.abs(a_t - a_ref).max() < 1e-4
    np.testing.assert_allclose(traj.records[-1].class_gaps, gaps_ref,
                               atol=1e-4)
    assert (a_t > 0).all() and (a_t < 1).all()


def test_the_certificate_reads_the_stream_in_blocks(monkeypatch):
    """``stream_class_loss_sums`` with blocks of 16 rows and reads of 16
    groups (one piece) — several blocks a shard, several reads a block, a
    read clamped at the stream's end — against the rectangle's pass on the
    same rows."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import class_tile_shape
    from cocoa_tpu.ops import pallas_longrows_lanes as pll
    from cocoa_tpu.ops.rows import class_loss_sums

    data = standin()
    stream, rect = shard(data, 3), shard(data, 3, rectangle=True)
    r = np.random.default_rng(1)
    t, tile = data.num_classes, class_tile_shape(data.num_classes)
    w = jnp.asarray(r.normal(size=(data.num_features,) + tile), jnp.float32)
    alpha = jnp.asarray(r.uniform(size=(3, stream.n_shard) + tile),
                        jnp.float32)
    want = np.asarray(class_loss_sums(w, alpha, rect.shard_arrays(), t,
                                      "hinge", 1.0))
    for rows, groups in ((256, 256), (16, 16)):
        monkeypatch.setattr(pll, "EVAL_ROWS", rows)
        monkeypatch.setattr(pll, "EVAL_GROUPS", groups)
        got = np.asarray(pll.stream_class_loss_sums(
            w, alpha, stream.shard_arrays(), t, "hinge", 1.0))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-3)
        share = pll.pass_slot_share(stream.sp_row_ptr, stream.sp_row_len,
                                    int(stream.sp_indices.shape[1]))
        assert 0 < share <= 1.0


# --- the step's values, the budgets ------------------------------------------

@pytest.mark.parametrize("mode", ["plus", "frozen"])
@pytest.mark.parametrize("loss", ["hinge", "smooth_hinge", "logistic"])
def test_no_value_of_a_label_stream_step_is_a_scalar(loss, mode):
    """``tests/test_losses.py``'s rule on this chain's body: the only 0-d
    floats computed from its data are loads of SMEM — a nonzero's value, 8
    slots written out in the margin's group and 8 in each of the update's
    two, and the step's sigma' |x|^2 — each splatted into a vector operation at once;
    ``alpha_step`` runs on (R, 128) tiles, T labels side by side."""
    import jax
    import jax.numpy as jnp
    import test_losses as tl

    from cocoa_tpu.ops import pallas_longrows_lanes as pll

    k, n_shard, d, h, pieces, slots = 2, 16, 64, 8, 16, 3
    f32 = jnp.float32
    rows, irows = jnp.ones((k, n_shard), f32), jnp.zeros((k, n_shard),
                                                         jnp.int32)
    shards = dict(sp_indices=jnp.zeros((k, pieces, 128), jnp.int32),
                  sp_values=jnp.ones((k, pieces, 128), f32),
                  sp_row_ptr=irows, sp_row_len=irows, sq_norms=rows,
                  classes=jnp.zeros((k, n_shard, slots), jnp.int32))
    plan = pll.stream_lanes_plan(h, 4, 1024, slots)
    traced = jax.make_jaxpr(lambda w, a, sh, i: pll.pallas_stream_lanes_round(
        w, a, sh, i, 0.01, 1000, 24, plan, mode=mode, sigma=3.0, loss=loss,
        smoothing=0.5, interpret=True))(
            jnp.zeros((d, 8, 128), f32), jnp.zeros((k, n_shard, 8, 128), f32),
            shards, jnp.zeros((k, h), jnp.int32))
    (body,) = [e.params["jaxpr"] for e in tl._walk(traced.jaxpr)
               if e.primitive.name == "pallas_call"]
    scalars = tl._scalar_float_eqns(body)
    assert {e.primitive.name for e in scalars} <= {"get"}, [
        str(e)[:120] for e in scalars if e.primitive.name != "get"]
    # (the update's group is written out twice: whole groups in the loop,
    # the row's last, partial one slot by slot behind it)
    assert len(scalars) == 1 + 3 * pll.GROUP
    exps = [e.outvars[0].aval.shape for e in tl._walk(body)
            if e.primitive.name == "exp"]
    assert exps == ([(8, 128)] * (tl.losses._NEWTON_ITERS + 1)
                    if loss == "logistic" else [])


def test_the_budget_gate_and_its_estimate_agree():
    from cocoa_tpu.ops import pallas_longrows_lanes as pll

    plan = pll.stream_lanes_plan(2457, 4, 1024, 8)
    assert (plan.ring, plan.row_block, plan.steps) == (512, 256, 2560)
    assert pll.vmem_estimate(plan.ring, 1024, 4) == (2 * 512 + 2) * 4096
    assert pll.vmem_estimate(plan.ring, 1024, 4) \
        <= pll.LABELSTREAM_VMEM_BUDGET
    assert pll.smem_estimate(plan.row_block, 8) <= pll.LABELSTREAM_SMEM_BUDGET
    # a wider class axis shrinks the ring, whole groups at a time
    for t_pad in (8192, 65536, 262144):
        p = pll.stream_lanes_plan(100, 4, t_pad, 8)
        assert p.ring % pll.GROUP == 0 and pll.RING_MIN <= p.ring <= 512
        assert pll.vmem_estimate(p.ring, t_pad, 4) \
            <= pll.LABELSTREAM_VMEM_BUDGET
        assert pll.vmem_estimate(p.ring + pll.GROUP, t_pad, 4) \
            > pll.LABELSTREAM_VMEM_BUDGET or p.ring == 512
    # and past the smallest ring nothing fits: the gate says no
    assert not pll.stream_lanes_fits(100, 4, 1 << 20, 8)
    assert pll.stream_lanes_plan(100, 4, 1 << 20, 8) is None
    assert not pll.stream_lanes_fits(100, 2, 1024, 8)      # float32 only
    assert pll.stream_lanes_fits(100, 4, 1024, 1)


# --- the path ------------------------------------------------------------------

def test_the_resolver_picks_the_path_from_the_dataset_alone():
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    ds = shard(standin(), 2)
    off = resolve_solver_path(ds, 12, None, math="fast")       # a cpu: fori
    assert (off.inner, off.kernel, off.storage, off.class_axis, off.state,
            off.plan) == ("sequential", "fori", "stream", "lanes", "hbm",
                          None)
    on = resolve_solver_path(ds, 12, None, math="fast", pallas=True)
    assert (on.inner, on.kernel, on.storage, on.class_axis, on.state,
            on.class_tiles, on.label_slots, on.classes, on.chunk_pieces,
            on.step_solve) == ("sequential", "pallas", "stream", "lanes",
                               "hbm", 1, 3, 24, 8, "lanes")
    assert on.for_mode("plus").margin == "split"
    assert on.for_mode("frozen").margin == "split"
    assert on.plan.ring == 512 and on.local_ids is None
    assert 0 < on.slot_fill < 1 and 0 < on.chunk_fill < 1
    assert on.longest_row == 1300 and 0 < on.pass_slot_share <= 1
    said = on.for_mode("plus").describe()
    for words in ("state in HBM", "24 class models", "rows kept as a stream",
                  "the class axis on the lanes", "3 label id(s) a row",
                  "margin split", "a ring of 512"):
        assert words in said, (words, said)
    plan = on.as_dict()["plan"]
    assert plan == {"t_pad": 1024, "ring": 512, "row_block": 256,
                    "steps": 256, "label_slots": 3}


@pytest.mark.parametrize("what", ["hybrid", "mesh", "accel", "block"])
def test_what_the_stream_does_not_carry_is_refused_by_name(what):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.parallel import make_mesh
    from cocoa_tpu.solvers import run_cocoa
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    data = standin()
    ds = shard(data, 2)
    if what == "hybrid":
        hot = dataclasses.replace(
            shard(data, 2, rectangle=True),
            X_hot=jnp.zeros((2, ds.n_shard, 8)),
            hot_cols=jnp.zeros((2, 8), jnp.int32))
        with pytest.raises(ValueError, match="hybrid layout"):
            resolve_solver_path(hot, 12, None, math="fast")
    elif what == "mesh":
        with pytest.raises(ValueError, match="not carried across a mesh"):
            resolve_solver_path(ds, 12, make_mesh(min(2, len(jax.devices()))),
                                math="fast")
    elif what == "block":
        with pytest.raises(ValueError, match="no class axis"):
            resolve_solver_path(ds, 12, None, math="fast", block_size=8)
    else:
        with pytest.raises(ValueError, match="--accel"):
            run_cocoa(ds, Params(n=data.n, num_rounds=5, local_iters=12,
                                 lam=LAM),
                      DebugParams(debug_iter=5), plus=True, quiet=True,
                      math="fast", accel="auto", gap_target=1e-2)


def test_no_other_path_imports_what_this_one_brought():
    """``import cocoa_tpu`` and ``chipbench.run`` list no module of this
    path, and neither does a resolve (nor a job) of a dataset that is not
    a stream with classes: a T = 1 stream, a rectangle with label sets, a
    dense multi-class set.  A stream with classes imports it at its
    resolve.  (One Pallas import on the chip's host read 1.2 to 1.3 s.)"""
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
import cocoa_tpu, cocoa_tpu.solvers, cocoa_tpu.evals.objectives
import chipbench.run
new = [m for m in sys.modules if "longrows_lanes" in m or "labelstream" in m
       or "longrows_multilabel" in m]
assert not new, new
assert not any(m.startswith("jax.experimental.pallas") for m in sys.modules)
import numpy as np
import test_labelstream as t
from cocoa_tpu.data import shard_dataset
from cocoa_tpu.data.synth import synth_dense
from cocoa_tpu.solvers.cocoa import resolve_solver_path
data = t.standin()
resolve_solver_path(t.shard(data, 2, rectangle=True), 12, None, math="fast",
                    pallas=True)
one = t.standin()
one.classes, one.num_classes = None, 1
ds = t.shard(one, 2)
assert ds.sp_row_ptr is not None
resolve_solver_path(ds, 12, None, math="fast", pallas=True)
dense = synth_dense(64, 8, seed=0)
dense.classes = np.arange(64, dtype=np.int32) % 3
dense.num_classes = 3
resolve_solver_path(shard_dataset(dense, k=2, layout="dense"), 3, None,
                    math="fast")
assert {NEW!r} not in sys.modules
resolve_solver_path(t.shard(data, 2), 12, None, math="fast")
assert {NEW!r} in sys.modules
print("ok")
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), \
        out.stderr[-2000:]


def test_the_cli_reaches_the_stream_from_a_multi_label_file(tmp_path,
                                                            capsys):
    """--classes=auto --layout=sparse on a multi-label file whose rows are
    long and uneven: the loader keeps them as a stream with their label
    sets, the path says so, every class is reported."""
    from cocoa_tpu import cli

    data = standin(n=64, d=700, t=6, longest=500, mean=110)
    lines = []
    for i in range(data.n):
        idx, val = data.row(i)
        label = ",".join(str(10 + t) for t in data.classes[i] if t >= 0)
        lines.append((label + " " if label else "") + " ".join(
            f"{c + 1}:{v:.6f}" for c, v in zip(idx, val)))
    path = str(tmp_path / "long.dat")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    argv = [f"--trainFile={path}", "--numFeatures=700", "--numSplits=2",
            "--lambda=0.01", "--localIterFrac=0.2", "--numRounds=10",
            "--debugIter=5", "--justCoCoA=true", "--classes=auto",
            "--accel=off", "--mesh=1", "--math=fast", "--layout=sparse"]
    assert cli.main(argv) == 0
    out = capsys.readouterr().out
    assert "label sets, up to 3 a row" in out
    assert "rows kept as a stream" in out
    assert "the class axis on the lanes" in out and "per-class gaps" in out
    assert cli.main(argv + ["--hotCols=8"]) == 2
    assert "carry no class axis" in capsys.readouterr().err
