"""Label SETS in the data model and T one-vs-rest models over sparse rows,
the class axis on the lanes (ops/pallas_sparse_lanes.py), on the CPU.

- a multi-label LIBSVM file (``3,17 1:0.5 ...``, a row in no label's set)
  through ``load_libsvm(classes=)``, ``shard_dataset``, ``class_labels`` /
  ``class_signs`` and the ordering by length; one class id a row is the set
  of size one, and gives the bits it gave;
- the system against ``chipbench/reference_labels.py`` at T = 24 and T =
  130 on ragged rows (a row in no set, a row in several), the interpreted
  Pallas chain, hinge and logistic — tight enough that a W rounded once to
  bfloat16 fails;
- the share adds up: three jobs over label batches of 8 give, model for
  model, what one job over all 24 gives (one-vs-rest models are
  independent: the chip's batch is a share, not an approximation);
- the plan with a class axis (``lanes_plan``), and ``hbm_plan``'s T = 1
  plans as they were;
- what the path says and what it refuses."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MULTI = """3,17 1:0.5 4:1
2:1.0 3:2
17 1:1
5,3,17 2:0.25

3 5:1
"""


def write(tmp_path, text, name="labels.dat"):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        f.write(text)
    return path


def standin(n=256, d=48, t=24, slots=3, width=20, seed=0):
    """Seeded stand-in rows as ``LibsvmData``: ragged lengths in [1,
    width], a bias column in every row, label sets of 0 .. ``slots`` ids
    (so some row is in no label's set and some in several)."""
    from cocoa_tpu.data.libsvm import LibsvmData

    r = np.random.default_rng(seed)
    lens = r.integers(1, width + 1, n)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices, values = [], []
    for length in lens:
        cols = np.sort(r.choice(d - 1, length - 1, replace=False))
        indices.append(np.append(cols, d - 1))
        values.append(np.full(length, 1.0 / np.sqrt(length)))
    ids = np.full((n, slots), -1, np.int32)
    for i in range(n):
        m = r.integers(0, slots + 1)
        ids[i, :m] = np.sort(r.choice(t, m, replace=False))
    assert (ids[:, 0] < 0).any() and (ids[:, 1] >= 0).any()
    return LibsvmData(
        labels=np.where((ids == 0).any(1), 1.0, -1.0), indptr=indptr,
        indices=np.concatenate(indices).astype(np.int32),
        values=np.concatenate(values), num_features=d, classes=ids,
        num_classes=t)


def run_job(data, *, k=4, loss="hinge", pallas=True, target=5e-3, lam=1e-2,
            rounds=60, seed=3):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import run_cocoa

    ds = shard_dataset(data, k=k, layout="sparse")
    h = max(1, int(0.1 * data.n / k))
    w, alpha, traj = run_cocoa(
        ds, Params(n=data.n, num_rounds=rounds, local_iters=h, lam=lam,
                   loss=loss),
        DebugParams(debug_iter=5, seed=seed), plus=True, quiet=True,
        math="fast", device_loop=True, rng="permuted", gap_target=target,
        accel="off", pallas=pallas)
    return ds, w, alpha, traj


# --- the data model ----------------------------------------------------------

def test_multi_label_lines_load_as_label_sets(tmp_path):
    from cocoa_tpu.data import load_libsvm

    data = load_libsvm(write(tmp_path, MULTI), 5, classes="auto")
    assert (data.num_classes, data.class_values) == (3, (3, 5, 17))
    np.testing.assert_array_equal(
        data.classes, [[0, 2, -1], [-1, -1, -1], [2, -1, -1], [1, 0, 2],
                       [0, -1, -1]])
    assert data.classes.dtype == np.int32
    # the row in no label's set keeps its first feature
    np.testing.assert_array_equal(data.indptr, [0, 2, 4, 5, 6, 7])
    np.testing.assert_array_equal(data.indices, [0, 3, 1, 2, 0, 1, 4])
    np.testing.assert_array_equal(data.values, [.5, 1, 1, 2, 1, .25, 1])
    # the count a caller states is held against the file
    assert load_libsvm(write(tmp_path, MULTI), 5, classes=3).num_classes == 3
    with pytest.raises(ValueError, match="4 classes were stated"):
        load_libsvm(write(tmp_path, MULTI), 5, classes=4)
    with pytest.raises(ValueError, match="labels are numbers"):
        load_libsvm(write(tmp_path, "3,x 1:1\n4 2:1\n"), 5, classes="auto")


def test_one_label_a_row_stays_one_class_id_a_row(tmp_path):
    from cocoa_tpu.data import load_libsvm

    data = load_libsvm(write(tmp_path, "7 1:1\n2 2:1\n7 3:1\n9 1:2\n"), 3,
                       classes="auto")
    assert data.classes.shape == (4,) and data.classes.tolist() == [1, 0, 1,
                                                                    2]
    assert data.class_values == (2, 7, 9)


def test_label_sets_shard_and_derive_their_labels(tmp_path):
    import jax.numpy as jnp

    from cocoa_tpu.data import load_libsvm, shard_dataset
    from cocoa_tpu.data.sharding import (class_labels, class_signs,
                                         class_tile_shape, class_vector,
                                         label_sets)

    data = load_libsvm(write(tmp_path, MULTI), 5, classes="auto")
    ds = shard_dataset(data, k=2, layout="sparse")
    ids = np.asarray(ds.classes)
    assert ids.shape == (2, ds.n_shard, 3) and ds.label_slots == 3
    np.testing.assert_array_equal(ids[0, :3], data.classes[:3])
    np.testing.assert_array_equal(ids[1, :2], data.classes[3:])
    assert (ids[0, 3:] == -1).all() and (ids[1, 2:] == -1).all()  # padding
    mask = np.asarray(ds.mask)
    want = np.stack([np.where((ids == t).any(-1), 1.0, -1.0) * mask
                     for t in range(3)])
    np.testing.assert_array_equal(
        np.asarray(class_labels(ds.classes, ds.mask,
                                jnp.arange(3)[:, None, None])), want)
    np.testing.assert_array_equal(
        np.asarray(class_labels(ds.classes, ds.mask, 2)), want[2])
    # the class axis last, as (R, 128) tiles: class t at [t // 128, t % 128]
    assert class_tile_shape(3) == class_tile_shape(1000) == (8, 128)
    assert class_tile_shape(1025) == (16, 128)
    signs = class_signs(label_sets(ds.classes, 2), 3, jnp.float32)
    assert signs.shape == (2, ds.n_shard, 8, 128)
    real = np.asarray(class_vector(signs, 3))
    np.testing.assert_array_equal(np.moveaxis(real, -1, 0) * mask, want)
    assert (np.asarray(signs).reshape(2, ds.n_shard, -1)[..., 3:] == -1
            ).all()


def test_one_class_id_a_row_gives_the_bits_it_gave():
    """``class_labels`` on (K, n_shard) ids is the parent's expression, bit
    for bit, at mnist8m's small dense shape; as a set of size one (a
    trailing axis of one id, or more with -1 past it) it is the same."""
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import class_labels

    r = np.random.default_rng(0)
    ids = jnp.asarray(r.integers(0, 10, (2, 504)).astype(np.int32))
    mask = jnp.asarray((r.uniform(size=(2, 504)) < 0.95).astype(np.float32))
    every = jnp.arange(10, dtype=ids.dtype)[:, None, None]
    parents = jnp.where(ids == every, 1.0, -1.0).astype(mask.dtype) * mask
    np.testing.assert_array_equal(
        np.asarray(class_labels(ids, mask, every)), np.asarray(parents))
    sets = jnp.concatenate([ids[..., None], jnp.full(ids.shape + (2,), -1,
                                                     ids.dtype)], -1)
    for form in (ids[..., None], sets):
        np.testing.assert_array_equal(
            np.asarray(class_labels(form, mask, every)), np.asarray(parents))


def test_the_length_order_carries_the_label_sets():
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.data.sharding import order_rows_by_length

    data = standin()
    ds = shard_dataset(data, k=2, layout="sparse")
    before = (np.asarray(ds.classes).copy(), np.asarray(ds.sp_indices).copy())
    order_rows_by_length(ds)
    order = np.asarray(ds.row_order)
    lens = (np.asarray(ds.sp_values) != 0).sum(-1)
    assert (np.diff(lens, axis=1) <= 0).all() and (order != np.arange(
        order.shape[1])).any()
    for s in range(2):
        np.testing.assert_array_equal(np.asarray(ds.classes)[s],
                                      before[0][s][order[s]])
        np.testing.assert_array_equal(np.asarray(ds.sp_indices)[s],
                                      before[1][s][order[s]])


# --- the system against the plain reference ----------------------------------

# the reference's readings on these jobs (float32 both sides, ~40 rounds of
# 4 x 6 steps): every class's gap within 1e-6 of the recorded one (both are
# differences of two float32 means over 256 rows near 0.5-0.9: a few ulps of
# those, 6e-8 each), W within 2e-5 of max(1, |w_t(alpha_t)|_inf) of
# w_t(alpha_t) (a running += over ~1,000 steps against one scatter-add).
# One bfloat16 rounding of W moves a coordinate by up to 2^-9 |w_t|_inf =
# 2e-3 at |w|_inf ~ 1: a hundred times the limit on W, so it must fail it.
GAP_TOL, W_TOL = 1e-6, 2e-5


@pytest.mark.parametrize("t,loss", [(24, "hinge"), (24, "logistic"),
                                    (130, "hinge"), (130, "logistic")])
def test_the_interpreted_chain_meets_the_reference(t, loss):
    import jax.numpy as jnp

    from chipbench import reference_labels

    data = standin(t=t, seed=t)
    target = 2e-2 if loss == "hinge" else 5e-3
    ds, w, alpha, traj = run_job(data, loss=loss, target=target)
    path = traj.meta["solver_path"]
    assert (path["kernel"], path["interpret"], path["state"],
            path["class_axis"], path["classes"], path["class_tiles"],
            path["label_slots"], path["local_ids"], path["segments"]) == (
        "pallas", True, "hbm", "lanes", t, 1, 3, "direct", 1)
    assert path["step_solve"] == "vector" and path["lane_fill"] == t / 1024
    assert w.shape == (data.num_features, 8, 128)
    assert alpha.shape == (4, ds.n_shard, 8, 128)
    last = traj.records[-1]
    assert traj.stopped == "target" and len(last.class_gaps) == t
    assert last.gap == max(last.class_gaps) <= target
    ref = reference_labels.recompute(ds, w, alpha, 1e-2, loss, row_block=32)
    off = max(abs(a - b) for a, b in zip(ref["gaps"], last.class_gaps))
    assert off < GAP_TOL, off
    assert max(ref["w_err"]) < W_TOL < min(ref["w_err_bf16"]), (
        max(ref["w_err"]), min(ref["w_err_bf16"]))
    assert ref["pad_lanes_max"] == 0.0
    assert 0.0 <= ref["alpha_min"] and ref["alpha_max"] <= 1.0
    if loss == "logistic":          # strictly inside once stepped on
        assert ref["alpha_max"] < 1.0
    # the same W rounded once to bfloat16 is outside the limit on W
    rounded = reference_labels.recompute(
        ds, w.astype(jnp.bfloat16).astype(jnp.float32), alpha, 1e-2, loss,
        row_block=32)
    assert min(rounded["w_err"]) > W_TOL
    # the lanes past T were never stepped
    assert not np.asarray(alpha).reshape(4, ds.n_shard, -1)[..., t:].any()


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_the_plain_round_is_the_chain(loss):
    """``fori`` (what a cpu resolves to) and the interpreted chain run the
    same steps in the same order: the same job to float32 rounding."""
    data = standin(seed=5)
    _, w0, a0, t0 = run_job(data, loss=loss, pallas=False, rounds=10,
                            target=1e-9)
    _, w1, a1, t1 = run_job(data, loss=loss, pallas=True, rounds=10,
                            target=1e-9)
    assert t0.meta["solver_path"]["kernel"] == "fori"
    assert t0.meta["solver_path"]["class_axis"] == "lanes"
    assert [r.round for r in t0.records] == [r.round for r in t1.records]
    np.testing.assert_allclose(np.asarray(w0), np.asarray(w1), atol=2e-6)
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1), atol=2e-6)


def test_the_share_adds_up():
    """T = 24 as three batches of 8 labels: model t of batch b is model
    8 b + t of the one job over all 24, and the uncut reference certifies
    the stacked models."""
    import dataclasses

    from chipbench import reference_labels
    from cocoa_tpu.data.sharding import class_vector

    data = standin(seed=9)
    ds, w, alpha, traj = run_job(data, rounds=30, target=1e-9)
    whole_w = np.asarray(class_vector(w, 24))               # (d, 24)
    whole_a = np.asarray(class_vector(alpha, 24))
    whole_gaps = traj.records[-1].class_gaps
    for b in range(3):
        ids = data.classes.copy()
        ids = np.where((ids >= 8 * b) & (ids < 8 * b + 8), ids - 8 * b, -1)
        ids = -np.sort(-ids, axis=1)            # the batch's ids first ...
        held = (ids >= 0).sum(1)
        for i in range(len(ids)):               # ... ascending, -1 after
            ids[i, :held[i]] = np.sort(ids[i, :held[i]])
        batch = dataclasses.replace(data, classes=ids.astype(np.int32),
                                    num_classes=8)
        _, wb, ab, tb = run_job(batch, rounds=30, target=1e-9)
        # the same sampler, independent models: to float32 rounding (the
        # lanes are independent, so in fact to the bit)
        np.testing.assert_allclose(np.asarray(class_vector(wb, 8)),
                                   whole_w[:, 8 * b:8 * b + 8], atol=1e-6)
        np.testing.assert_allclose(np.asarray(class_vector(ab, 8)),
                                   whole_a[..., 8 * b:8 * b + 8], atol=1e-6)
        np.testing.assert_allclose(tb.records[-1].class_gaps,
                                   whole_gaps[8 * b:8 * b + 8], atol=1e-6)
    ref = reference_labels.recompute(ds, w, alpha, 1e-2, "hinge",
                                     row_block=32)
    np.testing.assert_allclose(ref["gaps"], whole_gaps, atol=1e-6)
    assert max(ref["w_err"]) < W_TOL


def test_evaluate_reads_t_certificates_from_one_pass():
    from chipbench import reference_labels
    from cocoa_tpu.evals import objectives

    data = standin(seed=2)
    ds, w, alpha, traj = run_job(data, rounds=10, target=1e-9)
    primal, gap, err, gaps = objectives.evaluate(ds, w, alpha, 1e-2)
    assert err is None and len(gaps) == 24 and gap == max(gaps)
    assert gaps == traj.records[-1].class_gaps
    ref = reference_labels.recompute(ds, w, alpha, 1e-2, row_block=32)
    np.testing.assert_allclose(gaps, ref["gaps"], atol=1e-6)
    assert abs(primal - ref["primal"][int(np.argmax(gaps))]) < 1e-6
    # a test set: the label-wise error, the share of wrong signs
    *_, err, _ = objectives.evaluate(ds, w, alpha, 1e-2, test_ds=ds)
    assert 0.0 < err < 0.2


# --- the plan, the path, the refusals ----------------------------------------

def test_the_plan_with_a_class_axis():
    from cocoa_tpu.ops.pallas_sparse_hbm import (HBM_VMEM_BUDGET, HbmPlan,
                                                 hbm_plan)
    from cocoa_tpu.ops.pallas_sparse_lanes import lanes_plan

    # amazoncat13k: one call a shard's round, the ids VMEM holds at a time
    # are one step's 256 slots at (2 T_pad 4) B = 8 KB an id of [w | dw]
    plan = lanes_plan(256, 14827, 4, 1024, 8)
    assert plan == HbmPlan(t=1, s=14848, m=256, w_r=256, chunk=32,
                           direct=True, unrolled=False, t_pad=1024)
    assert plan.m * 2 * plan.t_pad * 4 == 2 << 20 < HBM_VMEM_BUDGET
    # sixteen tiles of classes still fit; sixty-four outgrow the budget
    assert lanes_plan(256, 14827, 4, 16384, 8).m == 256
    assert lanes_plan(256, 14827, 4, 65536, 8) is None
    assert (2 * 256 + 2) * 65536 * 4 > HBM_VMEM_BUDGET
    # T = 1 plans are what they were, field for field: kddb's and criteo's
    assert hbm_plan(29890095, 64, 240801, 4) == HbmPlan(
        t=2, s=120416, m=7733248, w_r=64, chunk=32, direct=False,
        unrolled=False)
    assert hbm_plan(1000000, 40, 143251, 4, one_length=True) == HbmPlan(
        t=1, s=143264, m=1000448, w_r=40, chunk=32, direct=True,
        unrolled=True)
    assert HbmPlan(1, 32, 1024, 8, 32, True).t_pad == 0


def test_the_path_says_which_axis_carries_the_classes():
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.data.synth import synth_dense
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    data = standin()
    ds = shard_dataset(data, k=4, layout="sparse")
    path = resolve_solver_path(ds, 6, None, math="fast", pallas=True)
    assert (path.class_axis, path.class_tiles, path.label_slots,
            path.ids_per_segment, path.table_width) == ("lanes", 1, 3, 24,
                                                        24)
    said = path.describe()
    for words in ("state in HBM", "direct local ids", "24 class models",
                  "the class axis on the lanes", "3 label id(s) a row",
                  "24 ids in VMEM at a time"):
        assert words in said, (words, said)
    assert path.as_dict()["class_axis"] == "lanes"
    off = resolve_solver_path(ds, 6, None, math="fast")     # a cpu: fori
    assert (off.kernel, off.class_axis, off.ids_per_segment) == (
        "fori", "lanes", None)
    # dense rows keep the sublanes; T = 1 states no axis
    dense = synth_dense(64, 8, seed=0)
    dense.classes = np.arange(64, dtype=np.int32) % 3
    dense.num_classes = 3
    on_rows = resolve_solver_path(
        shard_dataset(dense, k=2, layout="dense"), 3, None, math="fast")
    assert (on_rows.class_axis, on_rows.class_tiles,
            on_rows.label_slots) == ("sublanes", None, None)
    dense.classes, dense.num_classes = None, 1
    one = resolve_solver_path(shard_dataset(dense, k=2, layout="dense"), 3,
                              None, math="fast")
    assert one.class_axis is None and one.classes == 1


@pytest.mark.parametrize("what", ["dense_sets", "hybrid", "accel", "init",
                                  "checkpoint", "block"])
def test_what_the_lanes_do_not_carry_is_refused_by_name(what, tmp_path):
    import dataclasses

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import run_cocoa
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    data = standin()
    params = Params(n=data.n, num_rounds=5, local_iters=6, lam=1e-2)
    kw = dict(plus=True, quiet=True, math="fast", accel="off")
    if what == "dense_sets":
        ds = shard_dataset(data, k=4, layout="dense")
        with pytest.raises(ValueError, match="label sets .* sparse rows"):
            resolve_solver_path(ds, 6, None, math="fast")
        return
    ds = shard_dataset(data, k=4, layout="sparse")
    if what == "hybrid":
        # (rows kept as a stream carry the class axis since PR 57:
        # tests/test_labelstream.py; the hot-column panel does not)
        import jax.numpy as jnp

        hot = dataclasses.replace(
            ds, X_hot=jnp.zeros((4, ds.n_shard, 8)),
            hot_cols=jnp.zeros((4, 8), jnp.int32))
        with pytest.raises(ValueError, match="hybrid layout"):
            resolve_solver_path(hot, 6, None, math="fast")
    elif what == "block":
        with pytest.raises(ValueError, match="no class axis"):
            resolve_solver_path(ds, 6, None, math="fast", block_size=8)
    elif what == "accel":
        with pytest.raises(ValueError, match="--accel"):
            run_cocoa(ds, params, DebugParams(debug_iter=5),
                      **{**kw, "accel": "on", "gap_target": 1e-2})
    elif what == "init":
        with pytest.raises(ValueError, match="starts from alpha = 0"):
            run_cocoa(ds, params, DebugParams(debug_iter=5), **kw,
                      w_init=np.zeros((data.num_features, 8, 128)))
    else:
        with pytest.raises(ValueError, match="checkpoints hold one model"):
            run_cocoa(ds, params,
                      DebugParams(debug_iter=5, chkpt_iter=5,
                                  chkpt_dir=str(tmp_path)), **kw)


def test_the_cli_trains_a_multi_label_file(tmp_path, capsys, monkeypatch):
    """--classes=auto on a multi-label file with --layout=sparse: the label
    sets are found, the path says lanes, every class is reported; what
    does not run yet is said by name."""
    from cocoa_tpu import cli

    data = standin(n=96, d=24, t=6, width=8)
    lines = []
    for i in range(data.n):
        idx, val = data.row(i)
        label = ",".join(str(10 + t) for t in data.classes[i] if t >= 0)
        lines.append((label + " " if label else "") + " ".join(
            f"{c + 1}:{v:.6f}" for c, v in zip(idx, val)))
    path = write(tmp_path, "\n".join(lines) + "\n")
    argv = [f"--trainFile={path}", "--numFeatures=24", "--numSplits=2",
            "--lambda=0.01", "--localIterFrac=0.2", "--numRounds=10",
            "--debugIter=5", "--justCoCoA=true", "--classes=auto",
            "--accel=off", "--mesh=1", "--math=fast"]
    assert cli.main(argv + ["--layout=sparse"]) == 0
    out = capsys.readouterr().out
    assert "classes: 6 found" in out and "label sets, up to 3 a row" in out
    assert "the class axis on the lanes" in out
    assert "per-class gaps" in out
    assert cli.main(argv + ["--layout=dense"]) == 2
    assert "label SETS" in capsys.readouterr().err
    assert cli.main(argv + ["--layout=sparse", "--hotCols=8"]) == 2
    assert "carry no class axis" in capsys.readouterr().err
    # T x d past the device: the limit is the device's own word, not a
    # chip's size written into the CLI (W, two dW and alpha at T_pad =
    # 1,024 are 4 * 1,024 * (3 * 24 + 96) B = 0.69 MB here)
    monkeypatch.setattr(cli, "_device_memory_limit", lambda: 1 << 19)
    assert cli.main(argv + ["--layout=sparse"]) == 2
    err = capsys.readouterr().err
    assert "0.000688 GB of the 0.000524 GB" in err and "in batches" in err
