"""One-vs-rest over shared rows (PR 38): a dataset that states T > 1 classes
trains T class models in ONE ``run_cocoa`` job over one copy of the rows.

Lane t of such a job is the run of class t against the rest under the job's
one sampler, so it is held here against a solo ``run_cocoa`` on the labels
y_t: on the ``fori`` path and on the dense Pallas kernel in interpret mode,
hinge and logistic, T in {1, 3, 10}.  Tolerances: both sides are float32
(the suite's x64 leaves the data's dtype alone); the ``fori`` lanes run
the T = 1 step under a vmap (equal to rounding: 1e-5 after tens of
rounds), the Pallas lanes reduce x . (w + sigma' dw) once where the T = 1
kernel reduces the two dots apart."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

K, N_SHARD, D, H = 2, 128, 16, 16
LAM, TARGET = 1e-2, 2e-3


def rows_and_classes(t: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(K, N_SHARD, D)).astype(np.float32)
    x /= np.linalg.norm(x, axis=-1, keepdims=True)
    u = rng.normal(size=(max(t, 2), D)).astype(np.float32)
    return x, np.argmax(x @ u.T, axis=-1).astype(np.int32)


def dataset(x, labels, classes=None, t=1):
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import ShardedDataset

    ones = jnp.ones((K, N_SHARD), jnp.float32)
    return ShardedDataset(
        layout="dense", n=K * N_SHARD, num_features=D,
        counts=np.full(K, N_SHARD, np.int64),
        labels=jnp.asarray(labels, jnp.float32), mask=ones,
        sq_norms=jnp.asarray((x * x).sum(-1)), X=jnp.asarray(x),
        classes=None if classes is None else jnp.asarray(classes),
        num_classes=t)


def against_rest(cls, t):
    return np.where(cls == t, 1.0, -1.0).astype(np.float32)


def job(ds, *, pallas, loss="hinge", rounds=30, gap_target=None,
        device_loop=True, **kw):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.solvers import run_cocoa

    return run_cocoa(
        ds, Params(n=ds.n, num_rounds=rounds, local_iters=H, lam=LAM,
                   loss=loss),
        DebugParams(debug_iter=5, seed=0), plus=True, quiet=True,
        math="fast", device_loop=device_loop, rng="permuted",
        gap_target=gap_target, accel="off", pallas=pallas, **kw)


@pytest.mark.parametrize("pallas", [False, True], ids=["fori", "pallas"])
@pytest.mark.parametrize("loss", ["hinge", "logistic"])
@pytest.mark.parametrize("t", [3, 10])
def test_lane_t_is_the_solo_job_of_class_t(t, loss, pallas):
    x, cls = rows_and_classes(t)
    w, alpha, traj = job(dataset(x, against_rest(cls, 0), cls, t),
                         pallas=pallas, loss=loss)
    assert w.shape == (t, D) and alpha.shape == (t, K, N_SHARD)
    path = traj.meta["solver_path"]
    assert path["classes"] == t
    assert path["kernel"] == ("pallas" if pallas else "fori")
    last = traj.records[-1]
    assert len(last.class_gaps) == t and last.gap == max(last.class_gaps)
    for lane in sorted({0, t // 2, t - 1}):
        w1, a1, solo = job(dataset(x, against_rest(cls, lane)),
                           pallas=pallas, loss=loss)
        np.testing.assert_allclose(np.asarray(w[lane]), np.asarray(w1),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(alpha[lane]), np.asarray(a1),
                                   atol=1e-5)
        assert abs(solo.records[-1].gap - last.class_gaps[lane]) < 1e-5
        if last.gap == last.class_gaps[lane]:
            assert abs(solo.records[-1].primal - last.primal) < 1e-5


@pytest.mark.parametrize("pallas", [False, True], ids=["fori", "pallas"])
def test_one_class_stated_is_todays_job_to_the_bit(pallas):
    """T = 1: a dataset that states one class runs nothing of the class
    axis — the arrays, every record and the run's record are those of a
    dataset that states nothing."""
    x, cls = rows_and_classes(2)
    plain = dataset(x, against_rest(cls, 0))
    stated = dataclasses.replace(plain, num_classes=1)
    (w0, a0, t0), (w1, a1, t1) = (job(ds, pallas=pallas, gap_target=TARGET)
                                  for ds in (plain, stated))
    assert w0.shape == (D,) and a0.shape == (K, N_SHARD)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    strip = lambda r: dataclasses.replace(r, wall_time=None)  # noqa: E731
    assert [strip(r) for r in t0.records] == [strip(r) for r in t1.records]
    assert all(r.class_gaps is None and r.classes_done is None
               for r in t0.records)
    assert t0.meta["solver_path"] == t1.meta["solver_path"]
    assert (t0.meta["solver_path"]["classes"],
            t0.meta["solver_path"]["lane_fill"]) == (1, None)


@pytest.mark.parametrize("device_loop", [True, False],
                         ids=["device_loop", "host_stepped"])
def test_the_job_stops_when_the_worst_class_certifies(device_loop):
    t = 4
    x, cls = rows_and_classes(t, seed=3)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    w, alpha, traj = job(ds, pallas=False, rounds=400, gap_target=TARGET,
                         device_loop=device_loop)
    assert traj.stopped == "target"
    *before, last = traj.records
    assert before, "certified at the first evaluation: nothing is shown"
    for r in before:        # some class was still over: the job went on
        assert max(r.class_gaps) > TARGET and r.gap == max(r.class_gaps)
        assert r.classes_done == sum(g <= TARGET for g in r.class_gaps)
    assert max(last.class_gaps) <= TARGET and last.classes_done == t
    # no lane was frozen on the way: a class that certified early kept
    # taking the job's steps, so its gap at the stop is not its gap then
    early = [c for c in range(t)
             if any(r.class_gaps[c] <= TARGET for r in before)]
    assert early, "every class crossed at the last evaluation"
    first = next(r for r in before if r.class_gaps[early[0]] <= TARGET)
    assert first.class_gaps[early[0]] != last.class_gaps[early[0]]
    # and the two drivers tell the same story
    if not device_loop:
        _, _, dev = job(ds, pallas=False, rounds=400, gap_target=TARGET)
        assert [(r.round, r.gap, r.class_gaps) for r in dev.records] == \
            [(r.round, r.gap, r.class_gaps) for r in traj.records]


def test_the_system_against_the_plain_reference():
    """``chipbench/reference_ovr.py`` from alpha and the class ids alone:
    every class's gap and w(alpha) as the program has them."""
    from chipbench import reference_ovr

    t = 5
    x, cls = rows_and_classes(t, seed=11)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    w, alpha, traj = job(ds, pallas=True, rounds=40, gap_target=TARGET)
    ref = reference_ovr.recompute(ds, w, alpha, LAM)
    last = traj.records[-1]
    np.testing.assert_allclose(ref["gaps"], last.class_gaps, atol=2e-6)
    assert max(ref["w_err"]) < 1e-5 < min(ref["w_err_bf16"])
    worst = int(np.argmax(last.class_gaps))
    assert abs(ref["primal"][worst] - last.primal) < 1e-5
    assert 0.0 <= ref["alpha_min"] and ref["alpha_max"] <= 1.0
    assert abs(sum(ref["class_share"]) - 1.0) < 1e-6


def test_the_eval_event_carries_every_class(tmp_path):
    from cocoa_tpu.telemetry import events as tele

    t = 3
    x, cls = rows_and_classes(t)
    path = str(tmp_path / "ev.jsonl")
    tele.get_bus().configure(path)
    try:
        _, _, traj = job(dataset(x, against_rest(cls, 0), cls, t),
                         pallas=False, rounds=10, gap_target=TARGET)
    finally:
        tele.get_bus().reset()
    with open(path) as f:
        evals = [e for e in map(json.loads, f) if e["event"] == "round_eval"]
    assert [e["t"] for e in evals] == [r.round for r in traj.records]
    for e, r in zip(evals, traj.records):
        assert e["class_gaps"] == r.class_gaps and e["gap"] == r.gap
        assert e["classes_done"] == r.classes_done


# --- what the class axis is not carried through is refused by name ----------

@pytest.mark.parametrize("what", ["accel_auto", "accel_on", "sigma_auto",
                                  "checkpoints", "test_set"])
def test_a_job_the_class_axis_cannot_run_is_refused(what, tmp_path):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.solvers import run_cocoa

    t = 3
    x, cls = rows_and_classes(t)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    params = Params(n=ds.n, num_rounds=10, local_iters=H, lam=LAM)
    debug = DebugParams(debug_iter=5, seed=0)
    kw = dict(plus=True, quiet=True, math="fast", gap_target=TARGET)
    with pytest.raises(ValueError) as err:
        if what.startswith("accel"):
            run_cocoa(ds, params, debug, accel=what[6:], **kw)
        elif what == "sigma_auto":
            run_cocoa(ds, dataclasses.replace(params, sigma="auto"), debug,
                      accel="off", **kw)
        elif what == "checkpoints":
            run_cocoa(ds, params, dataclasses.replace(
                debug, chkpt_dir=str(tmp_path), chkpt_iter=5), accel="off",
                **kw)
        else:
            run_cocoa(ds, params, debug, accel="off",
                      test_ds=dataset(x, against_rest(cls, 0)), **kw)
    said = str(err.value)
    assert {"accel_auto": "--accel=off", "accel_on": "--accel=off",
            "sigma_auto": "schedule", "checkpoints": "checkpoints",
            "test_set": "--classes"}[what] in said


def test_the_resolver_refuses_sparse_rows_a_mesh_and_blocks():
    import types

    import jax.numpy as jnp

    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    labels = jnp.zeros((2, 128), jnp.float32)
    ds = types.SimpleNamespace(
        k=2, labels=labels, layout="sparse", n_hot=0, n_shard=128,
        num_features=64, sp_indices=jnp.zeros((2, 128, 4), jnp.int32),
        sp_row_ptr=None, num_classes=3)
    # (sparse rows as a rectangle carry the classes on the lanes since
    # PR 48, tests/test_labels.py, and rows kept as a stream since PR 57,
    # tests/test_labelstream.py; the hot-column panel carries none)
    ds.n_hot = 8
    with pytest.raises(ValueError, match="the hybrid layout"):
        resolve_solver_path(ds, 8, math="fast")
    ds.layout, ds.n_hot = "dense", 0
    with pytest.raises(ValueError, match="block"):
        resolve_solver_path(ds, 8, math="fast", block_size=128)
    path = resolve_solver_path(ds, 8, math="fast", pallas=True)
    assert (path.classes, path.form, path.step_solve) == (
        3, "interleaved", "lanes")
    assert path.lane_fill == 3 / 8
    assert "3 class models one-vs-rest" in path.describe()
    # on a CPU the resolver answers fori, and says nothing of lanes
    auto = resolve_solver_path(ds, 8, math="fast")
    assert (auto.kernel, auto.classes, auto.lane_fill) == ("fori", 3, None)


def test_a_state_too_large_for_vmem_runs_fori():
    """All K shards' state tiles are resident: the quarter share of
    mnist8m (8 x 253,125 rows, T = 10) is 124 MB and does not fit, the
    eighth (62 MB) does (PERF.md section 6, PR 38: Mosaic agrees)."""
    from cocoa_tpu.ops import pallas_sdca

    assert pallas_sdca.class_rows(10) == 16 and pallas_sdca.class_rows(6) == 8
    assert pallas_sdca.classes_fit(8, 126576, 784, 10, 4)
    assert not pallas_sdca.classes_fit(8, 253136, 784, 10, 4)


# --- the loader reads classes ------------------------------------------------

@pytest.fixture
def multiclass_file(tmp_path):
    rng = np.random.default_rng(5)
    n, d, t = 90, 12, 3
    x = rng.normal(size=(n, d))
    labels = np.array([2, 5, 9])[np.argmax(
        x @ rng.normal(size=(d, t)), axis=1)]
    labels[0] = 1                       # what the binary rule calls +1
    path = str(tmp_path / "mc.dat")
    with open(path, "w") as f:
        for i in range(n):
            f.write(f"{labels[i]} " + " ".join(
                f"{j + 1}:{x[i, j]:.5f}" for j in range(d)) + "\n")
    return path, labels


def test_the_loader_keeps_the_binary_rule_and_reads_classes_when_asked(
        multiclass_file):
    from cocoa_tpu.data import load_libsvm, shard_dataset

    path, labels = multiclass_file
    binary = load_libsvm(path, 12)
    assert binary.classes is None and binary.num_classes == 1
    np.testing.assert_array_equal(binary.labels,
                                  np.where(labels == 1, 1.0, -1.0))
    data = load_libsvm(path, 12, classes="auto")
    np.testing.assert_array_equal(data.labels, binary.labels)  # beside
    assert data.num_classes == 4 and data.class_values == (1, 2, 5, 9)
    np.testing.assert_array_equal(
        np.asarray(data.class_values)[data.classes], labels)
    assert load_libsvm(path, 12, classes=4).num_classes == 4
    ds = shard_dataset(data, k=2, layout="dense")
    assert ds.num_classes == 4 and ds.classes.shape == ds.labels.shape
    rows = np.concatenate([np.asarray(ds.classes)[s, :c]
                           for s, c in enumerate(ds.counts)])
    np.testing.assert_array_equal(rows, data.classes)
    assert "classes" in ds.shard_arrays()
    assert "classes" not in shard_dataset(binary, k=2,
                                          layout="dense").shard_arrays()


def test_a_class_count_the_file_contradicts_is_refused_with_the_numbers(
        multiclass_file):
    from cocoa_tpu.data import load_libsvm

    path, _ = multiclass_file
    with pytest.raises(ValueError) as err:
        load_libsvm(path, 12, classes=10)
    assert "10 classes were stated" in str(err.value)
    assert "holds 4 distinct labels" in str(err.value)
    assert "[1.0, 2.0, 5.0, 9.0]" in str(err.value)


@pytest.mark.parametrize("flags, said", [
    (["--objective=lasso"], "--objective=lasso"),
    (["--fleet=/nowhere.jsonl"], "--classes"),
    (["--layout=sparse", "--hotCols=8"], "carry no class axis"),
    (["--justCoCoA=false"], "--justCoCoA=true"),
    (["--classes=7"], "7 classes were stated"),
    (["--accel=auto", "--gapTarget=1e-2"], "--accel=off"),
])
def test_the_cli_refuses_what_cannot_mean_anything(multiclass_file, flags,
                                                   said, capsys):
    from cocoa_tpu import cli

    path, _ = multiclass_file
    argv = [f"--trainFile={path}", "--numFeatures=12", "--numSplits=2",
            "--lambda=0.01", "--mesh=1", "--quiet", "--justCoCoA=true",
            "--classes=auto", "--accel=off", "--numRounds=4",
            "--debugIter=2", *flags]
    try:
        code = cli.main(argv)
    except ValueError as e:         # the library's refusal, by name
        code, text = 2, str(e)
    else:
        text = capsys.readouterr().err
    assert code == 2 and said in text, text


def test_the_cli_trains_a_multiclass_file_as_one(multiclass_file, capsys):
    from cocoa_tpu import cli

    path, _ = multiclass_file
    assert cli.main([
        f"--trainFile={path}", f"--testFile={path}", "--numFeatures=12",
        "--numSplits=2", "--lambda=0.01", "--localIterFrac=0.2", "--mesh=1",
        "--justCoCoA=true", "--math=fast", "--deviceLoop", "--rng=permuted",
        "--accel=off", "--gapTarget=0.2", "--numRounds=200",
        "--debugIter=10", "--classes=4", "--layout=dense"]) == 0
    out = capsys.readouterr().out
    assert "classes: 4 found ([1, 2, 5, 9])" in out
    assert "4 class models one-vs-rest" in out
    assert "per-class gaps:" in out and "(4 of 4 at target)" in out
    assert "Duality gap by class:" in out


# --- the class kernel's state stays in tile form across a chunk (PR 51) -----

def _digest(*arrays):
    import hashlib

    return hashlib.sha256(
        b"".join(np.asarray(a).tobytes() for a in arrays)).hexdigest()


@pytest.mark.parametrize("n_shard", [200, 33 * 128 + 5],
                         ids=["two_blocks", "thirty_four_blocks"])
@pytest.mark.parametrize("t", [1, 3, 10, 14])
def test_pack_then_alpha_is_alpha_to_the_bit(t, n_shard):
    """``class_state_alpha`` undoes ``class_state_pack`` where n_shard is no
    multiple of 128, at one and at two 8-row groups of ``class_rows``; the
    norms and the class ids sit on tile rows T and T + 1, zeros past them
    and past n_shard."""
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    rng = np.random.default_rng(t)
    k = 2
    alpha = rng.random((t, k, n_shard)).astype(np.float32)
    sq = rng.random((k, n_shard)).astype(np.float32)
    cls = rng.integers(0, t, (k, n_shard)).astype(np.int32)
    state = pallas_sdca.class_state_pack(jnp.asarray(alpha), jnp.asarray(sq),
                                         jnp.asarray(cls))
    rows, n_blocks = pallas_sdca.class_rows(t), -(-n_shard // 128)
    assert rows == (8 if t <= 6 else 16)
    assert state.shape == (k, n_blocks, rows, 128)
    back = pallas_sdca.class_state_alpha(state, t, n_shard)
    assert _digest(back) == _digest(alpha)
    flat = np.asarray(state).transpose(2, 0, 1, 3).reshape(rows, k, -1)
    np.testing.assert_array_equal(flat[t, :, :n_shard], sq)
    np.testing.assert_array_equal(flat[t + 1, :, :n_shard], cls)
    assert not flat[t + 2:].any() and not flat[:, :, n_shard:].any()


def _per_round_pack(params, mode, scaling, sigma, classes, per_shard, pallas,
                    interpret, lanes_plan=None, block_chain="xla"):
    """The class round as it was before the loop carried the tiles, kept
    here as the reference: the state tile built from (T, K, n_shard) EVERY
    round, taken apart again every round, the scaling law on the
    (T, K, n_shard) form.  Shaped as ``solvers/cocoa._class_round``."""
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    def per_round(w, alpha, idxs_kh, shards):
        t, k, n_shard = alpha.shape
        dtype = alpha.dtype
        rows = pallas_sdca.class_rows(t)
        n_blocks = -(-n_shard // 128)
        n_pad = n_blocks * 128

        def blocked(v):
            v = jnp.pad(v.astype(dtype), [(0, 0)] * (v.ndim - 1)
                        + [(0, n_pad - n_shard)])
            return v.reshape(*v.shape[:-1], n_blocks, 128)

        state = jnp.concatenate(
            [jnp.transpose(blocked(alpha), (1, 2, 0, 3)),
             blocked(shards["sq_norms"])[:, :, None],
             blocked(shards["classes"])[:, :, None],
             jnp.zeros((k, n_blocks, rows - t - 2, 128), dtype)], axis=2)
        dw, state = pallas_sdca.pallas_sdca_round_classes_tiles(
            w, state, shards.get("X_folded", shards["X"]), idxs_kh,
            params.lam, params.n, mode=mode, sigma=sigma,
            interpret=interpret, loss=params.loss,
            smoothing=params.smoothing)
        a_inner = jnp.transpose(state[:, :, :t], (2, 0, 1, 3)).reshape(
            t, k, n_pad)[:, :, :n_shard]
        return dw, alpha + scaling * (a_inner - alpha)

    return per_round, None


def _class_job(ds, *, plus, rounds=12, pallas=True, **kw):
    """A 12-round one-vs-rest job, an eval every 4 rounds.  beta = 1 at
    K = 2: CoCoA's averaging scales by 1/2, CoCoA+ by gamma = 1, so every
    product of the scaling law is exact and a multiply-add the CPU backend
    contracts rounds as the two operations do."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.solvers import base, cocoa, run_cocoa

    base._DEVICE_RUNS.clear()
    cocoa._CHUNK_STEPS.clear()
    return run_cocoa(
        ds, Params(n=ds.n, num_rounds=rounds, local_iters=H, lam=LAM,
                   beta=1.0),
        DebugParams(debug_iter=4, seed=0), plus=plus, quiet=True,
        math="fast", rng="permuted", accel="off", pallas=pallas,
        **{"device_loop": True, **kw})


@pytest.mark.parametrize("device_loop", [True, False],
                         ids=["device_loop", "host_stepped"])
@pytest.mark.parametrize("plus", [True, False],
                         ids=["cocoa_plus_gamma_1", "cocoa_averaging_half"])
def test_tiles_across_a_chunk_are_the_per_round_pack_to_the_bit(
        monkeypatch, plus, device_loop):
    from cocoa_tpu.solvers import cocoa

    t = 3
    x, cls = rows_and_classes(t, seed=7)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    w, alpha, traj = _class_job(ds, plus=plus, device_loop=device_loop)
    assert traj.meta["solver_path"]["class_state"] == "tiles"
    assert [r.round for r in traj.records] == [4, 8, 12]
    monkeypatch.setattr(cocoa, "_class_round", _per_round_pack)
    w0, alpha0, traj0 = _class_job(ds, plus=plus, device_loop=device_loop)
    assert float(np.abs(np.asarray(alpha0)).max()) > 0
    assert _digest(w, alpha) == _digest(w0, alpha0)
    assert [(r.gap, r.class_gaps) for r in traj.records] == \
        [(r.gap, r.class_gaps) for r in traj0.records]


@pytest.mark.parametrize("pallas", [True, False], ids=["tiles", "fori"])
def test_a_resume_at_an_eval_boundary_is_the_uninterrupted_job(pallas):
    """The loop's state between chunks is (w, alpha (T, K, n_shard)) on
    every path: what a caller takes at round 8 and hands back in continues
    to round 12 as the job that never stopped."""
    t = 3
    x, cls = rows_and_classes(t, seed=7)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    w, alpha, traj = _class_job(ds, plus=True, pallas=pallas)
    w8, alpha8, _ = _class_job(ds, plus=True, pallas=pallas, rounds=8)
    assert w8.shape == (t, D) and alpha8.shape == (t, K, N_SHARD)
    w12, alpha12, resumed = _class_job(
        ds, plus=True, pallas=pallas, w_init=w8, alpha_init=alpha8,
        start_round=9)
    assert [r.round for r in resumed.records] == [12]
    assert _digest(w12, alpha12) == _digest(w, alpha)
    assert resumed.records[-1].class_gaps == traj.records[-1].class_gaps


def test_the_fori_class_path_carries_alpha_as_it_is(monkeypatch):
    """Off the class kernel nothing of the tile form runs: the resolver
    says so, the round hands the chunk no pack, and a chunk's program
    holds no array of the tiles' shape."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import Params
    from cocoa_tpu.ops import pallas_sdca
    from cocoa_tpu.solvers import cocoa

    t = 3
    x, cls = rows_and_classes(t)
    ds = dataset(x, against_rest(cls, 0), cls, t)
    _, _, traj = _class_job(ds, plus=True, pallas=False, rounds=4)
    assert (traj.meta["solver_path"]["kernel"],
            traj.meta["solver_path"]["class_state"]) == ("fori", None)
    params = Params(n=ds.n, num_rounds=4, local_iters=H, lam=LAM)
    shards = ds.shard_arrays()
    for pallas, packs in ((False, False), (True, True)):
        *_, carry_form = cocoa._sdca_round_parts(
            params, K, "plus", 1.0, float(K), math="fast", pallas=pallas,
            pallas_interpret=True, classes=t)
        assert (carry_form(shards) is not None) == packs
    monkeypatch.setattr(pallas_sdca, "class_state_pack", None)  # not called
    kernel = cocoa._make_chunk_kernel(None, params, K, ("plus", 1.0, float(K)),
                                      math="fast", classes=t)
    idxs = jnp.zeros((2, K, H), jnp.int32)
    text = str(jax.make_jaxpr(kernel)(
        jnp.zeros((t, D), jnp.float32), jnp.zeros((t, K, N_SHARD),
                                                  jnp.float32), idxs, shards))
    assert f"[{K},1,{pallas_sdca.class_rows(t)},128]" not in text


@pytest.mark.parametrize("scaling", [1.0, 0.5])
@pytest.mark.parametrize("n_shard", [200, 34 * 128 - 5, 70 * 128],
                         ids=["2_blocks", "34_blocks", "70_blocks"])
def test_the_law_in_the_epilogue_is_the_law_on_alpha(n_shard, scaling):
    """One round of the tiles kernel with the scaling law in its epilogue
    (old tiles back from HBM ``LAW_BLOCKS`` blocks at a time: one part
    chunk, one whole and a tail, two whole and a tail) against the round
    without it and ``alpha + scaling (inner - alpha)`` outside; the norms
    and the class ids pass through."""
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    t, k, h = 3, 2, 8
    rng = np.random.default_rng(n_shard)
    x = rng.normal(size=(k, n_shard, D)).astype(np.float32)
    x /= np.linalg.norm(x, axis=-1, keepdims=True)
    alpha = jnp.asarray(rng.integers(0, 5, (t, k, n_shard)) / 4, jnp.float32)
    sq = jnp.asarray((x * x).sum(-1))
    cls = jnp.asarray(rng.integers(0, t, (k, n_shard)), jnp.int32)
    idxs = jnp.asarray(np.stack([rng.permutation(n_shard)[:h]
                                 for _ in range(k)]), jnp.int32)
    w = jnp.asarray(rng.normal(size=(t, D)) / 4, jnp.float32)
    state = pallas_sdca.class_state_pack(alpha, sq, cls)
    kw = dict(mode="plus", sigma=float(k), interpret=True)
    dw0, inner = pallas_sdca.pallas_sdca_round_classes_tiles(
        w, state, jnp.asarray(x), idxs, LAM, k * n_shard, **kw)
    dw, scaled = pallas_sdca.pallas_sdca_round_classes_tiles(
        w, state, jnp.asarray(x), idxs, LAM, k * n_shard, scaling=scaling,
        **kw)
    a_inner = pallas_sdca.class_state_alpha(inner, t, n_shard)
    assert float(jnp.abs(a_inner - alpha).max()) > 0
    want = alpha + scaling * (a_inner - alpha)
    assert _digest(dw) == _digest(dw0)
    assert _digest(pallas_sdca.class_state_alpha(scaled, t, n_shard)) == \
        _digest(want)
    np.testing.assert_array_equal(np.asarray(scaled)[:, :, t:],
                                  np.asarray(state)[:, :, t:])
