"""chip_smoke.py cannot rot between chip runs: its phase functions run
here at tiny shapes — the Pallas kernels explicitly in interpret mode —
and the contract around them is pinned on the CPU: the script refuses to
run without a chip, a failed phase fails the whole script, the compile
cache can be placed from outside, and ``run_start`` says which local
solver a run resolved to."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# every phase's expected path, with the kernels interpreted on the CPU
INTERPRETED = {name: {**path, "interpret": True, "platform": "cpu"}
               for name, path in chip_smoke.EXPECT.items()}


@pytest.fixture(scope="module")
def out(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chip_smoke"))


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Put the kernels explicitly in interpret mode at the one place that
    decides (solvers/cocoa.resolve_solver_path): auto-selection picks the
    fori/XLA paths on a CPU backend, and there is no flag for this."""
    from cocoa_tpu.solvers import cocoa as cocoa_mod

    auto = cocoa_mod.resolve_solver_path

    def forced(*args, **kw):
        if kw.get("block_size", 0) > 0:
            kw["block_chain"] = "pallas_interpret"
        else:
            kw["pallas"] = True
        return auto(*args, **kw)

    monkeypatch.setattr(cocoa_mod, "resolve_solver_path", forced)


def test_plain_invocation_refuses_without_a_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         f"--out={tmp_path}"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    # no result: nothing on stdout can be read as a phase or a verdict
    assert proc.stdout.strip() == ""


def test_failed_phase_fails_the_script(tmp_path, capfd):
    """A gap target the demo cannot reach in its round budget."""
    sizes = {**chip_smoke.TINY,
             "demo": {**chip_smoke.TINY["demo"], "rounds": 20, "gap": 1e-9}}
    rc = chip_smoke.run(str(tmp_path), sizes, ["demo"], rehearse=True)
    captured = capfd.readouterr()
    assert rc != 0
    assert "did not certify" in captured.err
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last == {"ok": False, "failed": ["demo"]}


def test_demo_phase_and_auto_selected_path_on_cpu(out):
    """Phase 1 holds at its real size, and an auto-selected CPU run says
    fori — not pallas — in ``run_start``."""
    rep = chip_smoke.phase_demo(chip_smoke.TINY["demo"], out, expect=None)
    assert rep["solver_path"]["inner"] == "sequential"
    assert rep["solver_path"]["kernel"] == "fori"
    assert rep["solver_path"]["interpret"] is False
    assert rep["solver_path"]["platform"] == "cpu"
    assert rep["solver_path"]["rows"] == "device_default"   # no fold cache
    assert rep["solver_path"]["step_solve"] == "scalar"
    assert rep["stopped"] == "target" and rep["gap"] <= 1e-4
    assert rep["checkpoint"].startswith("CoCoA+-r")
    # the same record rides the events file a user would read
    events = chip_smoke.read_events(os.path.join(out, "demo.events.jsonl"))
    (start,) = [e for e in events if e["event"] == "run_start"]
    assert start["manifest"]["solver_path"] == rep["solver_path"]
    # a phase that asserts the compiled kernel fails on this backend
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel='fori'"):
        chip_smoke.check_path(rep["solver_path"], chip_smoke.EXPECT, "demo",
                              chip_smoke.TINY["demo"]["k"])


def test_serve_phase_audits_margins(out):
    """Phase 4 on the checkpoint the demo test left (a JAX-free client
    against a real ``--serve`` child)."""
    ck = os.path.join(out, "demo_ck")
    if not os.path.isdir(ck):
        pytest.skip("needs the demo phase's checkpoint")
    runner = chip_smoke.Runner(out, rehearse=True)
    try:
        rep = chip_smoke.phase_serve(runner, chip_smoke.TINY["serve"], ck)
    finally:
        runner.stop()
    assert rep["queries"] == 64 and rep["server_exit"] == 0
    assert rep["platform"] == "cpu"


def test_block_path_reads_xla_on_cpu(tiny_data):
    import jax.numpy as jnp

    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    ds = shard_dataset(tiny_data, k=2, layout="dense", dtype=jnp.float32)
    path = resolve_solver_path(ds, 8, math="fast", block_size=128)
    assert (path.inner, path.kernel, path.chain) == ("block", "xla", "xla")
    assert not path.interpret and not path.pallas


@pytest.mark.parametrize("layout, d, pallas, rows", [
    ("dense", 1024, True, "row_major"),         # no lane padding
    ("dense", 160000, True, "row_major"),       # imagenet: 0.48%
    ("dense", 2000, True, "device_default"),    # epsilon: 2.4% > 1%
    ("dense", 1024, False, "device_default"),   # fori: no fold cache
    ("sparse", 1024, True, "device_default"),
])
def test_solver_path_says_how_the_rows_are_stored(layout, d, pallas, rows):
    """``rows`` rides every surface ``solver_path`` has: decided where the
    path is (resolve_solver_path — what run_start's manifest and
    Trajectory.meta carry) from the kernel and the row width alone, and
    said on the console line when the fold cache is stored row-major."""
    import dataclasses
    import types

    import jax.numpy as jnp

    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    labels = jnp.zeros((2, 128), jnp.float32)
    ds = types.SimpleNamespace(
        k=2, labels=labels, layout=layout, n_hot=0, n_shard=128,
        num_features=d, sp_indices=jnp.zeros((2, 128, 4), jnp.int32),
        sp_row_ptr=None)
    path = resolve_solver_path(ds, 8, math="fast", pallas=pallas)
    assert path.rows == rows
    assert list(path.as_dict()) == [
        "inner", "kernel", "chain", "interpret", "layout", "platform",
        "devices", "shards_per_device", "rows", "state", "step_solve",
        "pass_slot_share", "storage", "margin", "slot_fill", "longest_row",
        "chunk_pieces", "chunk_fill", "refused", "objective", "form",
        "classes", "lane_fill", "row_fetch", "ring_depth", "row_align",
        "local_ids", "segments", "table_width", "slots_walked",
        "slot_walk", "class_axis", "class_state", "class_tiles",
        "label_slots", "ids_per_segment", "plan"]
    # no stream, no ring of chunks
    assert (path.chunk_pieces, path.chunk_fill) == (None, None)
    # the dense Pallas kernel's rows come by its own ring, as deep as fits
    # (rounds of 8 steps: the shallowest); nothing else has a row fetch
    assert (path.row_fetch, path.ring_depth) == (
        ("ring", 2) if pallas and layout == "dense" else (None, None))
    assert ("rows by a ring 2 steps deep" in path.describe()) == (
        pallas and layout == "dense")
    # a binary set: one model, no class axis
    assert (path.classes, path.lane_fill) == (1, None)
    # the dual family; which dense kernel runs, on the dense Pallas path
    assert path.objective == "svm"
    assert path.form == ("interleaved" if pallas and layout == "dense"
                         else None)
    # rows padded to the longest, their lengths not known here
    assert (path.storage, path.slot_fill, path.refused) == (
        "rectangle", None, "")
    # no stream: no chain whose margin has a form, whatever the algorithm
    assert path.margin is None and path.for_mode("plus") == path
    assert path.longest_row == (4 if layout == "sparse" else 0)
    # one block holds these shards: an all-rows pass touches every slot
    assert path.pass_slot_share == 1.0
    assert "all-rows passes touch" not in path.describe()
    # where w, dw and alpha live during the solve: on the chip for the
    # resident Pallas kernels at these sizes, in HBM on the fori path
    assert path.state == ("vmem" if pallas else "hbm")
    assert ("rows stored row-major" in path.describe()) == (
        rows == "row_major")
    # hinge's step is a closed form: on the dense Pallas kernel solved
    # chain by chain on (1, 1) vectors, no value of it a scalar (PR 39);
    # on the coordinate's own scalars in the fori and the sparse kernels
    dense_pallas = bool(pallas and layout == "dense")
    assert path.step_solve == ("vector" if dense_pallas else "scalar")
    assert "solved in lanes" not in path.describe()
    assert ("each step solved on the vector unit" in path.describe()) == (
        dense_pallas)
    for closed_form in ("smooth_hinge", "lasso"):
        assert resolve_solver_path(
            ds, 8, math="fast", pallas=pallas,
            loss=closed_form).step_solve == path.step_solve
    # logistic's iterates, and only the dense Pallas kernel solves its K
    # lockstep shards as one vector; the fori and the sparse kernels do not
    lanes = resolve_solver_path(ds, 8, math="fast", pallas=pallas,
                                loss="logistic")
    assert lanes.step_solve == ("lanes" if dense_pallas else "scalar")
    assert ("solved in lanes" in lanes.describe()) == (
        lanes.step_solve == "lanes")
    assert "on the vector unit" not in lanes.describe()
    assert dataclasses.replace(lanes, step_solve=path.step_solve) == path


def test_logistic_run_says_its_steps_are_solved_in_lanes(
        out, interpret_kernels, capfd):
    """A run whose K shards' Newton steps are solved as one vector says so
    on every surface a run has: the console line, ``run_start``'s manifest
    (the CLI's own resolver call) and ``Trajectory.meta`` (the driver's)."""
    import jax.numpy as jnp
    import numpy as np

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.synth import synth_dense_sharded
    from cocoa_tpu.solvers import run_cocoa

    rng = np.random.default_rng(0)
    train = os.path.join(out, "lanes_train.dat")
    with open(train, "w") as f:
        for _ in range(96):
            row = " ".join(f"{j}:{v:.4f}" for j, v in
                           enumerate(rng.normal(size=16), start=1))
            f.write(f"{rng.choice((-1, 1)):+d} {row}\n")
    argv = [f"--trainFile={train}", "--numFeatures=16", "--numRounds=2",
            "--localIterFrac=0.1", "--numSplits=3", "--lambda=0.01",
            "--justCoCoA=true", "--math=fast", "--layout=dense",
            "--loss=logistic", "--debugIter=2"]
    _, events = chip_smoke.run_cli(argv, os.path.join(out, "lanes.jsonl"))
    (start,) = [e for e in events if e["event"] == "run_start"]
    path = start["manifest"]["solver_path"]
    assert (path["kernel"], path["layout"], path["step_solve"]) == (
        "pallas", "dense", "lanes")
    assert "the shards' steps solved in lanes" in capfd.readouterr().out

    ds = synth_dense_sharded(96, 16, 3, seed=0, dtype=jnp.float32)
    _, _, traj = run_cocoa(
        ds, Params(n=ds.n, num_rounds=2, local_iters=4, lam=1e-2,
                   loss="logistic"),
        DebugParams(debug_iter=2, seed=0), plus=True, quiet=True,
        math="fast")
    assert traj.meta["solver_path"]["step_solve"] == "lanes"


def test_run_says_what_share_of_the_slots_its_passes_touch(out, capfd,
                                                          monkeypatch):
    """A sparse run whose all-rows passes run in row blocks and stop at the
    rows' lengths says what share of the padded slots they touch, on every
    surface ``step_solve`` is on: the console line, ``run_start``'s
    manifest (the CLI's own resolver call, on the dataset ``shard_dataset``
    ordered at ingest) and ``Trajectory.meta`` (the driver's)."""
    import jax.numpy as jnp
    import numpy as np

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import load_libsvm, shard_dataset
    from cocoa_tpu.ops import rows
    from cocoa_tpu.solvers import run_cocoa

    rng = np.random.default_rng(0)
    train = os.path.join(out, "lengths_train.dat")
    with open(train, "w") as f:
        for _ in range(900):
            cols = np.sort(rng.choice(64, rng.integers(1, 17),
                                      replace=False)) + 1
            f.write(f"{rng.choice((-1, 1)):+d} "
                    + " ".join(f"{j}:{rng.normal():.4f}" for j in cols)
                    + "\n")
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", 16 * 128)
    argv = [f"--trainFile={train}", "--numFeatures=64", "--numRounds=2",
            "--localIterFrac=0.1", "--numSplits=3", "--lambda=0.01",
            "--justCoCoA=true", "--math=fast", "--layout=sparse",
            "--debugIter=2"]
    _, events = chip_smoke.run_cli(argv, os.path.join(out, "lengths.jsonl"))
    (start,) = [e for e in events if e["event"] == "run_start"]
    share = start["manifest"]["solver_path"]["pass_slot_share"]
    ds = shard_dataset(load_libsvm(train, 64), k=3, layout="sparse",
                       dtype=jnp.float32)
    assert ds.row_order is not None
    lens = np.asarray(ds._row_len_cache)
    width = ds.sp_indices.shape[-1]
    # on the CLI's mesh every shard has a device, and a loop, of its own
    assert share == rows.pass_slots(lens, width, together=1) / (
        lens.size * width)
    assert 0.3 < share < 1.0
    assert (f"all-rows passes touch {share:.3f} of the padded slots"
            in capfd.readouterr().out)
    _, _, traj = run_cocoa(
        ds, Params(n=ds.n, num_rounds=2, local_iters=4, lam=1e-2),
        DebugParams(debug_iter=2, seed=0), plus=True, quiet=True,
        math="fast")
    # on one device the K shards share a loop: as far as the longest
    assert traj.meta["solver_path"]["pass_slot_share"] == rows.pass_slots(
        lens, width) / (lens.size * width) >= share


def test_rcv1_phases_with_interpreted_kernels(out, interpret_kernels,
                                              monkeypatch):
    cfg = chip_smoke.TINY["rcv1"]
    rep = chip_smoke.phase_rcv1_seq(cfg, out, INTERPRETED)
    assert rep["stopped"] == "target" and rep["parser"] in ("native",
                                                            "python")
    assert rep["solver_path"]["step_solve"] == "scalar"     # sparse, hinge
    rep = chip_smoke.phase_rcv1_hybrid(cfg, out, INTERPRETED)
    assert rep["solver_path"]["layout"] == "hybrid"
    # at this width the fused kernel would hold the densified tile; the
    # phase exists for the CSR Gram kernels, so shut the fused door the
    # way rcv1's real width does
    from cocoa_tpu.ops import pallas_chain

    monkeypatch.setattr(pallas_chain, "FUSED_VMEM_BUDGET", 0)
    rep = chip_smoke.phase_rcv1_block(cfg, out, INTERPRETED)
    assert rep["solver_path"]["kernel"] == "sparse_gram"
    assert rep["w_err_vs_f64"] <= 1e-4 * rep["w_scale"]


def test_epsilon_phase_with_interpreted_kernels(out, interpret_kernels):
    rep = chip_smoke.phase_epsilon(chip_smoke.TINY["epsilon"], out,
                                   INTERPRETED)
    for name in ("seq", "block"):
        assert rep[name]["stopped"] == "target"
        assert rep[name]["alpha_devices"] == rep["data_devices"]
    # hinge on the dense Pallas kernel: no value of a step is a scalar; the
    # block kernels still solve a coordinate on its own scalars
    assert rep["seq"]["solver_path"]["step_solve"] == "vector"
    assert rep["block"]["solver_path"]["step_solve"] == "scalar"
    assert rep["block"]["solver_path"]["kernel"] == "fused"


def test_compile_cache_is_placeable_from_outside(monkeypatch):
    import jax

    from cocoa_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(name))
    monkeypatch.delenv("COCOA_NO_COMPILE_CACHE", raising=False)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert "jax_compilation_cache_dir" not in updates

    del updates[:]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")
    assert updates.count("jax_compilation_cache_dir") == 1
