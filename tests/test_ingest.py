"""Streaming sharded ingest (data/ingest.py, docs/DESIGN.md §12).

The contract under test: the two-pass byte-range pipeline — index scan +
shard-range parse — builds a ``ShardedDataset`` BIT-IDENTICAL to the
whole-file replicated builder for the same file/config, across layouts,
the hybrid hot/cold split, the dense eval twin, and multiplexed dp
meshes; and a streamed multiplexed 2-process run trains the identical
(w, α) trajectory as the single-process replicated control (the
acceptance pin for ISSUE 8, via the tests/_multihost_data.py pattern).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from conftest import SMALL_TRAIN, DEMO_NUM_FEATURES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


def _assert_ds_equal(ds_a, ds_b):
    """Bit-exact ShardedDataset equality: metadata + every shard array."""
    assert ds_a.layout == ds_b.layout
    assert ds_a.n == ds_b.n
    assert ds_a.num_features == ds_b.num_features
    np.testing.assert_array_equal(ds_a.counts, ds_b.counts)
    arrs_a, arrs_b = ds_a.shard_arrays(), ds_b.shard_arrays()
    assert arrs_a.keys() == arrs_b.keys()
    for f in arrs_a:
        a, b = np.asarray(arrs_a[f]), np.asarray(arrs_b[f])
        assert a.dtype == b.dtype, f
        assert a.shape == b.shape, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def test_build_index_matches_whole_parse():
    from cocoa_tpu.data import build_index, load_libsvm

    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    index = build_index(SMALL_TRAIN, d)
    assert index.n == data.n
    assert index.total_nnz == int(data.indptr[-1])
    np.testing.assert_array_equal(index.row_nnz, np.diff(data.indptr))
    np.testing.assert_array_equal(
        index.hist, np.bincount(data.indices, minlength=d))
    # row_off is a strictly increasing line-start index ending at EOF
    assert index.row_off[0] == 0
    assert index.row_off[-1] == os.path.getsize(SMALL_TRAIN)
    assert (np.diff(index.row_off) > 0).all()


def test_build_index_window_size_invariant():
    """The pass-1 window is a memory bound, not a semantic knob: a tiny
    window that forces many range parses assembles the identical index."""
    from cocoa_tpu.data import build_index

    d = DEMO_NUM_FEATURES
    ref = build_index(SMALL_TRAIN, d)
    tiny = build_index(SMALL_TRAIN, d, window=10_000)
    np.testing.assert_array_equal(tiny.row_off, ref.row_off)
    np.testing.assert_array_equal(tiny.row_nnz, ref.row_nnz)
    np.testing.assert_array_equal(tiny.hist, ref.hist)


@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("k", [2, 4])
def test_stream_equals_whole(layout, k):
    import jax.numpy as jnp

    from cocoa_tpu.data import load_libsvm, shard_dataset, stream_shard_dataset

    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    ds_whole = shard_dataset(data, k=k, layout=layout, dtype=jnp.float32)
    ds_stream, info = stream_shard_dataset(
        SMALL_TRAIN, d, k, layout=layout, dtype=jnp.float32)
    _assert_ds_equal(ds_whole, ds_stream)
    # single-process pass 2 parses every row exactly once
    assert info.rows == data.n
    assert info.nnz == int(data.indptr[-1])
    assert info.bytes_read == os.path.getsize(SMALL_TRAIN)


def test_stream_equals_whole_hybrid_and_eval_twin():
    import jax.numpy as jnp

    from cocoa_tpu.data import load_libsvm, shard_dataset, stream_shard_dataset

    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    ds_whole = shard_dataset(data, k=2, layout="sparse", dtype=jnp.float32,
                             hot_cols=64, eval_dense=True)
    ds_stream, info = stream_shard_dataset(
        SMALL_TRAIN, d, 2, layout="sparse", dtype=jnp.float32,
        hot_cols=64, eval_dense=True)
    _assert_ds_equal(ds_whole, ds_stream)
    # the residual width is the measured global max cold nnz
    assert info.residual_max_nnz == ds_whole.sp_indices.shape[-1]


def test_stream_equals_whole_multiplexed_mesh():
    """Single-process multiplexed dp mesh (D=2 devices < K=4 shards):
    streamed build places exactly like the replicated builder."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.data import load_libsvm, shard_dataset, stream_shard_dataset
    from cocoa_tpu.parallel import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU backend")
    mesh = make_mesh(2)
    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    for layout in ("dense", "sparse"):
        ds_whole = shard_dataset(data, k=4, layout=layout,
                                 dtype=jnp.float32, mesh=mesh)
        ds_stream, _ = stream_shard_dataset(
            SMALL_TRAIN, d, 4, layout=layout, dtype=jnp.float32, mesh=mesh)
        _assert_ds_equal(ds_whole, ds_stream)


def test_stream_hot_width_resolution_matches_whole():
    """--hotCols resolution parity: the width/ids resolved from the pass-1
    histogram equal the whole-file resolution (same counts, same
    tie-breaks), for auto and explicit specs."""
    import jax.numpy as jnp

    from cocoa_tpu.data import load_libsvm
    from cocoa_tpu.data import hybrid as hybrid_lib
    from cocoa_tpu.data.ingest import build_index

    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    index = build_index(SMALL_TRAIN, d)
    k, dtype = 4, jnp.float32
    for spec in ("auto", "128", "64"):
        n_whole, _ = hybrid_lib.resolve_hot_cols(spec, data, k, dtype)
        n_stream = hybrid_lib.resolve_hot_width(spec, index.hist, data.n,
                                                k, dtype)
        assert n_whole == n_stream, spec
        if n_whole:
            np.testing.assert_array_equal(
                hybrid_lib.hottest_columns(index.hist, n_whole),
                hybrid_lib.hottest_columns(hybrid_lib.column_counts(data),
                                           n_whole))


def test_resolve_layout_stats_matches_data_resolution():
    from cocoa_tpu.data import load_libsvm
    from cocoa_tpu.data.sharding import resolve_layout, resolve_layout_stats

    d = DEMO_NUM_FEATURES
    data = load_libsvm(SMALL_TRAIN, d)
    for layout in ("auto", "dense", "sparse"):
        assert resolve_layout_stats(
            data.n, d, int(data.indptr[-1]), layout, None
        ) == resolve_layout(data, layout, None)


def test_resolve_ingest_mode():
    import jax

    from cocoa_tpu.data.ingest import resolve_ingest_mode
    from cocoa_tpu.parallel import make_mesh

    # single-process auto keeps the whole-file A/B control
    assert resolve_ingest_mode(None, None) == "whole"
    assert resolve_ingest_mode("auto", None) == "whole"
    assert resolve_ingest_mode("whole", None) == "whole"
    # --ingestCache armed: auto routes through the shard-granular
    # pipeline (what consults/populates the cache), explicit whole wins
    assert resolve_ingest_mode("auto", None, cached=True) == "stream"
    assert resolve_ingest_mode(None, None, cached=True) == "stream"
    assert resolve_ingest_mode("whole", None, cached=True) == "whole"
    assert resolve_ingest_mode("auto", None, objective="lasso",
                               cached=True) == "whole"
    # explicit stream is honored wherever it is legal
    assert resolve_ingest_mode("stream", None) == "stream"
    if len(jax.devices()) >= 2:
        assert resolve_ingest_mode("stream", make_mesh(2)) == "stream"
    with pytest.raises(ValueError, match="lasso"):
        resolve_ingest_mode("stream", None, objective="lasso")
    with pytest.raises(ValueError, match="ingest must be"):
        resolve_ingest_mode("shard", None)


def test_resolve_ingest_mode_rejects_fp_mesh():
    """fp meshes have no per-device byte range; stream must reject them
    loudly (auto falls back to whole)."""
    import jax
    from jax.sharding import Mesh

    from cocoa_tpu.data.ingest import resolve_ingest_mode

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    fp_mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                   ("dp", "fp"))
    with pytest.raises(ValueError, match="feature-parallel"):
        resolve_ingest_mode("stream", fp_mesh)
    assert resolve_ingest_mode("auto", fp_mesh) == "whole"
    # even with a cache armed, fp keeps whole (nothing shard-keyed)
    assert resolve_ingest_mode("auto", fp_mesh, cached=True) == "whole"


def test_stream_rejects_fp_mesh_and_bad_eval_dense(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from cocoa_tpu.data import stream_shard_dataset

    with pytest.raises(ValueError, match="eval_dense"):
        stream_shard_dataset(SMALL_TRAIN, DEMO_NUM_FEATURES, 2,
                             layout="dense", dtype=jnp.float32,
                             eval_dense=True)
    if len(jax.devices()) >= 4:
        fp_mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                       ("dp", "fp"))
        with pytest.raises(ValueError, match="feature-parallel"):
            stream_shard_dataset(SMALL_TRAIN, DEMO_NUM_FEATURES, 2,
                                 dtype=jnp.float32, mesh=fp_mesh)


def test_stream_detects_file_change(tmp_path):
    """A file rewritten between pass 1 and pass 2 must fail loudly, not
    train on silently skewed shards."""
    import jax.numpy as jnp

    from cocoa_tpu.data.ingest import build_index, stream_shard_dataset

    path = tmp_path / "mut.svm"
    path.write_text("1 1:1.0\n-1 2:2.0\n1 3:3.0\n-1 1:4.0\n")
    index = build_index(str(path), 10)
    path.write_text("1 1:1.0 2:2.0 3:3.0 4:4.0\n" * 4)
    with pytest.raises(ValueError, match="changed during ingest"):
        stream_shard_dataset(str(path), 10, 2, layout="sparse",
                             dtype=jnp.float32, index=index)


# --- the acceptance pin: 2-process streamed multiplexed ≡ replicated ------
#
# Two halves (written when the CPU backend could not run jit computations
# over a multi-process mesh; moving the real 2-process run into the fast
# sweep is ROADMAP D2):
#
# 1. REAL 2-process build (subprocess workers over jax.distributed/Gloo,
#    one device each, K=4 multiplexing m=2 per device): every worker
#    streams ONLY its own shards' byte ranges and the assembled global
#    dataset's shard arrays are bit-identical to the single-process
#    replicated control — hybrid split on and off.
# 2. The (w, α) TRAJECTORY pin runs on the simulated multi-host backend
#    (the virtual multi-device CPU mesh, same shard_map/psum code path
#    as a real pod): the streamed multiplexed build trains bit-identically
#    to the whole-file build on the same mesh, and matches the replicated
#    no-mesh control at the f64 reduction-order tolerance the repo's
#    multiplexing suite pins (tests/test_multiplex.py).
#
# Together: streamed build ≡ control build (bit-exact, real processes) and
# control-equal builds train identically — the end-to-end 2-process run is
# CI's streamed-multiplexed smoke once the backend supports it.

_WORKER = r"""
import json, os, sys
proc_id, nproc, port, path, outdir = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    sys.argv[5])
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from cocoa_tpu.parallel.distributed import maybe_initialize
assert maybe_initialize(f"127.0.0.1:{port}", process_id=proc_id,
                        num_processes=nproc)

import jax.numpy as jnp
import numpy as np
from _multihost_data import D
from cocoa_tpu.data.ingest import build_index, stream_shard_dataset
from cocoa_tpu.parallel import make_mesh

assert len(jax.devices()) == nproc  # one CPU device per process
mesh = make_mesh(nproc)
K = 4  # m = K/D = 2 logical shards multiplex per device

index = build_index(path, D)
out = {}
for tag, hot in (("plain", 0), ("hybrid", 8)):
    ds, info = stream_shard_dataset(
        path, D, K, layout="sparse", dtype=jnp.float64, mesh=mesh,
        hot_cols=hot, index=index)
    # pass 2 parsed ONLY this process's rows — the streaming guarantee
    assert info.rows < index.n, (tag, info.rows, index.n)
    out[f"{tag}|rows"] = np.asarray([info.rows])
    for field, arr in ds.shard_arrays().items():
        for s in arr.addressable_shards:
            lo = s.index[0].start or 0
            out[f"{tag}|{field}|{lo}"] = np.asarray(s.data)
np.savez(os.path.join(outdir, f"worker{proc_id}.npz"), **out)
print("WORKER_DONE", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_streamed_multiplexed_build_matches_control(tmp_path):
    from _multihost_data import write_libsvm

    data = write_libsvm(tmp_path / "mh.svm")
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{TESTS}"}
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port),
             str(tmp_path / "mh.svm"), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=ROOT, text=True,
        )
        for i in range(2)
    ]
    try:
        for p in procs:
            out, _ = p.communicate(timeout=220)
            assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
            assert "WORKER_DONE" in out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = [dict(np.load(tmp_path / f"worker{i}.npz")) for i in (0, 1)]

    # each process streamed a strict subset; together they tile the file
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import shard_dataset

    for tag, hot in (("plain", 0), ("hybrid", 8)):
        rows = [int(res[f"{tag}|rows"][0]) for res in results]
        assert all(r < data.n for r in rows)
        assert sum(rows) == data.n

        # the 2-process assembled shard arrays tile the control's exactly
        ds = shard_dataset(data, k=4, layout="sparse", dtype=jnp.float64,
                           hot_cols=hot)
        for field, ctrl in ds.shard_arrays().items():
            ctrl = np.asarray(ctrl)
            seen = 0
            for res in results:
                for key, val in res.items():
                    if key.startswith(f"{tag}|{field}|"):
                        lo = int(key.rsplit("|", 1)[1])
                        assert val.dtype == ctrl.dtype, (tag, field)
                        np.testing.assert_array_equal(
                            val, ctrl[lo:lo + val.shape[0]],
                            err_msg=f"{tag}: {field}[{lo}]")
                        seen += val.shape[0]
            assert seen == 4, (tag, field)  # every shard exactly once


@pytest.mark.slow
def test_streamed_multiplexed_trajectory_matches_replicated_control(
        tmp_path):
    """The (w, α) pin on the simulated multi-host backend: streamed
    multiplexed (D=2 virtual devices < K=4 shards) trains BIT-IDENTICALLY
    to the whole-file build on the same mesh — and both match the
    replicated no-mesh control at the f64 reduction-order tolerance the
    multiplexing suite pins (the psum tree differs between topologies,
    tests/test_multiplex.py)."""
    import jax
    import jax.numpy as jnp

    from _multihost_data import D, write_libsvm
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.ingest import stream_shard_dataset
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.parallel import make_mesh
    from cocoa_tpu.solvers import run_cocoa

    data = write_libsvm(tmp_path / "mh.svm")
    params = Params(n=data.n, num_rounds=5, local_iters=10, lam=0.01)
    mesh = make_mesh(2) if len(jax.devices()) >= 2 else None

    def train(ds, mesh):
        w, alpha, traj = run_cocoa(ds, params,
                                   DebugParams(debug_iter=1, seed=0),
                                   plus=True, mesh=mesh, quiet=True)
        return (np.asarray(w), np.asarray(alpha),
                np.asarray([r.gap for r in traj.records]))

    for hot in (0, 8):
        ctrl = train(shard_dataset(data, k=4, layout="sparse",
                                   dtype=jnp.float64, hot_cols=hot), None)

        # streamed replicated build (no mesh): bit-identical to the
        # whole-file control — same arrays in, same vmap path
        ds_flat, _ = stream_shard_dataset(
            str(tmp_path / "mh.svm"), D, 4, layout="sparse",
            dtype=jnp.float64, hot_cols=hot)
        flat = train(ds_flat, None)
        for g, x, name in zip(flat, ctrl, ("w", "alpha", "gaps")):
            np.testing.assert_array_equal(g, x,
                                          err_msg=f"hot={hot}: {name}")

        if mesh is None:
            continue
        ds_stream, _ = stream_shard_dataset(
            str(tmp_path / "mh.svm"), D, 4, layout="sparse",
            dtype=jnp.float64, mesh=mesh, hot_cols=hot)
        ds_whole = shard_dataset(data, k=4, layout="sparse",
                                 dtype=jnp.float64, mesh=mesh,
                                 hot_cols=hot)
        got = train(ds_stream, mesh)
        want = train(ds_whole, mesh)
        for g, x, name in zip(got, want, ("w", "alpha", "gaps")):
            np.testing.assert_array_equal(g, x,
                                          err_msg=f"hot={hot}: {name}")
        for g, x, name in zip(got, ctrl, ("w", "alpha", "gaps")):
            np.testing.assert_allclose(g, x, atol=1e-12,
                                       err_msg=f"hot={hot}: {name}")
