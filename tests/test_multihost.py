"""Multi-host (multi-process) runtime: the reference validates multi-worker
behavior with local-mode Spark (SURVEY.md §4); the multi-PROCESS analogue
here is two actual OS processes joined through
``parallel/distributed.maybe_initialize`` (the ``--master=host:port`` path),
forming a 2-device global CPU mesh whose psum rides the cross-process
collective backend (Gloo).  The trained w must be identical on every
process AND identical to a single-process run of the same problem — the
multi-host path is the same shard_map/psum code, only the device set
changes.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))

_WORKER = r"""
import json, os, sys
proc_id, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from cocoa_tpu.parallel.distributed import maybe_initialize
assert maybe_initialize(f"127.0.0.1:{port}", process_id=proc_id,
                        num_processes=nproc)

import jax.numpy as jnp
import numpy as np
from _multihost_data import build_data
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_cocoa

data = build_data()
assert len(jax.devices()) == nproc  # one CPU device per process
mesh = make_mesh(nproc)
ds = shard_dataset(data, k=nproc, layout="dense", dtype=jnp.float64,
                   mesh=mesh)
params = Params(n=data.n, num_rounds=5, local_iters=10, lam=0.01)
w, alpha, traj = run_cocoa(ds, params, DebugParams(debug_iter=5, seed=0),
                           plus=True, mesh=mesh, quiet=True)
print("RESULT " + json.dumps({
    "w": np.asarray(w).tolist(),
    "gap": float(traj.records[-1].gap),
}), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_run_matches_single_process(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{TESTS}"}
    # workers must not inherit the virtual 8-device flag (1 device each)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=ROOT, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=220)
            assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
            outs.append(out)
    finally:
        # a hung rendezvous must not orphan the sibling worker (it would
        # pin the Gloo port and poison later runs)
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = []
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in:\n{out[-2000:]}"
        results.append(json.loads(lines[-1][len("RESULT "):]))

    # identical across processes (replicated w is the same global value)
    np.testing.assert_array_equal(results[0]["w"], results[1]["w"])

    # and identical to a single-process run of the same problem
    import jax.numpy as jnp

    from _multihost_data import build_data
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.solvers import run_cocoa

    data = build_data()
    ds = shard_dataset(data, k=2, layout="dense", dtype=jnp.float64)
    params = Params(n=data.n, num_rounds=5, local_iters=10, lam=0.01)
    w, _, traj = run_cocoa(ds, params, DebugParams(debug_iter=5, seed=0),
                           plus=True, quiet=True)
    np.testing.assert_allclose(results[0]["w"], np.asarray(w), atol=1e-12)
    assert abs(results[0]["gap"] - traj.records[-1].gap) < 1e-12


_MEM_WORKER = r"""
import json, os, sys
proc_id, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")

from cocoa_tpu.parallel.distributed import maybe_initialize
assert maybe_initialize(f"127.0.0.1:{port}", process_id=proc_id,
                        num_processes=nproc)

import jax.numpy as jnp
import numpy as np
from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.parallel import make_mesh

# dense n x d, ~128 MB f64 full matrix; each process must only ever hold
# its own ~1/2 shard (host slab + its device buffer)
n, d = 4000, 4000
rng = np.random.default_rng(0)
X = (rng.random((n, d)) < 0.05) * 1.0   # sparse-ish values, dense layout
y = np.where(rng.random(n) > 0.5, 1.0, -1.0)
nz_rows = [np.nonzero(X[i])[0] for i in range(n)]
indptr = np.concatenate([[0], np.cumsum([len(r) for r in nz_rows])])
data = LibsvmData(labels=y, indptr=indptr.astype(np.int64),
                  indices=np.concatenate(nz_rows).astype(np.int32),
                  values=np.concatenate([X[i, r] for i, r in enumerate(nz_rows)]),
                  num_features=d)
del X, nz_rows

def rss():
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")

mesh = make_mesh(nproc)
before = rss()
ds = shard_dataset(data, k=nproc, layout="dense", dtype=jnp.float64, mesh=mesh)
jax.block_until_ready(ds.X)
delta = rss() - before
full = n * d * 8
# one addressable piece per process, and memory well under the full matrix
assert len(ds.X.addressable_shards) == 1
print("RESULT " + json.dumps({"delta": delta, "full": full,
                              "frac": delta / full}), flush=True)
"""


@pytest.mark.slow
def test_elastic_supervisor_recovers_from_sigkill(tmp_path, monkeypatch):
    """VERDICT r3 item 7 (coverage row 23): the --elastic supervisor is the
    all-reduce-runtime analogue of Spark's implicit lineage recovery — a
    SIGKILLed worker brings the gang down, the supervisor relaunches it
    with --resume, and the run completes to the final round with the same
    state an uninterrupted run reaches (resume exactness is pinned by
    tests/test_crash_resume.py; this test pins the supervision mechanics:
    detection, gang teardown, restart, completion)."""
    import signal
    import threading
    import time as _time

    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu import elastic
    from cocoa_tpu.data.synth import synth_sparse, write_libsvm

    data = synth_sparse(96, 64, nnz_mean=8, seed=2)
    train = tmp_path / "train.dat"
    write_libsvm(data, str(train))
    ckdir = tmp_path / "ck"
    rounds = 300
    argv = [
        f"--trainFile={train}", "--numFeatures=64", f"--numRounds={rounds}",
        "--localIterFrac=0.2", "--numSplits=2", "--lambda=.01",
        "--justCoCoA=true", "--debugIter=10", f"--chkptDir={ckdir}",
        "--chkptIter=10", "--dtype=float64",
    ]
    # each worker gets ONE cpu device (2-device global mesh over Gloo)
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ))

    gens = []

    def on_generation(gen, procs):
        gens.append(gen)
        if gen == 0:
            def killer():
                # wait for the run to be demonstrably mid-flight (a first
                # checkpoint exists), then SIGKILL one worker
                for _ in range(600):
                    if ckpt_lib.latest(str(ckdir), "CoCoA+"):
                        break
                    _time.sleep(0.25)
                if procs[1].poll() is None:
                    procs[1].send_signal(signal.SIGKILL)
            threading.Thread(target=killer, daemon=True).start()

    rc = elastic.supervise(argv, 2, max_restarts=3,
                           on_generation=on_generation, quiet_tail=True)
    assert rc == 0
    assert len(gens) >= 2, "the gang was never restarted"
    # the second CoCoA+ pass (justCoCoA runs CoCoA+ then CoCoA) finished:
    # a final-round checkpoint exists for both algorithms
    for alg in ("CoCoA+", "CoCoA"):
        path = ckpt_lib.latest(str(ckdir), alg)
        assert path is not None
        meta, w, a = ckpt_lib.load(path)
        assert meta["round"] == rounds
        assert w.shape == (64,) and a is not None


@pytest.mark.slow
def test_two_process_loading_materializes_only_local_shard(tmp_path):
    """VERDICT r1 item 5: per-process memory stays ~1/K of the dense
    matrix — each process builds only its own shard's host slab and device
    buffer (data/sharding._shard_dataset_distributed), never the full
    (K, n_shard, d) array."""
    worker = tmp_path / "memworker.py"
    worker.write_text(_MEM_WORKER)
    port = _free_port()
    env = {**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{TESTS}"}
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=ROOT, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=220)
            assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in:\n{out[-2000:]}"
        r = json.loads(lines[-1][len("RESULT "):])
        # own shard host slab (1/2) + its device buffer (1/2) + slack —
        # the old replicated path cost >= 2x full (numpy (K,·,d) + buffers)
        assert r["frac"] < 1.35, r


_WEDGE_WORKER = r"""
import os, sys, time

# Fault injection for the stall-watchdog wedge test: on the FIRST
# generation only (marker file absent), worker 1 lets two checkpoints land
# and then WEDGES inside checkpoint.save — it stops checkpointing but
# stays alive, and worker 0 blocks at the next collective.  No process
# dies, so death-only supervision would poll this gang forever.
marker = os.environ["WEDGE_MARKER"]
proc_id = [a for a in sys.argv[1:] if a.startswith("--processId=")]
proc_id = proc_id[0].split("=", 1)[1] if proc_id else "?"
if proc_id == "1" and not os.path.exists(marker):
    open(marker, "w").write("wedged")
    import cocoa_tpu.checkpoint as _ckpt
    _real_save = _ckpt.save
    _n = [0]
    def _wedging_save(*a, **k):
        _n[0] += 1
        if _n[0] > 2:
            time.sleep(3600)  # alive, silent, making no progress
        return _real_save(*a, **k)
    _ckpt.save = _wedging_save

from cocoa_tpu.cli import main
sys.exit(main(sys.argv[1:]))
"""


@pytest.mark.slow
def test_stall_watchdog_recovers_wedged_but_alive_gang(tmp_path, monkeypatch):
    """VERDICT r5 #6, end-to-end: one worker STOPS CHECKPOINTING but stays
    alive (wedged inside checkpoint.save), its peer blocks in the next
    collective — no death for death-only supervision to see.  The
    --stallTimeout watchdog kills the gang and restarts it from the last
    good checkpoint, and the run completes with the same final state an
    unwedged run reaches (resume exactness itself is pinned by
    tests/test_crash_resume.py; this pins the watchdog mechanics
    end-to-end: detection without a death, teardown, restart, completion).
    """
    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu import elastic
    from cocoa_tpu.data.synth import synth_sparse, write_libsvm

    data = synth_sparse(96, 64, nnz_mean=8, seed=2)
    train = tmp_path / "train.dat"
    write_libsvm(data, str(train))
    ckdir = tmp_path / "ck"
    marker = tmp_path / "wedged.marker"
    wedge_mod = tmp_path / "wedge_worker.py"
    wedge_mod.write_text(_WEDGE_WORKER)
    rounds = 200
    argv = [
        f"--trainFile={train}", "--numFeatures=64", f"--numRounds={rounds}",
        "--localIterFrac=0.2", "--numSplits=2", "--lambda=.01",
        "--justCoCoA=true", "--debugIter=10", f"--chkptDir={ckdir}",
        "--chkptIter=10", "--dtype=float64",
    ]
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ))
    monkeypatch.setenv("WEDGE_MARKER", str(marker))
    monkeypatch.setenv(
        "PYTHONPATH",
        f"{tmp_path}{os.pathsep}{os.environ.get('PYTHONPATH', '')}")

    def progress_token():
        # the cli.py supervisor's token: the checkpoint directory listing
        if not ckdir.is_dir():
            return None
        return tuple(sorted(f for f in os.listdir(ckdir)
                            if f.endswith(".npz")))

    gens = []
    rc = elastic.supervise(
        argv, 2, max_restarts=3, module="wedge_worker",
        on_generation=lambda gen, procs: gens.append(gen),
        progress_token=progress_token,
        # generous vs compile time, tiny vs the 3600 s wedge: the watchdog
        # is the ONLY thing that can unwedge this gang
        stall_timeout_s=90.0,
    )
    assert rc == 0
    assert marker.exists(), "the fault was never injected"
    assert len(gens) >= 2, "the wedged gang was never restarted"
    # the run completed: final-round checkpoints exist for both algorithms
    for alg in ("CoCoA+", "CoCoA"):
        path = ckpt_lib.latest(str(ckdir), alg)
        assert path is not None
        meta, w, a = ckpt_lib.load(path)
        assert meta["round"] == rounds
        assert w.shape == (64,) and a is not None
    # and bit-identically: an unwedged reference gang (same flags, same
    # 2-process layout) reaches exactly the same final checkpoint state —
    # round-keyed sampling makes restart-resume invisible to the math
    refdir = tmp_path / "ck_ref"
    ref_argv = [a if str(ckdir) not in a else f"--chkptDir={refdir}"
                for a in argv]
    marker.unlink()
    open(marker, "w").write("disarm")  # marker present -> no wedge
    rc_ref = elastic.supervise(
        ref_argv, 2, max_restarts=0, module="wedge_worker",
    )
    assert rc_ref == 0
    for alg in ("CoCoA+", "CoCoA"):
        _, w0, a0 = ckpt_lib.load(ckpt_lib.latest(str(ckdir), alg))
        _, w1, a1 = ckpt_lib.load(ckpt_lib.latest(str(refdir), alg))
        np.testing.assert_array_equal(w0, w1)
        np.testing.assert_array_equal(a0, a1)
