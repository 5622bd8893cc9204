"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the normal entry points once, at the published width of the
configurations BASELINE.json lists, on the TPU JAX finds, and checks what
comes out by the repo's own means (the duality-gap certificate, the
primal-dual correspondence against a host-f64 recomputation, a host-f64
margin audit for serving):

1. ``demo``     — the run-demo-tpu.sh flag set through the CLI on
                  data/small_train.dat: certified gap <= 1e-4, test error,
                  a validated checkpoint.
2. ``rcv1_*``   — an rcv1-width sparse file (generated from a seed, never
                  committed) through the CLI: the sequential sparse kernel
                  to the 1e-3 gap, then a few eval windows each of the
                  sparse block-chain kernels (--blockSize=128) and the
                  hybrid hot-panel branch (--hotCols=auto).
3. ``epsilon``  — 400,000 x 2,000 dense, generated on device, through the
                  library API (a LIBSVM text file of it would be ~10 GB):
                  the sequential dense kernel and the fused block kernel,
                  each to the certified 1e-4 gap.
4. ``serve``    — a solo ``--serve`` on phase 1's checkpoint answers 64
                  queries from a client with no JAX; every margin audited
                  against a host-f64 dot.

Every phase prints one JSON line (device, versions, the resolved
local-solver path, rounds, gap, cold seconds split into compile and the
rest — smoke timings, not benchmark numbers).  The last line of stdout is
``{"ok": true, "device": {...}}``; any failed phase, or no TPU, exits
non-zero.

The chip belongs to one process at a time: this parent never imports JAX
and runs each phase as a child, strictly one after another (in phase 4
the parent itself is the client).  ``--rehearse`` runs the same flow at
tiny shapes on whatever backend JAX finds — a plumbing rehearsal the
caller asks for explicitly, labelled as such, never a chip result.
``--phases a,b`` runs a subset (bring-up debugging).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1140            # the contract's 1200 s, minus room to clean up
PHASES = ("demo", "rcv1_seq", "rcv1_block", "rcv1_hybrid", "epsilon", "serve")

FULL = {
    "demo": dict(train="data/small_train.dat", test="data/small_test.dat",
                 d=9947, k=4, lam=1e-3, rounds=600, gap=1e-4),
    # the rcv1 shape (synth_sparse stands in for the LIBSVM file no round
    # could download); 350 rounds to 1e-3 on record
    "rcv1": dict(n=20242, d=47236, nnz_mean=75, k=8, lam=1e-4,
                 debug_iter=25, seq_rounds=600, seq_gap=1e-3,
                 window_rounds=50, block=128, hot="auto"),
    # the epsilon shape (chipbench/configs/epsilon.json); ~100 / ~20
    # rounds on record (reference / permuted sampling)
    "epsilon": dict(n=400_000, d=2000, k=8, lam=1e-3, rounds=400, gap=1e-4,
                    block=128),
    "serve": dict(d=9947, lines=8, per_line=8, nnz=24),
}
TINY = {
    "demo": FULL["demo"],
    "rcv1": dict(n=512, d=2048, nnz_mean=20, k=2, lam=1e-3,
                 debug_iter=10, seq_rounds=300, seq_gap=1e-2,
                 window_rounds=20, block=128, hot="128"),
    "epsilon": dict(n=256, d=128, k=2, lam=1e-2, rounds=300, gap=1e-3,
                    block=128),
    "serve": FULL["serve"],
}
# the path each phase exists to exercise: the Pallas kernel, compiled by
# Mosaic (not interpreted), on the chip
_ON_CHIP = dict(interpret=False, platform="tpu")
EXPECT = {
    "demo": dict(inner="sequential", kernel="pallas", layout="sparse",
                 **_ON_CHIP),
    "rcv1_seq": dict(inner="sequential", kernel="pallas", layout="sparse",
                     **_ON_CHIP),
    "rcv1_block": dict(inner="block", kernel="sparse_gram", chain="pallas",
                       **_ON_CHIP),
    "rcv1_hybrid": dict(inner="sequential", kernel="pallas",
                        layout="hybrid", **_ON_CHIP),
    "epsilon_seq": dict(inner="sequential", kernel="pallas", layout="dense",
                        **_ON_CHIP),
    "epsilon_block": dict(inner="block", kernel="fused", chain="pallas",
                          **_ON_CHIP),
}


class SmokeFailure(Exception):
    """A phase's check did not hold — the smoke exits non-zero."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --- child side: everything below here may touch JAX ------------------------


def device_info() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def mesh_devices(k: int) -> int:
    """Devices a K-shard run spans here — the CLI's own mesh inference."""
    import jax

    from cocoa_tpu.parallel.mesh import infer_dp_size

    return infer_dp_size(k, len(jax.devices()))


def check_path(path: dict, expect, name: str, k: int) -> None:
    """The resolved local-solver path is the compiled kernel phase ``name``
    exists to exercise, spanning the devices it should (``expect`` — a
    table like :data:`EXPECT` — None = rehearsal: the path is recorded,
    not asserted)."""
    if expect is None:
        return
    for key, want in expect[name].items():
        require(path.get(key) == want,
                f"resolved solver path has {key}={path.get(key)!r}, this "
                f"phase exists to run {key}={want!r} (path: {path})")
    require(path["devices"] == mesh_devices(k),
            f"the data spans {path['devices']} device(s), expected "
            f"{mesh_devices(k)}")


def require_certified(what: str, stopped, gap, rounds, target: float,
                      budget: int) -> None:
    require(stopped == "target" and gap is not None and gap <= target,
            f"{what} did not certify gap <= {target} inside {budget} "
            f"rounds: stopped={stopped!r} gap={gap} after {rounds} rounds")


def require_correspondence(w, w_ref, a_min: float, a_max: float,
                           where: str = "") -> dict:
    """alpha in [0,1] and w == its host-f64 recomputation ``w_ref``."""
    import numpy as np

    require(a_min >= -1e-6 and a_max <= 1 + 1e-6,
            f"alpha left [0,1]: min {a_min}, max {a_max}")
    err = float(np.abs(w - w_ref).max())
    scale = float(np.abs(w_ref).max())
    require(np.isfinite(w).all() and err <= 1e-4 * max(scale, 1e-12),
            f"w != (1/lam n) sum y alpha x: max |diff| {err:.3e} against "
            f"max |w| {scale:.3e}{where}")
    return {"alpha_min": float(a_min), "alpha_max": float(a_max),
            "w_err_vs_f64": err, "w_scale": scale}


def cache_counter():
    """Count this process's persistent-compile-cache hits and misses."""
    import jax

    counts = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event, **kw):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in counts:
            counts[name] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def read_events(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_cli(argv: list, events: str):
    """One CLI run through ``cocoa_tpu.cli.main`` — the console script's
    entry point.  Returns (wall seconds, events)."""
    from cocoa_tpu import cli

    if os.path.exists(events):
        os.unlink(events)
    t0 = time.perf_counter()
    rc = cli.main(argv + [f"--events={events}"])
    wall = time.perf_counter() - t0
    require(rc == 0, f"the CLI exited {rc} for {argv}")
    return wall, read_events(events)


def train_report(wall: float, events: list, algorithm: str = "CoCoA+") -> dict:
    """The fields every training phase line carries, read off the run's
    own event stream."""
    start = [e for e in events if e["event"] == "run_start"]
    require(len(start) == 1, f"expected one run_start, got {len(start)}")
    man = start[0]["manifest"]
    require("solver_path" in man, "run_start carries no solver_path")
    ends = [e for e in events if e["event"] == "run_end"
            and e["algorithm"] == algorithm]
    require(len(ends) == 1, f"no run_end for {algorithm}")
    compile_s = sum(e["seconds"] for e in events if e["event"] == "compile")
    gaps = [e["gap"] for e in events if e["event"] == "round_eval"
            and e["algorithm"] == algorithm]
    return {
        "solver_path": man["solver_path"],
        "rounds": ends[0]["rounds"], "gap": ends[0]["gap"],
        "stopped": ends[0]["stopped"], "test_error": ends[0]["test_error"],
        "first_gap": gaps[0] if gaps else None,
        "cold_s": round(wall, 2), "compile_s": round(compile_s, 2),
        "rest_s": round(wall - compile_s, 2),
    }


def audit_checkpoint(ck_dir: str, train_file: str, d: int, k: int,
                     lam: float, algorithm: str = "CoCoA+") -> dict:
    """alpha in [0,1] and w == (1/(lam n)) sum_i y_i alpha_i x_i for the
    newest validated checkpoint, recomputed on the host in f64 from the
    parsed CSR (rows split into K contiguous shards, data/sharding.py)."""
    import numpy as np

    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu.data import load_libsvm
    from cocoa_tpu.data.sharding import split_sizes

    path = ckpt_lib.latest(ck_dir, algorithm)
    require(path is not None, f"no validated {algorithm} checkpoint in "
                              f"{ck_dir}")
    meta, arrays = ckpt_lib.load_full(path)
    # jaxlint: allow=f64 -- host-side audit reference, never on device
    w = np.asarray(arrays["w"], np.float64)
    alpha = np.asarray(arrays["alpha"], np.float64)
    data = load_libsvm(train_file, d)
    sizes = split_sizes(data.n, k)
    require(alpha.shape[0] == k and alpha.shape[1] >= sizes.max(),
            f"alpha shape {alpha.shape} does not hold {k} shards of "
            f"{sizes.max()} rows")
    a_rows = np.concatenate([alpha[s, :sizes[s]] for s in range(k)])
    pad = np.concatenate([alpha[s, sizes[s]:] for s in range(k)])
    require(not pad.size or np.abs(pad).max() == 0,
            "padding rows carry nonzero alpha")
    coef = data.labels * a_rows / (lam * data.n)
    w_ref = np.bincount(
        data.indices, weights=data.values * np.repeat(
            coef, np.diff(data.indptr)), minlength=len(w))
    return {"checkpoint": os.path.basename(path),
            "checkpoint_round": int(meta["round"]),
            **require_correspondence(w, w_ref, a_rows.min(), a_rows.max(),
                                     f" (round {meta['round']})")}


def parser_in_use() -> str:
    from cocoa_tpu.data import native_loader

    return "native" if native_loader.available() else "python"


def phase_demo(cfg: dict, out: str, expect=EXPECT) -> dict:
    """Phase 1: the run-demo-tpu.sh flag set, real data, CLI."""
    ck = os.path.join(out, "demo_ck")
    argv = [f"--trainFile={cfg['train']}", f"--testFile={cfg['test']}",
            f"--numFeatures={cfg['d']}", f"--numRounds={cfg['rounds']}",
            "--localIterFrac=0.1", f"--numSplits={cfg['k']}",
            f"--lambda={cfg['lam']}", "--justCoCoA=true", "--math=fast",
            "--deviceLoop", "--rng=permuted", f"--gapTarget={cfg['gap']}",
            f"--chkptDir={ck}"]
    wall, events = run_cli(argv, os.path.join(out, "demo.events.jsonl"))
    rep = train_report(wall, events)
    check_path(rep["solver_path"], expect, "demo", cfg["k"])
    require_certified("CoCoA+", rep["stopped"], rep["gap"], rep["rounds"],
                      cfg["gap"], cfg["rounds"])
    require(rep["test_error"] is not None, "no test error was reported")
    rep.update(audit_checkpoint(ck, cfg["train"], cfg["d"], cfg["k"],
                                cfg["lam"]))
    rep["parser"] = parser_in_use()
    return rep


def rcv1_file(cfg: dict, out: str) -> str:
    """The rcv1-shaped LIBSVM file, written once from the seed."""
    path = os.path.join(out, f"rcv1_{cfg['n']}x{cfg['d']}.dat")
    if not os.path.exists(path):
        from cocoa_tpu.data.synth import synth_sparse, write_libsvm

        data = synth_sparse(cfg["n"], cfg["d"], nnz_mean=cfg["nnz_mean"],
                            seed=0)
        write_libsvm(data, path + ".tmp")
        os.replace(path + ".tmp", path)
    return path


def _rcv1_cli(cfg: dict, out: str, name: str, rounds: int, extra: list,
              expect) -> dict:
    train = rcv1_file(cfg, out)
    ck = os.path.join(out, f"{name}_ck")
    argv = [f"--trainFile={train}", f"--numFeatures={cfg['d']}",
            f"--numSplits={cfg['k']}", f"--lambda={cfg['lam']}",
            "--localIterFrac=0.1", "--math=fast", "--deviceLoop",
            "--justCoCoA=true", f"--numRounds={rounds}",
            f"--debugIter={cfg['debug_iter']}",
            f"--chkptIter={cfg['debug_iter']}", f"--chkptDir={ck}", *extra]
    wall, events = run_cli(argv, os.path.join(out, f"{name}.events.jsonl"))
    rep = train_report(wall, events)
    check_path(rep["solver_path"], expect, name, cfg["k"])
    require(rep["gap"] is not None and rep["first_gap"] is not None
            and rep["gap"] < rep["first_gap"],
            f"the duality gap did not fall: {rep['first_gap']} -> "
            f"{rep['gap']}")
    rep.update(audit_checkpoint(ck, train, cfg["d"], cfg["k"], cfg["lam"]))
    rep["parser"] = parser_in_use()
    return rep


def phase_rcv1_seq(cfg: dict, out: str, expect=EXPECT) -> dict:
    """Phase 2a: the sequential sparse kernel to the certified gap."""
    rep = _rcv1_cli(cfg, out, "rcv1_seq", cfg["seq_rounds"],
                    [f"--gapTarget={cfg['seq_gap']}"], expect)
    require_certified("CoCoA+", rep["stopped"], rep["gap"], rep["rounds"],
                      cfg["seq_gap"], cfg["seq_rounds"])
    return rep


def phase_rcv1_block(cfg: dict, out: str, expect=EXPECT) -> dict:
    """Phase 2b: sparse_block_gram/_apply + the lockstep chain kernel."""
    return _rcv1_cli(cfg, out, "rcv1_block", cfg["window_rounds"],
                     [f"--blockSize={cfg['block']}"], expect)


def phase_rcv1_hybrid(cfg: dict, out: str, expect=EXPECT) -> dict:
    """Phase 2c: the hybrid hot-panel branch of the sequential kernel."""
    return _rcv1_cli(cfg, out, "rcv1_hybrid", cfg["window_rounds"],
                     [f"--hotCols={cfg['hot']}"], expect)


def _audit_dense(ds, w, alpha, lam: float) -> dict:
    """The dense-layout correspondence audit: each device's block of rows
    comes back to the host once and w is recomputed in f64."""
    import numpy as np

    # jaxlint: allow=f64 -- host-side audit reference, never on device
    w_ref = np.zeros(ds.num_features, np.float64)
    a_all = np.asarray(alpha, np.float64)
    v_all = np.asarray(ds.labels, np.float64) * a_all   # labels are masked
    for block in ds.X.addressable_shards:
        x = np.asarray(block.data)                      # (m, n_shard, d)
        for j, s in enumerate(range(*block.index[0].indices(ds.k))):
            for lo in range(0, x.shape[1], 8192):
                w_ref += (v_all[s, lo:lo + 8192]
                          @ x[j, lo:lo + 8192].astype(np.float64))
    w_ref /= lam * ds.n
    return require_correspondence(np.asarray(w, np.float64), w_ref,
                                  a_all.min(), a_all.max())


def phase_epsilon(cfg: dict, out: str, expect=EXPECT) -> dict:
    """Phase 3: epsilon width, dense, generated on device, library API —
    the sequential dense kernel, then the fused block kernel under the
    pipelined block round."""
    import jax

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.synth import synth_dense_sharded
    from cocoa_tpu.parallel import make_mesh
    from cocoa_tpu.solvers import run_cocoa
    from cocoa_tpu.telemetry import events as tele_events
    from cocoa_tpu.analysis import sanitize

    del out
    n, d, k = cfg["n"], cfg["d"], cfg["k"]
    n_dev = mesh_devices(k)
    mesh = make_mesh(n_dev) if n_dev > 1 else None
    ds = synth_dense_sharded(n, d, k, seed=0, mesh=mesh)
    jax.block_until_ready(ds.X)
    stats = jax.devices()[0].memory_stats() or {}
    rep = {"peak_bytes_after_synth": stats.get("peak_bytes_in_use"),
           "data_devices": len(ds.X.sharding.device_set),
           "io_callback": tele_events.io_callback_supported()}
    require(rep["data_devices"] == n_dev,
            f"X spans {rep['data_devices']} device(s), expected {n_dev}")
    params = Params(n=n, num_rounds=cfg["rounds"],
                    local_iters=max(1, n // k // 10), lam=cfg["lam"])
    debug = DebugParams(debug_iter=10, seed=0)
    runs = {"seq": dict(),
            "block": dict(block_size=cfg["block"], rng="permuted")}
    for name, kw in runs.items():
        with sanitize.watch_compiles() as compiles:
            t0 = time.perf_counter()
            w, alpha, traj = run_cocoa(
                ds, params, debug, plus=True, quiet=True, math="fast",
                device_loop=True, gap_target=cfg["gap"], mesh=mesh, **kw)
            jax.block_until_ready((w, alpha))
            wall = time.perf_counter() - t0
        last = traj.records[-1]
        compile_s = sum(c.seconds for c in compiles)
        r = {"solver_path": traj.meta["solver_path"],
             "rounds": int(last.round), "gap": float(last.gap),
             "stopped": traj.stopped, "cold_s": round(wall, 2),
             "compile_s": round(compile_s, 2),
             "rest_s": round(wall - compile_s, 2),
             "alpha_devices": len(alpha.sharding.device_set)}
        rep[name] = r
        check_path(r["solver_path"], expect, f"epsilon_{name}", k)
        require(r["alpha_devices"] == n_dev,
                f"alpha spans {r['alpha_devices']} device(s), expected "
                f"{n_dev}")
        require_certified(f"epsilon {name}", traj.stopped, r["gap"],
                          r["rounds"], cfg["gap"], cfg["rounds"])
        r.update(_audit_dense(ds, w, alpha, cfg["lam"]))
    stats = jax.devices()[0].memory_stats() or {}
    rep["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return rep


CHILD_PHASES = {
    # the probe is the part every child reports: what JAX finds, and where
    # this run's compile cache lives
    "probe": lambda cfg, out, expect: {},
    "demo": phase_demo, "rcv1_seq": phase_rcv1_seq,
    "rcv1_block": phase_rcv1_block, "rcv1_hybrid": phase_rcv1_hybrid,
    "epsilon": phase_epsilon,
}


def child_main(name: str, cfg: dict, out: str, rehearse: bool) -> int:
    """Run one phase in this process and leave its result as JSON."""
    import traceback

    from cocoa_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    counts = cache_counter()
    result_path = os.path.join(out, f"{name}.result.json")
    try:
        # a rehearsal records the path it resolved; only a chip run asserts
        # the compiled kernel
        rep = {"ok": True, "cache_dir": cache_dir, **device_info(),
               **CHILD_PHASES[name](cfg, out, None if rehearse else EXPECT)}
    except Exception as e:  # the boundary: record, report, exit non-zero
        traceback.print_exc()
        rep = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    rep.update(counts)
    with open(result_path, "w") as f:
        json.dump(rep, f)
    return 0 if rep["ok"] else 1


# --- parent side: stdlib + numpy only, never JAX ----------------------------


def cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


class Runner:
    """Starts every child, strictly one at a time, and stops whatever is
    still alive when the smoke leaves."""

    def __init__(self, out: str, rehearse: bool):
        self.out = out
        self.rehearse = rehearse
        self.deadline = time.monotonic() + DEADLINE_S
        self.live = None

    def remaining(self) -> float:
        return max(1.0, self.deadline - time.monotonic())

    def start(self, argv: list, stdout, **kw) -> subprocess.Popen:
        if self.live is not None and self.live.poll() is None:
            raise RuntimeError("a child still holds the chip")
        self.live = subprocess.Popen(argv, cwd=ROOT, stdout=stdout,
                                     stderr=subprocess.STDOUT, **kw)
        return self.live

    def stop(self) -> None:
        if self.live is not None and self.live.poll() is None:
            self.live.kill()
            self.live.wait(timeout=30)

    def child(self, name: str, cfg: dict) -> dict:
        """One child phase to completion; returns its result record."""
        log = os.path.join(self.out, f"{name}.log")
        result = os.path.join(self.out, f"{name}.result.json")
        if os.path.exists(result):
            os.unlink(result)
        argv = [sys.executable, os.path.abspath(__file__), "--child", name,
                "--config", json.dumps(cfg), "--out", self.out]
        if self.rehearse:
            argv.append("--rehearse")
        with open(log, "w") as logf:
            proc = self.start(argv, logf)
            try:
                rc = proc.wait(timeout=self.remaining())
            except subprocess.TimeoutExpired:
                self.stop()
                return {"ok": False, "log_tail": tail(log),
                        "error": "ran past the smoke's deadline"}
        try:
            with open(result) as f:
                rep = json.load(f)
        except (OSError, ValueError):
            rep = {"ok": False, "error": f"the child exited {rc} and left "
                                         f"no result"}
        if rc != 0 or not rep.get("ok"):
            rep["ok"] = False
            rep.setdefault("error", f"the child exited {rc}")
            rep["log_tail"] = tail(log)
        return rep


def make_queries(cfg: dict):
    """Seeded sparse queries, as protocol lines and as (idx, val) pairs."""
    import numpy as np

    rng = np.random.default_rng(0)
    lines, pairs = [], []
    for _ in range(cfg["lines"]):
        qs = []
        for _ in range(cfg["per_line"]):
            idx = np.sort(rng.choice(cfg["d"], cfg["nnz"], replace=False))
            val = rng.standard_normal(cfg["nnz"]).astype(np.float32)
            pairs.append((idx, val))
            qs.append(" ".join(f"{i + 1}:{float(v)!r}"
                               for i, v in zip(idx, val)))
        lines.append(";".join(qs))
    return lines, pairs


def phase_serve(runner: Runner, cfg: dict, ck: str) -> dict:
    """Phase 4: a solo f32 ``--serve`` on phase 1's checkpoint directory;
    this process — which has no JAX — is the client."""
    import numpy as np

    events = os.path.join(runner.out, "serve.events.jsonl")
    if os.path.exists(events):
        os.unlink(events)
    log = os.path.join(runner.out, "serve.log")
    t0 = time.perf_counter()
    server = runner.start(
        [sys.executable, "-m", "cocoa_tpu.cli", "--serve=0",
         f"--chkptDir={ck}", f"--numFeatures={cfg['d']}",
         f"--events={events}"],
        subprocess.PIPE, text=True)
    lines, pairs = make_queries(cfg)
    with open(log, "w") as logf:
        port = None
        while time.monotonic() < runner.deadline:
            line = server.stdout.readline()
            if not line:
                break
            logf.write(line)
            if "listening on" in line:
                port = int(line.split("listening on ")[1].split()[0]
                           .rsplit(":", 1)[1])
                break
        require(port is not None,
                f"the server never announced its port (exit "
                f"{server.poll()}): {tail(log)}")
        ready_s = time.perf_counter() - t0
        answers = []
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=min(120, runner.remaining())
                                      ) as sock:
            f = sock.makefile("rwb")
            t1 = time.perf_counter()
            for ln in lines:
                f.write(ln.encode() + b"\n")
                f.flush()
                resp = json.loads(f.readline())
                require(isinstance(resp, list)
                        and len(resp) == cfg["per_line"],
                        f"bad batch response: {resp}")
                answers += resp
            answer_s = time.perf_counter() - t1
            f.write(b"shutdown\n")
            f.flush()
            ack = json.loads(f.readline())
            require(ack.get("ok") == "shutting down",
                    f"bad shutdown ack: {ack}")
        logf.write(server.stdout.read())
    rc = server.wait(timeout=min(120, runner.remaining()))
    require(rc == 0, f"the server exited {rc} after shutdown")

    require(all("margin" in a and a.get("dtype") == "f32" for a in answers),
            f"an answer carries no f32 margin: {answers[:2]}")
    rounds = {a["round"] for a in answers}
    require(len(rounds) == 1, f"answers came from rounds {rounds}")
    (rnd,) = rounds
    path = os.path.join(ck, f"CoCoA+-r{rnd:06d}.npz")
    with np.load(path) as npz:
        # jaxlint: allow=f64 -- host-side audit reference
        w = np.asarray(npz["w"], np.float64)
    worst = 0.0
    for a, (idx, val) in zip(answers, pairs):
        terms = val.astype(np.float64) * w[idx]
        err = abs(a["margin"] - terms.sum())
        bound = 1e-5 * (np.abs(terms).sum() + 1e-12)
        worst = max(worst, err / bound)
        require(err <= bound,
                f"margin {a['margin']} != host f64 {terms.sum()} "
                f"(|diff| {err:.3e} > {bound:.3e})")
    start = [e for e in read_events(events) if e["event"] == "run_start"]
    require(len(start) == 1, "the server emitted no run_start")
    man = start[0]["manifest"]
    return {"platform": man["backend"], "device_kind": man["device_kind"],
            "device_count": man["device_count"], "jax": man["jax_version"],
            "queries": len(answers), "model_round": rnd,
            "worst_err_over_bound": round(worst, 3),
            "ready_s": round(ready_s, 2), "answer_s": round(answer_s, 3),
            "server_exit": rc}


def rebuild_native_parser() -> None:
    """A chip run never trusts a parser .so it did not build: the tree may
    have been copied from a machine with another CPU.  The loader rebuilds
    it from the committed .cpp on first use (or falls to the Python parser,
    loudly); each phase line says which one ingested."""
    for so in glob.glob(os.path.join(ROOT, "native", "libsvm_parser.so*")):
        os.unlink(so)


def run(out: str, sizes: dict, phases, rehearse: bool) -> int:
    os.makedirs(out, exist_ok=True)
    runner = Runner(out, rehearse)
    failed = []

    def report(name: str, rep: dict) -> bool:
        print(json.dumps({"phase": name, **rep}), flush=True)
        if not rep.get("ok"):
            failed.append(name)
            print(f"chip_smoke: phase {name} FAILED: {rep.get('error')}\n"
                  f"{rep.get('log_tail', '')}", file=sys.stderr, flush=True)
        return bool(rep.get("ok"))

    try:
        probe = runner.child("probe", {})
        if not probe.get("ok"):
            print(f"chip_smoke: could not ask JAX for its devices: "
                  f"{probe.get('error')}\n{probe.get('log_tail', '')}",
                  file=sys.stderr)
            return 2
        device = {"platform": probe["platform"],
                  "kind": probe["device_kind"],
                  "count": probe["device_count"]}
        if device["platform"] != "tpu" and not rehearse:
            print(f"chip_smoke: no TPU — JAX found platform "
                  f"{device['platform']!r} ({device['kind']} x "
                  f"{device['count']}); this smoke only runs on the chip "
                  f"(--rehearse is the explicit tiny-shape CPU rehearsal)",
                  file=sys.stderr)
            return 2
        if not rehearse:
            rebuild_native_parser()
        cache_dir = probe["cache_dir"]
        before = cache_entries(cache_dir)
        report("probe", probe)

        for name in phases:
            if name == "serve":
                if "demo" in failed or not glob.glob(
                        os.path.join(out, "demo_ck", "CoCoA+-r*.npz")):
                    report(name, {"ok": False, "error":
                                  "needs phase demo's checkpoint"})
                    continue
                try:
                    rep = {"ok": True, **phase_serve(
                        runner, sizes["serve"],
                        os.path.join(out, "demo_ck"))}
                except (SmokeFailure, OSError, ValueError,
                        subprocess.TimeoutExpired) as e:
                    rep = {"ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "log_tail": tail(os.path.join(out, "serve.log"))}
                finally:
                    runner.stop()
            else:
                rep = runner.child(name, sizes[name.split("_")[0]])
            if report(name, rep):
                for key in ("platform", "device_kind", "device_count"):
                    if rep[key] != probe[key]:
                        failed.append(name)
                        print(f"chip_smoke: phase {name} ran on {key}="
                              f"{rep[key]!r}, the probe found "
                              f"{probe[key]!r}", file=sys.stderr)
        print(json.dumps({"phase": "cache", "dir": cache_dir,
                          "entries_before": before,
                          "entries_after": cache_entries(cache_dir)}),
              flush=True)
    finally:
        runner.stop()
    if failed:
        print(json.dumps({"ok": False, "failed": sorted(set(failed))}))
        return 1
    result = {"ok": True, "device": device}
    if rehearse or tuple(phases) != PHASES:
        # never mistakable for the contract's line: say what this was
        result = {"ok": True, "rehearsal": rehearse, "phases": list(phases),
                  "device": device}
    print(json.dumps(result))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on whatever backend JAX finds")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)}")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for every file a run generates")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--config", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    if args.child:
        return child_main(args.child, json.loads(args.config), out,
                          args.rehearse)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; choose from {PHASES}")
    return run(out, TINY if args.rehearse else FULL, phases, args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
