"""CI bench-regression gate: fresh CPU runs vs the committed baselines.

The benchmark lineage (benchmarks/results.jsonl) records, per config, the comm-ROUND count
to the certified duality-gap target.  Rounds are the one benchmark axis
that is backend-independent (the math is bit-exact per platform and
platform-stable to within a few evals), so CI can guard it on plain CPU
runners without the TPU the wallclock columns need:

    python benchmarks/check_regression.py --report=report.jsonl

re-runs each gated config through the real CLI (fresh process, CPU),
reads the trajectory artifact, and FAILS (exit 1) when

- the run no longer certifies its gap target at all (``stopped`` is not
  ``"target"``), or
- the fresh round count exceeds the committed baseline round count by
  more than the config's explicit tolerance (a convergence regression —
  the kind a bad σ′ default, sampling change, or accel bug causes).

``--fresh=PATH`` skips the runs and checks an existing results.jsonl
(rows matched by ``config``) against the same committed bounds — the
mode for wiring an already-produced benchmark artifact into the gate.

The report is one JSONL row per gated config in the benchmarks-results
dialect, schema-validated (telemetry/schema.py) before the gate exits —
a malformed report is itself a failure.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "benchmarks", "results.jsonl")

# run as `python benchmarks/check_regression.py`: sys.path[0] is
# benchmarks/, so the package needs the repo root added for the schema
# validation import
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# The gated configs.  ``flags`` reproduce the committed results.jsonl
# row's run through the CLI (benchmarks/run.py bench_demo is the
# producer: dense layout, H=50, λ=1e-3, 1e-4 gap target — the BENCH_r*
# lineage headline config).  ``rounds_tol`` is the explicit relative
# slack on the committed round count: float32 reduction order differs
# across CPU microarchitectures by a few evals, never by 15%.
GATES = (
    {
        "config": "demo-cocoa+",
        "algorithm": "CoCoA+",
        "gap_target": 1e-4,
        "rounds_tol": 0.15,
        "flags": [
            "--trainFile=data/small_train.dat", "--numFeatures=9947",
            "--numSplits=4", "--numRounds=600", "--debugIter=10",
            "--localIterFrac=0.1", "--lambda=0.001", "--layout=dense",
            "--math=fast", "--deviceLoop", "--gapTarget=1e-4",
            "--justCoCoA=true", "--quiet",
        ],
    },
    {
        "config": "demo-cocoa+(permuted)",
        "algorithm": "CoCoA+",
        "gap_target": 1e-4,
        "rounds_tol": 0.15,
        "flags": [
            "--trainFile=data/small_train.dat", "--numFeatures=9947",
            "--numSplits=4", "--numRounds=600", "--debugIter=10",
            "--localIterFrac=0.1", "--lambda=0.001", "--layout=dense",
            "--math=fast", "--deviceLoop", "--gapTarget=1e-4",
            "--rng=permuted", "--justCoCoA=true", "--quiet",
        ],
    },
    # The round-barrier levers (ISSUE 12, docs/DESIGN.md §15): a REAL
    # 2-process host-exchange CoCoA+ gang (tests/_gang_worker.py
    # --real=cocoa), synchronous control vs --overlapComm=on
    # --staleRounds=1.  Round counts are fully deterministic here —
    # round-keyed sampling AND round-indexed join windows — so the
    # committed baselines are exact; the tolerance only absorbs future
    # deliberate solver changes.  sleeps are zero: the gate guards the
    # comm-ROUND axis, wall-clock belongs to the slow A/B test.
    {
        "config": "gang-cocoa+sync",
        "algorithm": "GangCoCoA+",
        "gap_target": 1e-4,
        "rounds_tol": 0.15,
        "runner": "gang",
        "flags": [
            "--real=cocoa", "--numSplits=2", "--numRounds=400",
            "--debugIter=5", "--gapTarget=1e-4", "--lambda=0.01",
            "--rowsPerShard=64", "--numFeatures=32", "--localIters=16",
            "--overlapComm=off", "--staleRounds=0",
        ],
    },
    {
        "config": "gang-cocoa+overlap-stale1",
        "algorithm": "GangCoCoA+",
        "gap_target": 1e-4,
        "rounds_tol": 0.15,
        "runner": "gang",
        "flags": [
            "--real=cocoa", "--numSplits=2", "--numRounds=400",
            "--debugIter=5", "--gapTarget=1e-4", "--lambda=0.01",
            "--rowsPerShard=64", "--numFeatures=32", "--localIters=16",
            "--overlapComm=on", "--staleRounds=1",
        ],
    },
    # The fleet row (ISSUE 13): 256 synthetic tenants (a log-spaced λ
    # path over 256 distinct planted-separator problems) through the ONE
    # compiled vmapped round (benchmarks/fleet_bench.py).  The gate
    # re-runs the fleet side only — rounds-to-certify-every-tenant and
    # full certification are the backend-independent axes; the
    # models-per-second and the 173x-vs-serial speedup live in the
    # committed row (CPU-measured, re-measured by fleet_bench --row).
    {
        "config": "fleet-256-synth",
        "algorithm": "CoCoA+ fleet",
        "gap_target": 1e-2,
        "rounds_tol": 0.25,
        "runner": "fleet",
        "flags": ["--fleet-only", "--tenants=256"],
    },
    # The serving row (ISSUE 14, docs/DESIGN.md §17): queries/s at a
    # pinned p99 SLA with a mid-bench hot-swap, measured by
    # benchmarks/serve_bench.py on CPU.  The environment-robust axes the
    # gate pins hard: the p99 SLA holds (the row IS "queries/s at p99 <=
    # SLA"), the scoring path compiled exactly once per bucket, and the
    # hot-swap happened ("stopped" == "target" requires zero failed
    # queries + >= 1 swap).  Throughput itself is wall-clock on a shared
    # CI runner, so only a catastrophic collapse fails: fresh qps must
    # stay above qps_floor_frac x the committed row.
    {
        "config": "serve-cpu-synth",
        "algorithm": "CoCoA+",
        "gap_target": 1e-2,
        "rounds_tol": 0.25,
        "runner": "serve",
        "kind": "serve",
        "qps_floor_frac": 0.25,
        "expected_compiles": 2,
        "flags": ["--duration=3", "--threads=4"],
    },
    # The low-precision serving row (ISSUE 16, docs/DESIGN.md §20): the
    # packed-bf16 compiled scoring path vs the SAME-harness f32 control
    # at the L2-straddle geometry (benchmarks/serve_bench.py
    # --serveDtype=bf16).  The committed row must hold the acceptance
    # bar (qps_ratio >= 1.7, zero sign flips beyond 2x the certified
    # bound); the fresh CI re-run — interleaved-pass wall-clock on a
    # shared runner — is gated at a catastrophic floor plus the
    # environment-robust axes: zero flips, the quantized form actually
    # served ("stopped" == "target" requires swap >= 1 + no certificate
    # fallback), and exactly one compile per (bucket, dtype) per scorer
    # (3 = control f32 + packed bf16 + the f32 fallback form).
    {
        "config": "serve-cpu-synth-bf16",
        "runner": "serve",
        "kind": "serve_quant",
        "min_qps_ratio": 1.7,
        "fresh_ratio_floor": 1.3,
        "expected_compiles": 3,
        "flags": ["--serveDtype=bf16", "--duration=3",
                  "--ratio-bar=1.3"],
    },
    # The int8 serving row (ISSUE 16 residue): same A/B harness as the
    # bf16 row, committed under --correctness-only — XLA's CPU backend
    # emulates the int8 unpack, so CPU throughput is not the claim (the
    # committed row records the honest ratio); what the gate pins is
    # the certificate machinery at the narrower dtype: zero sign flips
    # beyond 2x the certified bound, the quantized form actually
    # served through a mid-measure swap, and one compile per
    # (bucket, dtype) per scorer.  Both ratio bars sit at 0.0 —
    # correctness-only by construction.
    {
        "config": "serve-cpu-synth-int8",
        "runner": "serve",
        "kind": "serve_quant",
        "min_qps_ratio": 0.0,
        "fresh_ratio_floor": 0.0,
        "expected_compiles": 3,
        "flags": ["--serveDtype=int8", "--duration=3",
                  "--correctness-only"],
    },
    # The fleet-serving row (ISSUE 17, docs/DESIGN.md §21): R real CLI
    # scorer replicas serving a (T, d) tenant catalogue behind the
    # router (benchmarks/serve_bench.py --serveReplicas).  The
    # COMMITTED row must beat the committed single-process serve row's
    # qps by min_qps_ratio_committed (the horizontal-scaling acceptance
    # bar); the fresh CI re-run — three process spawns of wall-clock on
    # a shared runner — is gated on the environment-robust axes hard
    # (zero failed queries through a SIGKILL, one compile per bucket
    # per replica process, every replica hot-swapped, the victim
    # respawned) plus a catastrophic throughput floor.
    # The tracing A/B rides the fleet row (ISSUE 19, docs/DESIGN.md
    # §22): the COMMITTED row's tracing-on window must stay within
    # max_trace_overhead_committed of its untraced twin (serve_bench's
    # own 5% self-gate produced it); the fresh re-run — two more
    # wall-clock windows on a shared runner — is held to a
    # catastrophic bound only, plus the environment-robust axes: the
    # sampled query_trace stream is schema-clean and assembled into a
    # waterfall that names a dominant hop (tracing never goes dark).
    {
        "config": "serve-cpu-fleet",
        "runner": "serve",
        "kind": "serve_fleet",
        "replicas": 2,
        "min_qps_ratio_committed": 1.5,
        "baseline_config": "serve-cpu-synth",
        "qps_floor_frac": 0.25,
        "expected_compiles": 2,
        "max_trace_overhead_committed": 5.0,
        "fresh_trace_overhead_bar": 25.0,
        "flags": ["--serveReplicas=2", "--duration=3",
                  "--trace-bar=25"],
    },
    # The warm-ingest row (ISSUE 15, docs/DESIGN.md §18): --ingestCache
    # serves device-ready shard slabs from memmap-able artifacts with
    # ZERO parse.  The gate re-measures the full rcv1-synth warm-vs-
    # streamed-cold A/B (benchmarks/run.py bench_ingest) and fails when
    # the warm map drops below the ≥10× acceptance bar — wall-clock on a
    # shared runner, so the bar IS the bound (the committed row shows
    # 64×; a cache that has regressed to re-parsing or re-validating
    # per byte lands well under 10×, timer noise never costs 6×).
    {
        "config": "ingest/warm-p2",
        "runner": "ingest",
        "kind": "ingest",
        "min_speedup": 10.0,
        "flags": [],
    },
)

# bounded-staleness round overhead vs the synchronous control (the
# ISSUE-12 acceptance bar): the stale gang config may use at most this
# multiple of the sync gang config's fresh rounds
STALE_ROUNDS_RATIO = 1.25
_GANG_PAIR = ("gang-cocoa+sync", "gang-cocoa+overlap-stale1")


def committed_baselines(path: str = RESULTS) -> dict:
    """config -> committed row from benchmarks/results.jsonl."""
    out = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            # perf-accounting rows share the config name but carry no
            # round count — only rows with an anchoring metric (rounds,
            # warm_speedup for the ingest gate, qps_ratio for the
            # low-precision serving gate, or scaling_eff for the
            # fleet-serving gate) can anchor the gate, regardless of
            # row order in the file
            if isinstance(row, dict) and "config" in row \
                    and ("rounds" in row or "warm_speedup" in row
                         or "qps_ratio" in row
                         or "scaling_eff" in row):
                # first qualifying row per config wins (the file appends
                # refreshed rows last in regen; the gate keys on the
                # curated head)
                out.setdefault(row["config"], row)
    return out


def run_fresh(gate: dict, workdir: str) -> dict:
    """One fresh CPU run of the gate's config through the real CLI (own
    process: clean jit caches, clean telemetry); returns the fresh row.
    Never raises: a hung/torn run becomes a per-config ``error`` row so
    the gate still evaluates the remaining configs and writes its
    report."""
    traj_base = os.path.join(workdir, gate["config"].replace("/", "_"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "cocoa_tpu.cli", *gate["flags"],
             f"--trajOut={traj_base}"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=900)
        if proc.returncode != 0:
            return {"config": gate["config"], "error":
                    f"CLI exited {proc.returncode}: {proc.stderr[-500:]}"}
        traj_path = (f"{traj_base}."
                     f"{gate['algorithm'].replace(' ', '_')}.jsonl")
        with open(traj_path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        # line 0 is the manifest header; a run killed before its first
        # eval leaves no record lines at all
        records = [ln for ln in lines if "round" in ln]
        if not records:
            return {"config": gate["config"], "error":
                    f"trajectory {traj_path} carries no round records"}
        last = records[-1]
        return {
            "config": gate["config"],
            "rounds": int(last["round"]),
            "gap": float(last["gap"]),
            "stopped": last.get("stopped"),
            "gap_target": gate["gap_target"],
            "type": "bench-regression-fresh",
        }
    except (subprocess.TimeoutExpired, OSError, ValueError, KeyError,
            TypeError) as e:
        return {"config": gate["config"], "error":
                f"{type(e).__name__}: {e}"}


def run_fresh_gang(gate: dict, workdir: str) -> dict:
    """One fresh 2-process host-exchange gang run (tests/_gang_worker.py
    --real=cocoa) under the in-process elastic supervisor; the fresh
    rounds/gap come from the worker-0 events stream.  Same never-raises
    contract as :func:`run_fresh`."""
    # the gang workers need the repo + tests/ importable, and must not
    # inherit a virtual-device XLA flag (they use no devices).  The
    # supervisor spawns them with the AMBIENT environment, so the tweaks
    # go through os.environ — saved and restored, so later gates (and
    # the caller) see the environment they started with.
    saved = {k: os.environ.get(k)
             for k in ("PYTHONPATH", "XLA_FLAGS", "JAX_PLATFORMS")}
    try:
        os.environ["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, os.path.join(ROOT, "tests"),
                        os.environ.get("PYTHONPATH", "")) if p)
        os.environ["XLA_FLAGS"] = " ".join(
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        tests_dir = os.path.join(ROOT, "tests")
        if tests_dir not in sys.path:
            sys.path.insert(0, tests_dir)
        from _gang_worker import supervise_gang  # the shared launch contract

        ev = os.path.join(workdir,
                          gate["config"].replace("/", "_") + ".jsonl")
        rc, records = supervise_gang(gate["flags"], events=ev)
        if rc != 0:
            return {"config": gate["config"],
                    "error": f"gang exited {rc}"}
        evals = [r for r in records if r.get("event") == "round_eval"]
        end = next((r for r in reversed(records)
                    if r.get("event") == "run_end"), None)
        if not evals or end is None:
            return {"config": gate["config"],
                    "error": f"events stream {ev} carries no run"}
        return {
            "config": gate["config"],
            "rounds": int(evals[-1]["t"]),
            "gap": float(evals[-1]["gap"]),
            "stopped": end.get("stopped"),
            "gap_target": gate["gap_target"],
            "type": "bench-regression-fresh",
        }
    except (OSError, ValueError, KeyError, TypeError) as e:
        return {"config": gate["config"],
                "error": f"{type(e).__name__}: {e}"}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_fresh_fleet(gate: dict, workdir: str) -> dict:
    """One fresh CPU fleet run (benchmarks/fleet_bench.py --fleet-only):
    the row comes from the bench driver's own --row artifact, so the
    gate and the benchmark can never disagree about what a fleet row
    means.  Same never-raises contract as :func:`run_fresh`."""
    row_path = os.path.join(workdir,
                            gate["config"].replace("/", "_") + ".jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks",
                                          "fleet_bench.py"),
             *gate["flags"], f"--row={row_path}"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=900)
        if proc.returncode != 0:
            return {"config": gate["config"], "error":
                    f"fleet bench exited {proc.returncode}: "
                    f"{proc.stderr[-500:]}"}
        with open(row_path) as f:
            row = json.loads(f.readline())
        return {
            "config": gate["config"],
            "rounds": int(row["rounds"]),
            "gap": float(row["gap"]),
            # "target" iff EVERY tenant certified (fleet_bench sets it)
            "stopped": row.get("stopped"),
            "gap_target": gate["gap_target"],
            "type": "bench-regression-fresh",
        }
    except (subprocess.TimeoutExpired, OSError, ValueError, KeyError,
            TypeError) as e:
        return {"config": gate["config"], "error":
                f"{type(e).__name__}: {e}"}


def run_fresh_serve(gate: dict, workdir: str) -> dict:
    """One fresh CPU serving bench (benchmarks/serve_bench.py): the row
    comes from the bench driver's own --row artifact, like the fleet
    gate.  Same never-raises contract as :func:`run_fresh`."""
    row_path = os.path.join(workdir,
                            gate["config"].replace("/", "_") + ".jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks",
                                          "serve_bench.py"),
             *gate["flags"], f"--row={row_path}"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=900)
        if proc.returncode != 0:
            return {"config": gate["config"], "error":
                    f"serve bench exited {proc.returncode}: "
                    f"{proc.stderr[-500:]}"}
        with open(row_path) as f:
            row = json.loads(f.readline())
        return {**row, "type": "bench-regression-fresh"}
    except (subprocess.TimeoutExpired, OSError, ValueError, KeyError,
            TypeError) as e:
        return {"config": gate["config"], "error":
                f"{type(e).__name__}: {e}"}


def run_fresh_ingest(gate: dict, workdir: str) -> dict:
    """One fresh warm-vs-cold ingest A/B at full rcv1-synth scale
    (benchmarks/run.py bench_ingest, the producer of the committed
    ingest/* rows, P=2 only — the gated config).  Same never-raises
    contract as :func:`run_fresh`."""
    try:
        bench_dir = os.path.join(ROOT, "benchmarks")
        if bench_dir not in sys.path:
            sys.path.insert(0, bench_dir)
        import run as bench_run

        results: list = []
        bench_run.bench_ingest(results, quick=False, processes=(2,))
        row = next((r for r in results
                    if r["config"] == gate["config"]), None)
        if row is None:
            return {"config": gate["config"], "error":
                    f"bench_ingest produced no {gate['config']} row"}
        return {**row, "type": "bench-regression-fresh"}
    except (OSError, ValueError, KeyError, TypeError,
            ImportError) as e:
        return {"config": gate["config"], "error":
                f"{type(e).__name__}: {e}"}


def ingest_failures(gate: dict, fresh: dict, committed: dict) -> list:
    """The warm-ingest bounds: the warm map stays ≥ min_speedup× faster
    than the streamed cold parse of the same file/geometry, and warm
    really parses nothing (the row carries mapped bytes, never read
    bytes)."""
    cfg = gate["config"]
    if "error" in fresh:
        return [f"{cfg}: fresh run failed — {fresh['error']}"]
    failures = []
    speedup = fresh.get("warm_speedup")
    if speedup is None:
        failures.append(f"{cfg}: fresh warm row carries no warm_speedup")
    elif speedup < gate["min_speedup"]:
        failures.append(
            f"{cfg}: WARM INGEST REGRESSION — warm map only "
            f"{speedup}× the streamed cold parse (bar ≥ "
            f"{gate['min_speedup']:g}×); the cache is re-parsing or "
            f"re-validating per byte")
    if fresh.get("bytes_read_mb"):
        failures.append(
            f"{cfg}: warm ingest READ {fresh['bytes_read_mb']} MB of "
            f"text — the zero-parse contract broke")
    if committed.get(cfg) is None:
        failures.append(f"{cfg}: no committed baseline row in "
                        f"benchmarks/results.jsonl")
    return failures


def serve_failures(gate: dict, fresh: dict, committed: dict) -> list:
    """The serve-specific bounds (on top of :func:`evaluate`'s
    certification + round checks): the p99 SLA holds, the compile count
    equals the bucket count, and throughput has not collapsed below the
    floor fraction of the committed row."""
    cfg = gate["config"]
    failures = []
    p99, sla = fresh.get("p99_ms"), fresh.get("sla_ms")
    if p99 is None or sla is None:
        failures.append(f"{cfg}: fresh serve row carries no p99/SLA")
    elif p99 > sla:
        failures.append(
            f"{cfg}: SLA VIOLATION — fresh p99 {p99}ms exceeds the "
            f"pinned {sla}ms bound; the row is queries/s AT p99 <= SLA")
    if fresh.get("compiles") != gate["expected_compiles"]:
        failures.append(
            f"{cfg}: COMPILE LEAK — {fresh.get('compiles')} scoring "
            f"compiles for {gate['expected_compiles']} buckets; the "
            f"one-compile-per-bucket contract broke")
    base = committed.get(cfg)
    if base is not None and base.get("qps") is not None:
        floor = base["qps"] * gate["qps_floor_frac"]
        if (fresh.get("qps") or 0) < floor:
            failures.append(
                f"{cfg}: THROUGHPUT COLLAPSE — fresh {fresh.get('qps')} "
                f"qps vs committed {base['qps']} (floor "
                f"{gate['qps_floor_frac']}x = {floor:.0f}); CI noise "
                f"never costs 4x")
    return failures


def serve_quant_failures(gate: dict, fresh: dict,
                         committed: dict) -> list:
    """The low-precision serving bounds.  The COMMITTED row carries the
    acceptance bar (qps_ratio >= min_qps_ratio at zero flips — it was
    produced by serve_bench's own 1.7 self-gate); the fresh re-run is
    held to the environment-robust axes hard (flips, compile count,
    quantized-form-served) and to a catastrophic ratio floor only,
    because absolute wall-clock on a shared CI runner is noise the
    cache-footprint mechanism itself is not."""
    cfg = gate["config"]
    if "error" in fresh:
        return [f"{cfg}: fresh run failed — {fresh['error']}"]
    failures = []
    base = committed.get(cfg)
    if base is None:
        failures.append(f"{cfg}: no committed baseline row in "
                        f"benchmarks/results.jsonl")
    else:
        if (base.get("qps_ratio") or 0) < gate["min_qps_ratio"]:
            failures.append(
                f"{cfg}: COMMITTED ROW BELOW BAR — qps_ratio "
                f"{base.get('qps_ratio')} < {gate['min_qps_ratio']:g}; "
                f"regen the row (serve_bench --serveDtype) on a quiet "
                f"machine, never commit one under the bar")
        if base.get("flips") != 0:
            failures.append(
                f"{cfg}: COMMITTED ROW CARRIES {base.get('flips')} sign "
                f"flips beyond 2x the certified bound — the certificate "
                f"understated the quantization error")
    if fresh.get("stopped") != "target":
        failures.append(
            f"{cfg}: fresh run did not serve the quantized form to "
            f"target (stopped={fresh.get('stopped')!r}: needs >= 1 "
            f"hot-swap, zero flips, and no certificate fallback)")
    if fresh.get("flips") != 0:
        failures.append(
            f"{cfg}: SIGN FLIPS — {fresh.get('flips')} of "
            f"{fresh.get('flip_checked')} audited margins flipped at "
            f"|m32| > 2x the certified bound "
            f"{fresh.get('margin_err_bound')}")
    if fresh.get("compiles") != gate["expected_compiles"]:
        failures.append(
            f"{cfg}: COMPILE LEAK — {fresh.get('compiles')} scoring "
            f"compiles, expected {gate['expected_compiles']} (control "
            f"f32 + packed form + the f32 certificate-fallback form); "
            f"a quantized swap must never compile mid-flight")
    if (fresh.get("qps_ratio") or 0) < gate["fresh_ratio_floor"]:
        failures.append(
            f"{cfg}: RATIO COLLAPSE — fresh qps_ratio "
            f"{fresh.get('qps_ratio')} under the "
            f"{gate['fresh_ratio_floor']:g} catastrophic floor "
            f"(committed {base.get('qps_ratio') if base else '?'}); "
            f"the packed path lost its cache-footprint mechanism, not "
            f"just runner speed")
    return failures


def serve_fleet_failures(gate: dict, fresh: dict,
                         committed: dict) -> list:
    """The fleet-serving bounds.  The COMMITTED row must beat the
    committed single-process serving row's qps by the horizontal-
    scaling acceptance bar; the fresh re-run is held hard to the axes
    a shared runner cannot excuse — zero failed queries through the
    SIGKILL drill, one compile per bucket per replica process, every
    replica hot-swapped, the victim respawned — plus a catastrophic
    qps floor vs the committed fleet row."""
    cfg = gate["config"]
    if "error" in fresh:
        return [f"{cfg}: fresh run failed — {fresh['error']}"]
    failures = []
    base = committed.get(cfg)
    single = committed.get(gate["baseline_config"])
    if base is None:
        failures.append(f"{cfg}: no committed baseline row in "
                        f"benchmarks/results.jsonl")
    else:
        bar = gate["min_qps_ratio_committed"]
        if single is None or single.get("qps") is None:
            failures.append(
                f"{cfg}: no committed {gate['baseline_config']} row to "
                f"anchor the scaling bar against")
        elif (base.get("qps") or 0) < bar * single["qps"]:
            failures.append(
                f"{cfg}: COMMITTED ROW BELOW BAR — fleet qps "
                f"{base.get('qps')} < {bar:g}x the committed "
                f"{gate['baseline_config']} qps {single['qps']}; regen "
                f"the row on a quiet machine, never commit one under "
                f"the bar")
        if base.get("failed") != 0:
            failures.append(
                f"{cfg}: COMMITTED ROW CARRIES {base.get('failed')} "
                f"failed queries — a dead replica must requeue, never "
                f"fail")
        if (base.get("trace_overhead_pct") is not None
                and base["trace_overhead_pct"]
                > gate["max_trace_overhead_committed"]):
            failures.append(
                f"{cfg}: COMMITTED ROW OVER THE TRACING BAR — "
                f"{base['trace_overhead_pct']:g}% qps overhead with "
                f"sampled tracing on (bar "
                f"{gate['max_trace_overhead_committed']:g}%); regen on "
                f"a quiet machine, never commit one over the bar")
        floor = (base.get("qps") or 0) * gate["qps_floor_frac"]
        if (fresh.get("qps") or 0) < floor:
            failures.append(
                f"{cfg}: THROUGHPUT COLLAPSE — fresh "
                f"{fresh.get('qps')} qps vs committed {base.get('qps')} "
                f"(floor {gate['qps_floor_frac']}x = {floor:.0f}); CI "
                f"noise never costs 4x")
    if fresh.get("failed") != 0:
        failures.append(
            f"{cfg}: {fresh.get('failed')} FAILED queries — the "
            f"SIGKILLed replica must cost latency, never an answer")
    if fresh.get("compiles") != gate["expected_compiles"]:
        failures.append(
            f"{cfg}: COMPILE LEAK — {fresh.get('compiles')} scoring "
            f"compiles per replica process, expected "
            f"{gate['expected_compiles']} (one per bucket; the tenant "
            f"catalogue must ride the same executables)")
    if (fresh.get("swaps") or 0) < gate["replicas"]:
        failures.append(
            f"{cfg}: only {fresh.get('swaps')}/{gate['replicas']} "
            f"replicas observed the injected catalogue generation")
    if fresh.get("stopped") != "target":
        failures.append(
            f"{cfg}: fresh fleet run did not reach target "
            f"(stopped={fresh.get('stopped')!r}: needs zero failures, "
            f"every replica swapped, the compile pin, and the "
            f"SIGKILLed replica respawned into routing)")
    if fresh.get("trace_schema_errors"):
        failures.append(
            f"{cfg}: {fresh['trace_schema_errors']} schema violations "
            f"in the sampled query_trace stream — the trace artifact "
            f"stopped being machine-readable")
    if "trace_overhead_pct" in fresh and fresh.get("dominant_hop") \
            is None:
        failures.append(
            f"{cfg}: no sampled query_trace assembled into a "
            f"waterfall — tracing went dark under the committed "
            f"sampling rate")
    if (fresh.get("trace_overhead_pct") or 0) \
            > gate["fresh_trace_overhead_bar"]:
        failures.append(
            f"{cfg}: TRACING OVERHEAD COLLAPSE — fresh "
            f"{fresh['trace_overhead_pct']:g}% qps overhead with "
            f"sampled tracing on, over the "
            f"{gate['fresh_trace_overhead_bar']:g}% catastrophic "
            f"bound; the peel/stamp path got hot, not just the runner")
    return failures


def gang_ratio_failures(rows: list) -> list:
    """The cross-config staleness bound: overlap+stale rounds <=
    STALE_ROUNDS_RATIO x sync rounds (evaluated only when both gang
    rows ran cleanly — a per-config error already failed the gate)."""
    by_cfg = {r.get("config"): r for r in rows if "error" not in r}
    sync, stale = (by_cfg.get(c) for c in _GANG_PAIR)
    if not sync or not stale:
        return []
    bound = int(sync["rounds"] * STALE_ROUNDS_RATIO)
    if stale["rounds"] > bound:
        return [f"{_GANG_PAIR[1]}: STALENESS OVERHEAD — "
                f"{stale['rounds']} rounds vs the synchronous control's "
                f"{sync['rounds']} (bound {STALE_ROUNDS_RATIO}x = "
                f"{bound}); the bounded-staleness trajectory regressed"]
    return []


def evaluate(gate: dict, fresh: dict, committed: dict) -> list:
    """Failure strings for one gate (empty = pass)."""
    cfg = gate["config"]
    if "error" in fresh:
        return [f"{cfg}: fresh run failed — {fresh['error']}"]
    failures = []
    if fresh.get("stopped") != "target":
        failures.append(
            f"{cfg}: fresh run no longer certifies the "
            f"{gate['gap_target']:g} gap target within its round budget "
            f"(stopped={fresh.get('stopped')!r}, gap={fresh.get('gap')})")
    base = committed.get(cfg)
    if base is None:
        failures.append(f"{cfg}: no committed baseline row in "
                        f"benchmarks/results.jsonl — the gate has nothing "
                        f"to compare against")
        return failures
    bound = int(base["rounds"] * (1.0 + gate["rounds_tol"]))
    if fresh.get("rounds", 0) > bound:
        failures.append(
            f"{cfg}: ROUND REGRESSION — fresh {fresh['rounds']} rounds vs "
            f"committed {base['rounds']} (+{gate['rounds_tol'] * 100:.0f}% "
            f"tolerance = {bound}); a convergence change must update the "
            f"baseline deliberately (benchmarks/run.py), not ride in")
    return failures


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    report_path = None
    fresh_path = None
    only = None
    for a in argv:
        if a.startswith("--report="):
            report_path = a.split("=", 1)[1]
        elif a.startswith("--fresh="):
            fresh_path = a.split("=", 1)[1]
        elif a.startswith("--only="):
            only = a.split("=", 1)[1]
        else:
            print(f"usage: python benchmarks/check_regression.py "
                  f"[--report=PATH] [--fresh=results.jsonl] "
                  f"[--only=CONFIG]  (got {a!r})", file=sys.stderr)
            return 2
    committed = committed_baselines()
    gates = [g for g in GATES if only is None or g["config"] == only]
    if not gates:
        print(f"no gated config named {only!r}", file=sys.stderr)
        return 2

    rows = []
    failures = []
    if fresh_path:
        fresh_rows = committed_baselines(fresh_path)  # same config keying
        for gate in gates:
            row = fresh_rows.get(gate["config"])
            if row is None:
                failures.append(f"{gate['config']}: no row in "
                                f"{fresh_path}")
                continue
            if gate.get("kind") == "ingest":
                fresh = {**row, "config": gate["config"]}
                rows.append({**fresh, "type": "bench-regression-fresh"})
                failures += ingest_failures(gate, fresh, committed)
                continue
            if gate.get("kind") == "serve_quant":
                # quant rows anchor on qps_ratio, not rounds — the
                # generic convergence evaluate() does not apply
                fresh = {**row, "config": gate["config"]}
                rows.append({**fresh, "type": "bench-regression-fresh"})
                failures += serve_quant_failures(gate, fresh, committed)
                continue
            if gate.get("kind") == "serve_fleet":
                # fleet rows anchor on scaling_eff/qps, not rounds
                fresh = {**row, "config": gate["config"]}
                rows.append({**fresh, "type": "bench-regression-fresh"})
                failures += serve_fleet_failures(gate, fresh, committed)
                continue
            fresh = {**row,
                     "config": gate["config"],
                     "rounds": int(row["rounds"]),
                     "gap": (float(row["gap"])
                             if row.get("gap") is not None else None),
                     # results.jsonl rows certify by construction; honor
                     # an explicit stopped column when present
                     "stopped": row.get("stopped", "target")}
            rows.append({**fresh, "type": "bench-regression-fresh"})
            failures += evaluate(gate, fresh, committed)
            if gate.get("kind") == "serve":
                failures += serve_failures(gate, fresh, committed)
        # the cross-row staleness bound applies to artifact-checked rows
        # exactly like fresh runs — an overhead regression must not ride
        # in through --fresh mode
        failures += gang_ratio_failures(rows)
    else:
        workdir = tempfile.mkdtemp(prefix="bench-regress-")
        for gate in gates:
            base = committed.get(gate["config"], {})
            if "scaling_eff" in base:
                anchor = (f"qps {base.get('qps')} at scaling_eff "
                          f"{base.get('scaling_eff')}")
            elif "qps_ratio" in base:
                anchor = f"qps_ratio {base.get('qps_ratio')}"
            else:
                anchor = f"{base.get('rounds')} rounds"
            print(f"check_regression: running {gate['config']} "
                  f"(committed baseline {anchor})", flush=True)
            runner = {"gang": run_fresh_gang,
                      "fleet": run_fresh_fleet,
                      "serve": run_fresh_serve,
                      "ingest": run_fresh_ingest}.get(
                          gate.get("runner"), run_fresh)
            fresh = runner(gate, workdir)
            rows.append(fresh)
            if gate.get("kind") == "ingest":
                failures += ingest_failures(gate, fresh, committed)
                continue
            if gate.get("kind") == "serve_quant":
                failures += serve_quant_failures(gate, fresh, committed)
                continue
            if gate.get("kind") == "serve_fleet":
                failures += serve_fleet_failures(gate, fresh, committed)
                continue
            failures += evaluate(gate, fresh, committed)
            if gate.get("kind") == "serve" and "error" not in fresh:
                failures += serve_failures(gate, fresh, committed)
        failures += gang_ratio_failures(rows)

    if report_path:
        with open(report_path, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
        from cocoa_tpu.telemetry import schema as tele_schema

        errs = tele_schema.check_file(report_path, kind="results")
        if errs:
            failures.append(f"report schema violations: {errs[:5]}")

    for row in rows:
        if "error" in row:
            continue
        if "scaling_eff" in row:
            print(f"check_regression: {row['config']}: "
                  f"{row.get('qps')} qps x {row.get('replicas')} "
                  f"replicas (eff {row.get('scaling_eff')}), "
                  f"shed {row.get('shed')} / requeued "
                  f"{row.get('requeued')} / failed {row.get('failed')}, "
                  f"stopped={row.get('stopped')}", flush=True)
        elif "qps_ratio" in row:
            print(f"check_regression: {row['config']}: "
                  f"qps_ratio {row.get('qps_ratio')}, "
                  f"flips {row.get('flips')}/{row.get('flip_checked')}, "
                  f"stopped={row.get('stopped')}", flush=True)
        else:
            print(f"check_regression: {row['config']}: "
                  f"{row.get('rounds')} rounds, gap {row.get('gap')}, "
                  f"stopped={row.get('stopped')}", flush=True)
    if failures:
        for msg in failures:
            print(f"check_regression FAIL: {msg}", file=sys.stderr)
        return 1
    print(f"check_regression: OK — {len(rows)} config(s) within "
          f"tolerance of the committed baselines")
    return 0


if __name__ == "__main__":
    sys.exit(main())
