"""Command-line driver — drop-in for the reference CLI
(hingeDriver.scala:11-115).

Accepts the same ``--key=value`` flag set, loads train/test LIBSVM data,
computes H = max(1, localIterFrac·n/K), then runs the same algorithm menu:
CoCoA+ and CoCoA always; mini-batch CD, mini-batch SGD, local SGD and DistGD
when ``--justCoCoA=false`` (hingeDriver.scala:84-110).  ``--master`` (the
Spark cluster-manager flag, hingeDriver.scala:23) keeps its meaning:
``local``/``local[k]`` runs single-process; ``host:port`` joins the pod's
multi-controller runtime via ``jax.distributed.initialize`` (with
``--processId`` / ``--numProcesses`` or auto-detection on TPU pods).

TPU-native additions (no reference analogue): ``--dtype``, ``--layout``,
``--rng`` (reference | jax | permuted — permuted is random reshuffling,
~5x fewer comm-rounds to the same certified gap at epsilon scale; see
solvers/base.IndexSampler), ``--mesh`` (dp size; defaults to the largest
divisor of numSplits that fits the device count — K shards multiplex
m = K/D per device when D < K, the Spark coalesce analogue;
``--mesh=1`` forces the single-chip vmap path), ``--trajOut`` (JSONL
trajectory dump), ``--gapTarget`` (early stop on duality gap — with a
divergence guard: the run bails out and reports DIVERGED when the best
gap stalls across a ~300-round window, at least 12 evals; see
solvers/base.stall_window),
``--math`` (exact | fast: margins-decomposition inner loop with
auto-Pallas on TPU, CoCoA/CoCoA+ only), ``--deviceLoop`` (whole train
loop as one on-device while_loop; incompatible with checkpointing),
``--loss`` (hinge | smooth_hinge | logistic — all solvers and the
duality-gap certificate generalize; see ops/losses.py), ``--smoothing``
(the smooth_hinge parameter s), ``--blockSize`` (block-coordinate MXU
inner loop for the SDCA family — same index stream and math as
--math=fast via cached block Gram matrices; see
ops/local_sdca.local_sdca_block; ``auto`` picks the measured-best block
size per data layout — sparse layouts whose densified tile cannot ride
the fused kernel use the in-kernel CSR Gram path of ops/pallas_sparse
when it fits, and keep the sequential kernel otherwise, since
SPLIT-path densified sparse blocks lose to it),
``--divergenceGuard=auto|on|off`` (the
gap-target stall watch; auto arms it only when σ′ is overridden below
the safe K·γ bound — see solvers/base.resolve_divergence_guard),
``--sigma`` (σ′ override — below the
safe K·γ it buys comm-rounds on randomly partitioned data; ``auto``
starts at the aggressive K·γ/2, needs --gapTarget),
``--sigmaSchedule=anneal|trial`` (how --sigma=auto reacts when the stall
watch fires: ``anneal`` — the default — backs σ′ off multiplicatively
toward the safe K·γ *inside* the device loop, continuing from the
current iterate with no restart; ``trial`` is the pre-schedule
trial-then-rerun A/B control, preserved bit-exact.  ``anneal`` with an
explicit sub-safe ``--sigma=<float>`` anneals from that start),
``--warmStart=<s>,<rounds>`` (smooth_hinge(s) warm phase handing off to
hinge at the first debugIter boundary ≥ rounds, inside the same device
loop; requires --loss=hinge), ``--elastic=N`` (gang supervisor: N worker
processes, restart-from-checkpoint on any death; after ``max_restarts``
consecutive failed same-size generations the gang is REFORMED at the
largest P′ < P whose devices divide numSplits — shrink-to-survivors,
cocoa_tpu/elastic.py, docs/DESIGN.md §13), ``--elastic=shrink`` /
``--elastic=N,shrink`` (shrink immediately on the first worker loss —
for deployments whose dead host is not coming back; the bare ``shrink``
form takes the gang size from ``--numProcesses``), and
``--stallTimeout=S`` (with --elastic: also restart a gang that stops
making checkpoint progress for S seconds without any process dying).

``--hotCols=auto|off|<n>`` (sparse layout only) builds the HYBRID
hot/cold column-split layout (data/hybrid.py, docs/DESIGN.md §3b-vi):
the globally hottest columns move into a dense MXU-friendly panel and
the padded-CSR keeps only the cold residual — the scalar-issue-bound
stream merges (97.8% of the measured rcv1 round) shrink by the
coverage fraction.  ``auto`` resolves a 75%-coverage panel under an
explicit HBM budget (panel bytes reported); ``off`` keeps the stream
layout bit-exactly as the A/B control.  ``--evalDense`` additionally
accepts ``auto``: materialize the dense eval twin only when it fits
the HBM budget, otherwise (with a hot panel) the certificate margins
ride the panel matvec + residual stream.

``--ingest=stream|whole|auto`` picks how the LIBSVM text reaches the
device (data/ingest.py, docs/DESIGN.md §12).  ``whole`` is the original
path: every process parses the entire file, then slices out its shards.
``stream`` is the two-pass byte-range pipeline: a parallel index scan
(1/P of the file per process, partial column histograms assembled over
the jax.distributed KV store) followed by each process parsing ONLY the
byte ranges of its local devices' shards, built straight into the
target layout — multiplexed dp meshes (D < K devices), ``--hotCols``
and ``--evalDense`` are all first-class, and per-process peak host RSS
drops to ~1/P of the dataset plus the index.  ``auto`` streams exactly
where it wins: multi-process svm runs on a dp mesh.  The built shards
are bit-identical either way (the whole-file build stays the A/B
control); fp meshes and ``--objective=lasso`` are whole-file only and
reject ``--ingest=stream`` loudly.

``--ingestCache=DIR`` (round 20, docs/DESIGN.md §18) makes ingest free
after first touch: a cold run writes each built shard's device-ready
slabs (plus the pass-1 index/histogram and the hybrid layout meta) as
memmap-able artifacts under DIR — atomic rename, one writer wins,
keyed by the source file's (size, mtime_ns, inode) and the full layout
resolution — and every later run of the same file/config ``np.load``\\ s
them straight into ``device_put``: zero parse, page-cache-shared RSS.
The key is the SHARD, not the process geometry, so an elastic shrink's
survivors re-ingest warm and the supervisor forwards the flag to every
relaunched generation unchanged.  With the cache armed, ``--ingest=auto``
routes every svm run through the shard-granular pipeline (bit-identical
shards, pinned); cold pass-2 parses fan out over an intra-process thread
pool when the native parser is available.  Torn or stale artifacts fall
back to a cold parse with a typed ``ingest_cache_corrupt`` event —
never a crash, never a silently wrong slab.  lasso column shards and fp
meshes have no shard-keyed artifact and reject the flag loudly.

``--fleet=manifest.jsonl`` (round 18, docs/DESIGN.md §16) trains a
FLEET: one tenant model per manifest line (dataset ref / λ / gap
target — a schema-validated JSONL dialect, data/fleet.py), all of them
through ONE compiled vmapped round (solvers/fleet.py): per-tenant λ·n
rides the unchanged SDCA kernels as a traced scalar, each tenant's σ′
schedule / secant bank / gap watch is an independent lane, certified
tenants mask out bitwise-frozen, and the whole fleet costs one compile,
one dispatch and one fetch.  ``--fleetLanes=vmap|map`` picks batched
lanes (throughput) vs sequential lanes in the same jit (bit-parity with
the solo path at any T).  The fleet surface is deliberately narrow:
every flag that cannot mean anything on the one-dispatch path
(--elastic, --staleRounds>0, --hotCols, --warmStart, checkpointing,
--testFile, ...) is rejected loudly with a pointer.

``--serve=PORT`` (round 19, docs/DESIGN.md §17) turns this process into
the production SCORING loop (cocoa_tpu/serving/): batched margin
queries ``x·w`` answered on a TCP line protocol through a compiled
scoring path with statically-shaped batch buckets (``--serveBatch``,
default 64/256/1024 — one XLA compile per bucket, ever), an adaptive
micro-batcher admitting requests under the ``--serveSlaMs`` p99 budget,
and double-buffered model slots a watcher hot-swaps ATOMICALLY from the
newest *validated* checkpoint generation in ``--chkptDir`` — so a
background trainer (a separate process, e.g. an ``--elastic`` gang
pointed at the same directory) keeps the served model fresh without
ever dropping or blocking a query.  Freshness is exported as gap age
(``cocoa_model_gap_age_seconds``: seconds since the serving model's
certificate was produced).  The serve surface is a whitelist — every
training flag passed alongside ``--serve`` is rejected loudly.

``--objective=lasso`` switches to the ProxCoCoA+ L1 family
(solvers/prox_cocoa.py): labels become the regression target b,
``--lambda`` the L1 weight, ``--l2`` the optional elastic-net weight;
A's columns are sharded over the workers and the printed certificate is
the lasso duality gap.

Observability (round 15, docs/DESIGN.md §14): ``--trace`` arms
gang-wide span tracing (per-phase, per-worker timing through the
``--events`` stream; assemble with
``python -m cocoa_tpu.telemetry.trace_report``),
``--flightRecorder=auto|on|off`` the crash flight recorder (last-N
events dumped to ``<events>.flightrec`` on divergence / unhandled
exception / SIGTERM, and by the ``--elastic`` supervisor when a worker
dies), ``--eventsMaxMB=N`` size-caps the event JSONL with an atomic
``.1`` rollover, and ``--metricsInterval=S`` debounces the metrics
textfile rewrites.  Multi-process runs stream events per process
(worker 0 owns ``<events>``, worker p ``<events>.p<p>``).
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

from cocoa_tpu.config import REFERENCE_FLAGS, RunConfig
from cocoa_tpu.data import load_libsvm, shard_dataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_cocoa, run_dist_gd, run_minibatch_cd, run_sgd

_TPU_FLAGS = ("dtype", "layout", "rng", "math", "loss",
              "smoothing", "sampling", "sigma")  # same-named RunConfig fields
_EXTRA_FLAGS = ("mesh", "fp", "trajOut", "gapTarget", "resume", "scanChunk",
                "deviceLoop", "master", "processId", "numProcesses",
                "profile", "objective", "l2", "blockSize",
                "divergenceGuard",
                "sigmaSchedule", "warmStart", "accel", "theta",
                "elastic", "stallTimeout", "evalDense", "hotCols",
                "ingest", "ingestCache", "metrics", "events", "quiet",
                "trace", "flightRecorder", "eventsMaxMB",
                "metricsInterval", "overlapComm",
                "staleRounds", "fleet", "fleetLanes",
                "serve", "serveBatch", "serveSlaMs",
                "serveMaxNnz", "serveDtype", "serveReplicas",
                "serveRoute", "traceSample", "statusPort",
                "classes")  # run-level

_BOOL_FIELDS = {"just_cocoa"}
_INT_FIELDS = {"num_features", "num_splits", "chkpt_iter", "num_rounds",
               "debug_iter", "seed"}
_FLOAT_FIELDS = {"lam", "local_iter_frac", "beta", "gamma", "smoothing",
                 "sigma"}


def _resolve_auto_block(ds_active, mesh, k: int, dtype,
                        quiet: bool = False) -> int:
    """``--blockSize=auto`` against the ACTIVE dataset (rows for svm,
    columns for lasso): the measured-best B per layout, or 0 to keep the
    sequential kernels (solvers/cocoa.auto_block_size)."""
    from cocoa_tpu.parallel.fanout import shards_per_device
    from cocoa_tpu.solvers.cocoa import auto_block_size

    m_local = shards_per_device(mesh, k) if mesh is not None else k
    bs = auto_block_size(ds_active, m_local, dtype)
    if not quiet:
        print(f"blockSize=auto: using {bs or 'the sequential path'} for the "
              f"{ds_active.layout} layout")
    return bs


def _device_memory_limit():
    """What the first device says it can hold (``memory_stats()``'s
    ``bytes_limit``), or None on a backend without the counter (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def parse_args(argv: list[str]):
    """--key=value (or bare --flag == true, hingeDriver.scala:13-19)."""
    options: dict[str, str] = {}
    for arg in argv:
        stripped = arg.lstrip("-")
        if "=" in stripped:
            key, val = stripped.split("=", 1)
        else:
            key, val = stripped, "true"
        options[key] = val

    cfg = RunConfig()
    extras = {k: None for k in _EXTRA_FLAGS}
    for key, val in options.items():
        if key in _EXTRA_FLAGS:
            extras[key] = val
            continue
        if key in REFERENCE_FLAGS:
            field = REFERENCE_FLAGS[key]
        elif key in _TPU_FLAGS:
            field = key
        else:
            raise SystemExit(f"Invalid argument: --{key}")
        if field in _BOOL_FIELDS:
            if val.lower() not in ("true", "false"):
                # Scala's String.toBoolean rejects anything else too
                raise SystemExit(f"Invalid argument: --{key}={val} (expected true/false)")
            setattr(cfg, field, val.lower() == "true")
        elif field in _INT_FIELDS:
            setattr(cfg, field, int(val))
        elif field in _FLOAT_FIELDS:
            if field == "sigma" and val == "auto":
                # σ′ auto-tuning: try the aggressive K·γ/2, fall back to
                # the safe K·γ if the divergence guard fires (run_cocoa)
                setattr(cfg, field, "auto")
            else:
                setattr(cfg, field, float(val))
        else:
            setattr(cfg, field, val)
    # which flags the USER actually passed (vs dataclass defaults) — what
    # lets the fleet path reject explicitly-given-but-meaningless
    # reference flags (--lambda, --numFeatures) instead of silently
    # training on different values.  A non-field attribute: asdict() and
    # the config hash never see it.
    cfg._explicit = frozenset(options)
    return cfg, extras


def main(argv=None) -> int:
    # persistent XLA compilation cache: repeat CLI runs of the same config
    # skip the first compile (COCOA_NO_COMPILE_CACHE=1 opts out)
    from cocoa_tpu.utils import compile_cache

    compile_cache.enable()

    argv = sys.argv[1:] if argv is None else argv
    cfg, extras = parse_args(argv)

    # --quiet: silence the console (flag echo, per-round lines, summaries).
    # The telemetry sinks (--events/--metrics/--trajOut) are unaffected —
    # a quiet run still leaves the full machine-readable trace.
    quiet = (extras["quiet"] is not None
             and str(extras["quiet"]).lower() != "false")

    # --trace: gang-wide span tracing (telemetry/tracing.py) — per-phase,
    # per-worker timing through the event stream; --flightRecorder: the
    # bounded last-N-events ring dumped to `<events>.flightrec` on
    # divergence / unhandled exception / SIGTERM (and by the --elastic
    # supervisor on a worker death); --eventsMaxMB: size-capped JSONL
    # with atomic `.1` rollover; --metricsInterval: the metrics-textfile
    # write debounce.  Validated up front so a typo fails before the run.
    trace_on = (extras["trace"] is not None
                and str(extras["trace"]).lower() != "false")
    if trace_on and not (extras["events"] or extras["metrics"]):
        print("error: --trace records spans through the telemetry sinks "
              "and needs --events (for trace_report/Perfetto) or "
              "--metrics (for the phase-seconds gauges)", file=sys.stderr)
        return 2
    flightrec_mode = (extras["flightRecorder"] or "auto").lower()
    if flightrec_mode == "true":
        flightrec_mode = "on"   # bare --flightRecorder
    if flightrec_mode not in ("auto", "on", "off"):
        print(f"error: --flightRecorder must be auto|on|off, got "
              f"{extras['flightRecorder']!r}", file=sys.stderr)
        return 2
    if flightrec_mode == "on" and not extras["events"]:
        print("error: --flightRecorder=on needs --events (the dump lands "
              "at <events>.flightrec, and the supervisor-side dump tails "
              "the per-process event streams)", file=sys.stderr)
        return 2
    events_max_bytes = None
    if extras["eventsMaxMB"]:
        try:
            events_max_bytes = int(extras["eventsMaxMB"]) << 20
        except ValueError:
            events_max_bytes = 0
        if events_max_bytes <= 0:
            print(f"error: --eventsMaxMB takes a positive integer of "
                  f"mebibytes, got {extras['eventsMaxMB']!r}",
                  file=sys.stderr)
            return 2
        if not extras["events"]:
            print("error: --eventsMaxMB caps the --events JSONL and "
                  "needs --events", file=sys.stderr)
            return 2
    metrics_interval = 0.0
    if extras["metricsInterval"]:
        try:
            metrics_interval = float(extras["metricsInterval"])
        except ValueError:
            metrics_interval = -1.0
        if metrics_interval < 0:
            print(f"error: --metricsInterval takes seconds >= 0, got "
                  f"{extras['metricsInterval']!r}", file=sys.stderr)
            return 2
        if not extras["metrics"]:
            print("error: --metricsInterval debounces the --metrics "
                  "textfile and needs --metrics", file=sys.stderr)
            return 2

    # --overlapComm: the round-barrier levers (docs/DESIGN.md §15).  On
    # this compiled-collective CLI path the in-round Δw aggregation is a
    # fused psum — already as overlapped as XLA schedules it — so the
    # flag's CLI consumer is the host-side IO at super-block boundaries:
    # checkpoint writes ride a writer thread concurrent with the next
    # dispatch (solvers/base.drive_device_full).  auto = on for
    # single-process runs (a multi-process save allgathers alpha — a
    # collective that must not race a training dispatch); off (default)
    # is bit-identical to pre-flag behavior by construction.
    overlap_flag = (extras["overlapComm"] or "off").lower()
    if overlap_flag == "true":
        overlap_flag = "on"   # bare --overlapComm
    if overlap_flag not in ("auto", "on", "off"):
        print(f"error: --overlapComm must be auto|on|off, got "
              f"{extras['overlapComm']!r}", file=sys.stderr)
        return 2
    # --staleRounds=S: bounded-staleness CoCoA+ aggregation — a round-r
    # contribution may join up to S rounds late under the safe-γ rule
    # (solvers/cocoa.StaleJoinWindow, docs/DESIGN.md §15).  S=0 (the
    # default) is today's synchronous barrier.  S>0 needs the HOST-side
    # exchange aggregation path (the gang harness, tests/_gang_worker.py
    # --real=cocoa); this CLI path aggregates inside the compiled
    # collective, where a round cannot be split — reject loudly instead
    # of accepting a flag that silently does nothing.
    stale_rounds = 0
    if extras["staleRounds"] is not None:
        try:
            stale_rounds = int(extras["staleRounds"])
        except ValueError:
            stale_rounds = -1
        if stale_rounds < 0:
            print(f"error: --staleRounds takes an integer >= 0, got "
                  f"{extras['staleRounds']!r}", file=sys.stderr)
            return 2
        if stale_rounds > 0:
            print("error: --staleRounds > 0 rides the host-exchange "
                  "aggregation path (the chaos gang harness, "
                  "tests/_gang_worker.py --real=cocoa); this CLI path "
                  "aggregates Δw inside the compiled collective, which "
                  "is synchronous by construction — drop the flag "
                  "(docs/DESIGN.md §15)", file=sys.stderr)
            return 2

    # --fleet=manifest.jsonl: thousands of tenant models through ONE
    # compiled vmapped round (solvers/fleet.py, docs/DESIGN.md §16).
    # The fleet surface is deliberately narrow — every flag that cannot
    # mean anything on the one-dispatch tenant-vmapped path is rejected
    # LOUDLY here with a pointer, never accepted as a silent no-op.
    fleet_path = extras["fleet"]
    fleet_lanes = (extras["fleetLanes"] or "vmap").lower()
    if extras["fleetLanes"] and not fleet_path:
        print("error: --fleetLanes picks the fleet's lane execution and "
              "needs --fleet", file=sys.stderr)
        return 2
    if fleet_lanes not in ("vmap", "map"):
        print(f"error: --fleetLanes must be vmap|map, got "
              f"{extras['fleetLanes']!r}", file=sys.stderr)
        return 2
    if fleet_path:
        if extras["serve"]:
            # checked before the fleet's own prerequisite checks so the
            # combination names the real conflict, not a side effect
            # (--serve needs --chkptDir, which the fleet also rejects)
            print("error: --serve does not combine with --fleet: the "
                  "fleet is one training dispatch, serving is a "
                  "long-lived query loop — run them as separate "
                  "processes (docs/DESIGN.md §17)", file=sys.stderr)
            return 2
        rejected = {
            "elastic": "the elastic supervisor gang-restarts one model's "
                       "training; a fleet is thousands of independent "
                       "models in one dispatch — shrinking a gang "
                       "mid-fleet has no defined tenant semantics "
                       "(docs/DESIGN.md §16)",
            "resume": "fleet checkpoint/resume is not in the v1 surface",
            "warmStart": "the warm-start loss handoff is a solo-path "
                         "schedule; fleets share one loss phase "
                         "(docs/DESIGN.md §16)",
            "hotCols": "fleet v1 is dense-layout only",
            "evalDense": "fleet v1 is dense-layout only",
            "ingestCache": "the slab cache is keyed to the solo shard "
                           "layout; fleet tenants sharing a dataset ref "
                           "already dedupe through the in-process memo "
                           "(data/fleet.py — one parse per distinct "
                           "ref)",
            "blockSize": "the block/Pallas kernels own their shard axes "
                         "and cannot ride the tenant vmap",
            "classes": "a fleet's tenants are independent models, each "
                       "over its own copy of its rows; T class models "
                       "over ONE copy of the rows are a solo job's "
                       "(--classes without --fleet; docs/DESIGN.md, "
                       "one-vs-rest)",
        }
        if cfg.test_file:
            print("error: --testFile does not combine with --fleet: "
                  "per-tenant test sets are not in the fleet v1 surface",
                  file=sys.stderr)
            return 2
        if cfg.chkpt_dir:
            print("error: --chkptDir does not combine with --fleet: fleet "
                  "checkpoint/resume is not in the v1 surface (the run is "
                  "one dispatch; rerun the fleet instead)", file=sys.stderr)
            return 2
        for flag, why in rejected.items():
            if extras[flag]:
                print(f"error: --{flag} does not combine with --fleet: "
                      f"{why}", file=sys.stderr)
                return 2
        if cfg.train_file:
            print("error: --fleet names per-tenant datasets in the "
                  "manifest; drop --trainFile", file=sys.stderr)
            return 2
        explicit = getattr(cfg, "_explicit", frozenset())
        if "lambda" in explicit:
            print("error: --lambda does not combine with --fleet: λ is "
                  "per-tenant and comes from the manifest — a global "
                  "--lambda would silently train different models than "
                  "asked for", file=sys.stderr)
            return 2
        if "numFeatures" in explicit:
            print("error: --numFeatures does not combine with --fleet: "
                  "the feature dimension comes from each tenant's "
                  "dataset ref (manifest num_features for file-backed "
                  "tenants)", file=sys.stderr)
            return 2
        if (extras["objective"] or "svm").lower() != "svm":
            print("error: --fleet runs the SVM dual family only "
                  "(--objective=lasso has no fleet path yet)",
                  file=sys.stderr)
            return 2
        if extras["overlapComm"] and overlap_flag != "off":
            print("error: --overlapComm does not combine with --fleet: "
                  "the whole fleet is ONE dispatch and one fetch — there "
                  "is no per-round exchange or checkpoint write to "
                  "overlap (docs/DESIGN.md §16)", file=sys.stderr)
            return 2

    # --serve=PORT (0/bare = ephemeral): the production scoring loop
    # (cocoa_tpu/serving/, docs/DESIGN.md §17) — answer batched margin
    # queries from the newest VALIDATED checkpoint generation in
    # --chkptDir while a background trainer (a separate process, e.g.
    # an --elastic supervised gang pointed at the same directory) keeps
    # it fresh.  The serve surface is a WHITELIST: serving answers
    # queries, it does not train, so every training flag explicitly
    # passed alongside --serve is rejected loudly with a pointer —
    # never accepted as a silent no-op.
    serve_flag = extras["serve"]
    for dep, what in (("serveBatch", "sets the static batch buckets"),
                      ("serveSlaMs", "sets the p99 latency budget"),
                      ("serveMaxNnz", "sets the per-query nonzero "
                                      "budget"),
                      ("serveDtype", "sets the serving precision"),
                      ("serveReplicas", "scales the scorer fleet"),
                      ("serveRoute", "selects the fleet routing "
                                     "policy"),
                      ("traceSample", "samples per-query distributed "
                                      "traces"),
                      ("statusPort", "serves the live ops plane")):
        if extras[dep] and not serve_flag:
            print(f"error: --{dep} {what} of the serving loop and needs "
                  f"--serve", file=sys.stderr)
            return 2
    if serve_flag:
        if fleet_path:
            print("error: --serve does not combine with --fleet: the "
                  "fleet is one training dispatch, serving is a "
                  "long-lived query loop — run them as separate "
                  "processes (docs/DESIGN.md §17)", file=sys.stderr)
            return 2
        pointers = {
            "elastic": "supervise the background TRAINER with --elastic "
                       "and point --serve's --chkptDir at its "
                       "checkpoints — the server must stay outside the "
                       "gang so a resize can never wedge a query "
                       "(docs/DESIGN.md §17)",
            "sigmaSchedule": "σ′ schedules belong to the trainer "
                             "process (--sigmaSchedule=trial is a "
                             "training A/B control; the server only "
                             "reads validated checkpoints)",
            "gapTarget": "the trainer certifies the gap; the server "
                         "reports it as freshness "
                         "(cocoa_model_gap_age_seconds)",
            "resume": "the server always serves the newest validated "
                      "generation; there is nothing to resume",
            "classes": "the server scores one model's margins; a "
                       "one-vs-rest job's T models are not served yet",
            "ingestCache": "the slab cache serves TRAINING ingest; put "
                           "--ingestCache on the background trainer's "
                           "command line (the serve-side --trainFile "
                           "parse only derives the query nonzero "
                           "budget)",
            "dtype": "--dtype is the TRAINING precision; the serving "
                     "stack quantizes the model at swap time — set "
                     "--serveDtype=f32|bf16|int8 instead "
                     "(docs/DESIGN.md §20)",
        }
        allowed = {
            # the documented serve surface (README flag table): the
            # serve flags, the model source, the query-side layout, and
            # the observability flags every mode shares
            "serve", "serveBatch", "serveSlaMs", "serveMaxNnz",
            "serveDtype", "serveReplicas", "serveRoute", "chkptDir",
            "numFeatures", "trainFile", "hotCols", "quiet",
            "metrics", "events", "trace", "flightRecorder",
            "eventsMaxMB", "metricsInterval", "seed",
            "traceSample", "statusPort",
        }
        explicit = getattr(cfg, "_explicit", frozenset())
        for key in sorted(explicit - allowed):
            why = pointers.get(
                key, "serving answers queries from the checkpoints in "
                     "--chkptDir; training flags belong to the "
                     "background trainer process (docs/DESIGN.md §17)")
            print(f"error: --{key} does not combine with --serve: {why}",
                  file=sys.stderr)
            return 2
        if not cfg.chkpt_dir:
            print("error: --serve needs --chkptDir (the checkpoint "
                  "directory the hot-swap watcher polls — point it at "
                  "the background trainer's --chkptDir)",
                  file=sys.stderr)
            return 2
        if extras["hotCols"] is not None and not cfg.train_file:
            print("error: --serve with --hotCols needs --trainFile: the "
                  "hot panel is the TRAINED column split, resolved from "
                  "the training data's column histogram "
                  "(data/hybrid.py)", file=sys.stderr)
            return 2
        # --serveReplicas=N scales the scorer fleet behind a router
        # front door (serving/fleet.py + router.py, docs/DESIGN.md
        # §21); --serveRoute picks its routing policy.  Validated HERE
        # (before any JAX work) so a typo fails in milliseconds
        n_replicas = 1
        if extras["serveReplicas"]:
            try:
                n_replicas = int(extras["serveReplicas"])
            except ValueError:
                n_replicas = 0
            if n_replicas < 1:
                print(f"error: --serveReplicas takes a replica count "
                      f">= 1, got {extras['serveReplicas']!r}",
                      file=sys.stderr)
                return 2
            cores = os.cpu_count() or 1
            if n_replicas > cores:
                print(f"warning: --serveReplicas={n_replicas} "
                      f"oversubscribes the {cores} detected core(s): "
                      f"replicas time-share cores and per-replica "
                      f"scaling efficiency degrades — measure before "
                      f"trusting a fleet this wide", file=sys.stderr)
        if extras["serveRoute"]:
            from cocoa_tpu.serving.router import Router as _Router
            if extras["serveRoute"] not in _Router.ROUTES:
                print(f"error: --serveRoute takes one of "
                      f"{'/'.join(_Router.ROUTES)}, got "
                      f"{extras['serveRoute']!r}", file=sys.stderr)
                return 2
            if n_replicas < 2:
                print("error: --serveRoute picks how the fleet router "
                      "spreads queries and needs --serveReplicas>=2 "
                      "(one replica has nothing to route between)",
                      file=sys.stderr)
                return 2
        if n_replicas >= 2 and extras["hotCols"] is not None:
            print("error: --hotCols does not combine with "
                  "--serveReplicas>=2: per-replica hot panels are not "
                  "in the fleet v1 surface — serve the hybrid layout "
                  "from a single process, or drop --hotCols "
                  "(docs/DESIGN.md §21)", file=sys.stderr)
            return 2

    # --profile=DIR traces the whole run; --profile=DIR,START,STOP traces
    # the round window [START, STOP) by riding the telemetry event stream
    # (telemetry/profiling.py) — validated here so a typo fails before the
    # run, not after it
    profile_dir = profile_window = None
    if extras["profile"]:
        from cocoa_tpu.telemetry.profiling import parse_profile_flag

        try:
            profile_dir, p_start, p_stop = parse_profile_flag(
                extras["profile"])
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if p_start is not None:
            profile_window = (p_start, p_stop)

    if not cfg.train_file and not fleet_path and not serve_flag:
        print("error: --trainFile is required", file=sys.stderr)
        return 2
    if cfg.num_features <= 0 and not fleet_path:
        # serving needs it too: the query width the compiled scoring
        # path is built for (and the width checkpoints must match)
        print("error: --numFeatures must be positive", file=sys.stderr)
        return 2
    from cocoa_tpu.ops import losses as losses_mod

    try:
        losses_mod.validate(cfg.loss, cfg.smoothing)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if cfg.loss not in losses_mod.LOSSES:
        # prox rules (lasso) are selected by --objective, never by --loss —
        # the SVM solvers would run garbage updates and crash at first eval
        print(f"error: --loss must be one of {losses_mod.LOSSES}; "
              f"use --objective=lasso for the L1 family", file=sys.stderr)
        return 2
    if cfg.math not in ("exact", "fast"):
        print(f"error: --math must be exact|fast, got {cfg.math!r}",
              file=sys.stderr)
        return 2

    if cfg.sigma == "auto" and not extras["gapTarget"] and not fleet_path:
        # fail at the CLI boundary with the standard message/exit-code —
        # run_cocoa would raise the same requirement later as a traceback.
        # (--fleet runs accept manifest-supplied per-tenant targets
        # instead; the fleet runner validates per-tenant coverage.)
        print("error: --sigma=auto requires --gapTarget (the σ′ fallback "
              "triggers on the divergence guard, which runs on the "
              "gap-target path)", file=sys.stderr)
        return 2

    sigma_schedule = extras["sigmaSchedule"]
    if sigma_schedule is not None and sigma_schedule not in ("trial",
                                                             "anneal"):
        print(f"error: --sigmaSchedule must be trial|anneal, got "
              f"{extras['sigmaSchedule']!r}", file=sys.stderr)
        return 2
    if sigma_schedule == "trial" and cfg.sigma != "auto":
        print("error: --sigmaSchedule=trial is the --sigma=auto A/B "
              "control and needs --sigma=auto", file=sys.stderr)
        return 2
    anneal_engages = (cfg.sigma == "auto"
                      or (isinstance(cfg.sigma, float)
                          and 0 < cfg.sigma < cfg.num_splits * cfg.gamma))
    if (sigma_schedule == "anneal" and anneal_engages
            and not extras["gapTarget"] and not fleet_path):
        # the anneal backoff rides the stall watch, which only runs on the
        # gap-target path (with no sub-safe σ′ the schedule is inert and
        # the flag is accepted as a no-op)
        print("error: --sigmaSchedule=anneal requires --gapTarget (the "
              "in-loop backoff triggers on the stall watch, which runs "
              "on the gap-target path)", file=sys.stderr)
        return 2

    accel_flag = (extras["accel"] or "auto").lower()
    if accel_flag not in ("auto", "on", "off"):
        print(f"error: --accel must be auto|on|off, got "
              f"{extras['accel']!r}", file=sys.stderr)
        return 2
    theta_flag = (extras["theta"] or "fixed").lower()
    if theta_flag not in ("fixed", "adaptive"):
        print(f"error: --theta must be fixed|adaptive, got "
              f"{extras['theta']!r}", file=sys.stderr)
        return 2
    if accel_flag == "on" and not extras["gapTarget"] and not fleet_path:
        # momentum's restart rule monitors the eval-cadence gap; without
        # a target the run is a fixed-round benchmark path that must stay
        # bit-comparable — require the gap-target regime explicitly.
        # (--fleet accel accepts manifest-supplied per-tenant targets;
        # the fleet runner validates every tenant carries one.)
        print("error: --accel=on requires --gapTarget (the momentum "
              "restart rule monitors the gap trajectory; fixed-round "
              "benchmark runs stay unaccelerated)", file=sys.stderr)
        return 2
    if accel_flag == "on" and sigma_schedule == "trial":
        print("error: --accel cannot ride --sigmaSchedule=trial (the "
              "trial is the bit-exact A/B control); use "
              "--sigmaSchedule=anneal", file=sys.stderr)
        return 2
    if theta_flag == "adaptive" and (accel_flag == "off"
                                     or sigma_schedule == "trial"
                                     or not extras["gapTarget"]):
        print("error: --theta=adaptive requires an accelerated "
              "gap-targeted run (--accel=auto|on with --gapTarget, "
              "not --sigmaSchedule=trial)", file=sys.stderr)
        return 2

    warm_start = None
    if extras["warmStart"]:
        parts = str(extras["warmStart"]).split(",")
        try:
            if len(parts) != 2:
                raise ValueError
            warm_start = (float(parts[0]), int(parts[1]))
        except ValueError:
            print(f"error: --warmStart takes <smoothing>,<rounds> (e.g. "
                  f"0.1,300), got {extras['warmStart']!r}", file=sys.stderr)
            return 2
        if warm_start[0] <= 0 or warm_start[1] < 1:
            print("error: --warmStart needs smoothing > 0 and rounds >= 1",
                  file=sys.stderr)
            return 2
        if cfg.loss != "hinge":
            print("error: --warmStart hands a smooth_hinge phase off to "
                  "hinge and requires --loss=hinge", file=sys.stderr)
            return 2
        if cfg.debug_iter <= 0:
            print("error: --warmStart requires --debugIter > 0 (the "
                  "in-loop handoff lands on the eval cadence)",
                  file=sys.stderr)
            return 2

    if extras["stallTimeout"] and not extras["elastic"]:
        # without a supervisor there is no watchdog to act on the timeout —
        # silently ignoring it would leave the user believing stall
        # protection is active on a run that can still wedge forever
        print("error: --stallTimeout only acts under --elastic=N (the "
              "supervisor is what kills and restarts a wedged gang)",
              file=sys.stderr)
        return 2

    if extras["elastic"]:
        # --elastic=N: this process becomes the SUPERVISOR — it launches N
        # worker copies of this command line (each with its own processId
        # and a supervisor-chosen coordinator port) and gang-restarts them
        # from the latest checkpoint when any worker dies.  The Spark-
        # lineage-recovery analogue for an all-reduce runtime
        # (cocoa_tpu/elastic.py).  When the same-size gang cannot be kept
        # alive (max_restarts consecutive failures — or immediately with
        # the "shrink" spec), the supervisor reforms it at P′ < P
        # survivors: numSplits shards re-divide over the smaller gang and
        # each survivor streams in only its inherited shards
        # (docs/DESIGN.md §13).
        from cocoa_tpu import elastic

        shrink_mode = "auto"
        n_workers = None
        devices_per_worker = 1
        for part in str(extras["elastic"]).split(","):
            part = part.strip()
            if part == "shrink":
                shrink_mode = "now"
            elif part.startswith("devices="):
                # local devices each worker owns (the per-host chip count
                # on TPU; 1 for a localhost CPU gang) — the granularity
                # shrink must keep K divisible by.  Declared, not probed:
                # the supervisor must never initialize a backend itself
                # (on a TPU host it would steal the chips from its own
                # workers)
                try:
                    devices_per_worker = int(part[len("devices="):])
                except ValueError:
                    devices_per_worker = 0
                if devices_per_worker < 1:
                    print(f"error: --elastic devices= takes a positive "
                          f"per-worker device count, got {part!r}",
                          file=sys.stderr)
                    return 2
            elif part:
                try:
                    n_workers = int(part)
                except ValueError:
                    print("error: --elastic takes an integer worker count "
                          "and/or 'shrink' and/or 'devices=D' "
                          "(--elastic=4, --elastic=4,shrink, "
                          "--elastic=shrink, --elastic=4,shrink,devices=4), "
                          f"got {extras['elastic']!r}",
                          file=sys.stderr)
                    return 2
        if n_workers is None:
            # bare --elastic=shrink: the gang size comes from
            # --numProcesses (the flag that already names it)
            if not extras["numProcesses"]:
                print("error: --elastic=shrink needs a gang size; pass "
                      "--elastic=N,shrink or add --numProcesses=N",
                      file=sys.stderr)
                return 2
            try:
                n_workers = int(extras["numProcesses"])
            except ValueError:
                print("error: --numProcesses must be an integer",
                      file=sys.stderr)
                return 2
        if n_workers < 1:
            print("error: --elastic needs at least 1 worker", file=sys.stderr)
            return 2
        try:
            elastic_fp = int(extras["fp"]) if extras["fp"] else 1
        except ValueError:
            print(f"error: --fp must be an integer, got {extras['fp']!r}",
                  file=sys.stderr)
            return 2
        if elastic_fp > 1:
            # the fp axis pins w's column split to the device grid — a
            # resized gang cannot restore the old checkpoints' placement.
            # Explicit shrink is rejected loudly; the default degrades to
            # the pre-shrink same-size supervision with a note.
            if shrink_mode == "now":
                print("error: --elastic=shrink does not support "
                      "feature-parallel (fp) meshes: w's column split is "
                      "pinned to the device grid, so a reformed gang "
                      "cannot resume the checkpoints; drop --fp or use "
                      "--elastic=N", file=sys.stderr)
                return 2
            shrink_mode = "off"
            print("note: --elastic with --fp keeps same-size restarts "
                  "only (an fp gang cannot shrink; see docs/DESIGN.md "
                  "§13)", file=sys.stderr)
        if not cfg.chkpt_dir:
            print("warning: --elastic without --chkptDir restarts from "
                  "round 1 on failure (no checkpoints to resume from)",
                  file=sys.stderr)

        def progress_token():
            # the restart budget bounds CONSECUTIVE failures: any new or
            # renamed checkpoint file since the last generation means the
            # run advanced, so the streak resets.  The worker's --metrics
            # textfile (refreshed per event, or per --metricsInterval
            # window under the debounce — see the warning above) is a
            # FINER progress signal than checkpoint files — it advances
            # on every eval, so the stall watchdog can catch a wedge
            # well inside a long chkptIter interval.
            ckpts = None
            if cfg.chkpt_dir and os.path.isdir(cfg.chkpt_dir):
                ckpts = tuple(sorted(
                    f for f in os.listdir(cfg.chkpt_dir)
                    if f.endswith(".npz")))
            metrics = None
            if extras["metrics"]:
                try:
                    with open(extras["metrics"]) as f:
                        metrics = f.read()
                except OSError:
                    pass
            if ckpts is None and metrics is None:
                return None
            return (ckpts, metrics)

        stall = None
        if extras["stallTimeout"]:
            # --stallTimeout=SECONDS: also restart a gang that WEDGES
            # without any process dying (a hung device dispatch, one worker
            # exiting 0 while peers block in a collective).  Progress =
            # new round-stamped checkpoint files, so it needs --chkptDir
            # and a sensible --chkptIter cadence.
            try:
                stall = float(extras["stallTimeout"])
            except ValueError:
                print("error: --stallTimeout must be seconds (float), got "
                      f"{extras['stallTimeout']!r}", file=sys.stderr)
                return 2
            if stall <= 0:
                print("error: --stallTimeout must be > 0", file=sys.stderr)
                return 2
            if not cfg.chkpt_dir and not extras["metrics"]:
                print("error: --stallTimeout watches checkpoint/metrics "
                      "progress — it needs --chkptDir or --metrics",
                      file=sys.stderr)
                return 2
            if stall < 120:
                # the watchdog cannot tell "compiling" from "wedged": a
                # generation's first token change needs first-compile
                # (seconds to minutes cold, see utils/compile_cache.py)
                # PLUS chkptIter rounds — a tight
                # timeout SIGKILLs healthy gangs until the restart budget
                # burns (round-5 review finding)
                print(f"warning: --stallTimeout={stall:g}s is shorter than "
                      f"a typical first-compile + first-checkpoint budget; "
                      f"healthy gangs may be killed as stalled — consider "
                      f">= 120s (and a --chkptIter the gang can reach "
                      f"within the timeout)", file=sys.stderr)
            if (extras["metrics"] and metrics_interval > 0
                    and metrics_interval * 2 > stall):
                # the watchdog's finest progress signal is worker 0's
                # metrics textfile, and the debounce delays its rewrites
                # by up to one interval — an interval near (or past) the
                # stall timeout blinds the watchdog to live progress and
                # SIGKILLs healthy gangs
                print(f"warning: --metricsInterval={metrics_interval:g}s "
                      f"debounces the metrics progress signal the "
                      f"--stallTimeout={stall:g}s watchdog reads; keep "
                      f"the interval well under half the timeout (or "
                      f"rely on --chkptDir progress)", file=sys.stderr)

        if extras["events"] or extras["metrics"]:
            # the supervisor's gang-restart/resize events land in the SAME
            # event JSONL worker 0 writes (whole-line appends interleave
            # safely) — one machine-readable stream for the whole
            # supervised run.  The gang gauges (cocoa_gang_size,
            # cocoa_gang_generations_total, cocoa_restart_backoff_seconds)
            # land in a SIBLING textfile `<metrics>.gang` rendering ONLY
            # those families: worker 0 owns `<metrics>` and rewrites it
            # per event, so sharing one file would have two processes
            # flip-flopping its contents — and duplicating the worker
            # families here would break textfile collectors that glob
            # the directory
            from cocoa_tpu import telemetry

            bus_sup = telemetry.get_bus()
            # no max_bytes here: the supervisor shares worker 0's file,
            # and a file must have exactly ONE rotating owner (two
            # emitters racing os.replace would clobber the fresh `.1`
            # archive) — worker 0 rotates; the supervisor's handful of
            # restart/resize events ride whichever file is current
            bus_sup.configure(jsonl_path=extras["events"])
            if extras["metrics"]:
                from cocoa_tpu.telemetry.metrics import MetricsWriter

                bus_sup.subscribe(MetricsWriter(
                    extras["metrics"] + ".gang", families="gang",
                    flush_interval_s=metrics_interval))
            if trace_on:
                # supervisor spans (gang generations, restart backoffs)
                # join the same stream; no worker tag — trace_report
                # attributes them by pid
                from cocoa_tpu.telemetry import tracing

                tracing.configure(enabled=True)
        return elastic.supervise(
            elastic.strip_elastic_flags(argv), n_workers,
            resume=bool(cfg.chkpt_dir), progress_token=progress_token,
            stall_timeout_s=stall,
            num_splits=cfg.num_splits, shrink=shrink_mode,
            devices_per_worker=devices_per_worker,
        )

    # multi-host: --master=host:port connects this process to the pod's
    # coordinator (the Spark-master analogue) BEFORE any backend use, so
    # jax.devices() below is the global device set
    from cocoa_tpu.parallel import distributed

    try:
        proc_id = int(extras["processId"]) if extras["processId"] else None
        n_procs = int(extras["numProcesses"]) if extras["numProcesses"] else None
    except ValueError:
        print("error: --processId/--numProcesses must be integers",
              file=sys.stderr)
        return 2
    try:
        distributed.maybe_initialize(extras["master"], proc_id, n_procs)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    # echo flags, as the reference does (hingeDriver.scala:41-48) — with its
    # gamma-prints-beta bug (quirk #2) fixed
    if not quiet:
        for f in dataclasses.fields(cfg):
            print(f"{f.name}: {getattr(cfg, f.name)}")

    dtype = jnp.dtype(cfg.dtype)
    # jaxlint: allow=f64 -- explicit --dtype=float64 opt-in: the
    # reference (Breeze) is f64 throughout, and parity runs reproduce it
    if dtype == jnp.float64:
        # jaxlint: allow=f64 -- same opt-in: x64 only flips when the user
        # asked for the f64 parity configuration
        jax.config.update("jax_enable_x64", True)

    # telemetry: the event bus + metrics textfile are owned by process 0
    # (worker 0 of an elastic gang / host 0 of a pod inherits stdout the
    # same way); the run manifest is the FULL flag surface — reference
    # flags and TPU-native extras alike — so the config hash identifies
    # the run end to end.  The ``run_start`` emit itself waits until the
    # data layout is resolved (below) so the manifest can record the
    # hot/cold split provenance; cfg/extras are not mutated in between.
    from cocoa_tpu import telemetry
    from cocoa_tpu.telemetry import recorder as flightrec_lib
    from cocoa_tpu.telemetry import tracing

    bus = telemetry.get_bus()
    is_primary = (proc_id or 0) == 0
    # per-process event streams: worker 0 owns `<events>` (shared with the
    # elastic supervisor's appends, as before); worker p > 0 streams to
    # `<events>.p<p>` — so every worker's spans and events survive its own
    # death for the supervisor's flight-recorder dump, and
    # telemetry/trace_report.py can merge the gang's streams into one
    # timeline.  The metrics textfile stays worker-0-only (the
    # supervisor's `.gang` sibling carries the gang families).
    events_path = None
    if extras["events"]:
        events_path = flightrec_lib.worker_stream_path(
            extras["events"], proc_id or 0)
    if events_path or (is_primary and extras["metrics"]):
        bus.configure(
            jsonl_path=events_path,
            metrics_path=extras["metrics"] if is_primary else None,
            max_bytes=events_max_bytes,
            metrics_interval_s=metrics_interval)
    if trace_on:
        tracing.configure(enabled=True, worker=proc_id or 0)
    if events_path and flightrec_mode != "off":
        # the in-process half of the flight recorder: ring of the last N
        # events, dumped on divergence / unhandled exception / SIGTERM
        # (telemetry/recorder.py; the supervisor covers SIGKILL)
        flightrec_lib.install(bus, events_path)
    cfg_manifest = {**dataclasses.asdict(cfg),
                    **{k: v for k, v in extras.items() if v is not None}}
    run_meta = {"dataset": cfg.train_file, "seed": cfg.seed,
                "config_hash": telemetry.events.config_hash(cfg_manifest)}

    if fleet_path:
        return _run_fleet_cli(cfg, extras, quiet, bus, cfg_manifest,
                              fleet_lanes, sigma_schedule, accel_flag,
                              theta_flag)

    if serve_flag:
        return _run_serve_cli(cfg, extras, quiet, bus, cfg_manifest,
                              serve_flag)

    k = cfg.num_splits

    # mesh selection: K shards ride a D-device dp mesh whenever D divides K
    # (m = K/D logical shards multiplex per device — the Spark ``coalesce``
    # analogue, OptUtils.scala:14); K=D is the 1:1 case, D=1 runs the
    # single-chip vmap path (all K logical shards on one device).  An
    # explicit --mesh that can't be honored is an error; inferred sizes
    # fall back silently.  --fp=F adds a feature axis: a (D, F) mesh over
    # D*F devices, w and X columns split over fp.
    mesh = None
    try:
        fp = int(extras["fp"]) if extras["fp"] else 1
    except ValueError:
        print(f"error: --fp must be an integer, got {extras['fp']!r}",
              file=sys.stderr)
        return 2
    if fp < 1:
        print(f"error: --fp must be >= 1, got {fp}", file=sys.stderr)
        return 2
    explicit = extras["mesh"] is not None
    if explicit:
        try:
            mesh_size = int(extras["mesh"])
        except ValueError:
            print(f"error: --mesh must be an integer, got {extras['mesh']!r}",
                  file=sys.stderr)
            return 2
    else:
        from cocoa_tpu.parallel.mesh import infer_dp_size

        mesh_size = infer_dp_size(k, len(jax.devices()) // fp)
    if explicit and (mesh_size * fp > len(jax.devices())
                     or (mesh_size > 1 and k % mesh_size != 0)):
        print(f"error: --mesh={mesh_size} (x fp={fp}) needs a divisor of "
              f"numSplits={k} and mesh x fp devices (have "
              f"{len(jax.devices())}); use --mesh=1 for the single-chip "
              f"path", file=sys.stderr)
        return 2
    if fp > 1 and explicit and mesh_size == 1 and k > 1:
        print(f"error: --fp={fp} needs a device mesh and is incompatible "
              f"with the --mesh=1 single-chip path; drop --mesh or pass "
              f"--mesh={k}", file=sys.stderr)
        return 2
    if fp > 1 and mesh_size != k:
        print(f"error: --fp={fp} requires a {k}x{fp}-device mesh "
              f"(numSplits x fp; shard multiplexing is dp-only; have "
              f"{len(jax.devices())} devices)", file=sys.stderr)
        return 2
    if not explicit and not quiet and mesh_size * fp < len(jax.devices()):
        # inferred mesh leaves devices idle (prime/coprime K falls to the
        # largest divisor, worst case 1 — all shards on one chip).  A perf
        # cliff the user can fix by aligning K, so say so.
        print(f"note: inferred mesh uses {mesh_size} of "
              f"{len(jax.devices())} devices (largest divisor of "
              f"numSplits={k} that fits); a numSplits divisible by "
              f"{len(jax.devices())} would use every device")
    if mesh_size > 1 or fp > 1:
        mesh = make_mesh(mesh_size, fp=fp)

    objective = (extras["objective"] or "svm").lower()
    if objective not in ("svm", "lasso"):
        print(f"error: --objective must be svm|lasso, got {objective!r}",
              file=sys.stderr)
        return 2

    # same bare-flag/boolean convention as --deviceLoop: present (or any
    # value except "false") enables it — except the new "auto", which
    # resolves per dataset below (twin only when it fits the HBM budget)
    ed_spec = ("false" if extras["evalDense"] is None
               else str(extras["evalDense"]).lower())
    eval_dense = ed_spec not in ("false", "auto")

    # --classes=auto|<T>: the train (and test) file is multi-class and is
    # loaded as one — class ids beside the reference's +-1 labels — and
    # CoCoA / CoCoA+ train T models one-vs-rest over the one copy of the
    # rows (solvers/cocoa.run_cocoa).  The one loader-side flag; what the
    # class axis is not carried through yet is refused here, loudly
    classes_flag = extras["classes"]
    if classes_flag is not None:
        why = None
        if str(classes_flag).lower() != "auto":
            try:
                if int(classes_flag) < 2:
                    raise ValueError
            except ValueError:
                why = (f"--classes takes auto or a class count >= 2, got "
                       f"{classes_flag!r}")
        if objective != "svm":
            why = ("--classes trains SVMs one-vs-rest; --objective=lasso "
                   "regresses on one target and has no class axis")
        elif not cfg.just_cocoa:
            why = ("--classes trains CoCoA / CoCoA+ one-vs-rest; the "
                   "primal baselines carry no class axis: pass "
                   "--justCoCoA=true")
        elif mesh is not None:
            why = ("--classes trains on one chip (--mesh=1): the class "
                   "axis is not carried across a mesh")
        elif extras["hotCols"] is not None or ed_spec != "false":
            why = ("--classes trains on dense rows (a class a sublane; the "
                   "classes on the lanes, a block of rows a step, where "
                   "the T models outgrow the sublanes) or on sparse rows, "
                   "a rectangle or a stream (--layout=sparse: the classes "
                   "on the lanes, label sets too); the hot-column panel "
                   "and the dense "
                   "eval twin carry no class axis: drop --hotCols / "
                   "--evalDense")
        elif extras["ingestCache"] or (extras["ingest"] or "auto") \
                not in ("auto", "whole"):
            why = ("--classes reads the file whole: --ingest=stream and "
                   "--ingestCache keep no class ids")
        elif extras["resume"] or cfg.chkpt_iter > 0 and cfg.chkpt_dir:
            why = ("--classes writes and resumes no checkpoints yet (they "
                   "hold one model's w and alpha): drop --resume / "
                   "--chkptIter")
        if why:
            print(f"error: {why} (docs/DESIGN.md, one-vs-rest)",
                  file=sys.stderr)
            return 2

    # --ingest=stream|whole|auto: how the LIBSVM text reaches the device
    # (data/ingest.py).  Resolved against the mesh/objective BEFORE any
    # parse so a streamed run never pays a whole-file pass by accident.
    from cocoa_tpu.data import ingest as ingest_lib

    # --ingestCache=DIR: the shard-granular persistent slab cache
    # (data/slab_cache.py, docs/DESIGN.md §18).  Armed BEFORE mode
    # resolution: with a cache, auto routes svm runs through the
    # shard-granular pipeline so warm shards load with zero parse.
    ingest_cache = None
    if extras["ingestCache"]:
        if objective == "lasso":
            print("error: --ingestCache does not apply to "
                  "--objective=lasso (the column shards transpose the "
                  "row slabs per run — nothing shard-keyed to cache); "
                  "drop the flag", file=sys.stderr)
            return 2
        from cocoa_tpu.parallel.mesh import has_fp as _has_fp

        if _has_fp(mesh):
            print("error: --ingestCache does not support "
                  "feature-parallel (fp) meshes (the fp column split "
                  "re-buckets rows per device grid — the shard "
                  "artifacts are geometry-free by contract); drop --fp "
                  "or the cache flag", file=sys.stderr)
            return 2
        from cocoa_tpu.data import slab_cache as slab_cache_lib

        try:
            ingest_cache = slab_cache_lib.SlabCache(
                str(extras["ingestCache"]))
        except OSError as e:
            print(f"error: --ingestCache={extras['ingestCache']!r}: "
                  f"{e}", file=sys.stderr)
            return 2
        if bus.active():
            ingest_cache.on_corrupt = (
                lambda **kw: bus.emit("ingest_cache_corrupt", **kw))

    try:
        ingest_mode = ingest_lib.resolve_ingest_mode(
            extras["ingest"], mesh, objective=objective,
            cached=ingest_cache is not None)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if classes_flag is not None:
        ingest_mode = "whole"       # (auto resolved: the class ids' path)

    from cocoa_tpu.data import resolve_hot_cols, resolve_layout

    hot_n = 0
    layout_split = None
    ingest_reports = []
    cache_events = []

    def record_cache(path, status, info):
        """One typed ``ingest_cache`` record per file (``info`` is the
        StreamBuildInfo both ingest paths produce) — the single appender
        every branch shares, so the event's field set cannot drift."""
        if ingest_cache is not None:
            cache_events.append(dict(
                path=path, status=status,
                shards_cached=info.shards_cached,
                shards_total=info.shards_total,
                bytes_mapped=info.cache_bytes_mapped,
                seconds_saved=info.seconds_saved))

    def cache_snap():
        """Counter snapshot bracketing one whole-path build."""
        if ingest_cache is None:
            return (0, 0, 0)
        return (ingest_cache.shard_hits, ingest_cache.shard_misses,
                ingest_cache.bytes_mapped)

    data = None
    ds = test_ds = None
    if objective == "lasso" and extras["hotCols"] is not None:
        # column shards transpose the roles (the shard "rows" ARE
        # columns); a row-space hot panel has no meaning there
        print("error: --hotCols does not apply to --objective=lasso "
              "(column shards already partition the feature axis)",
              file=sys.stderr)
        return 2

    def announce_eval(eval_dense, hot_n):
        if not quiet:
            fallback = ("hot panel + residual stream" if hot_n
                        else "per-nonzero gather (no hot panel — "
                             "consider --hotCols=auto)")
            print(f"evalDense=auto: "
                  f"{'dense twin' if eval_dense else fallback} "
                  f"for the certificate margins")

    def announce_hot(layout_split, hot_n):
        if hot_n and not quiet:
            print(f"hotCols={layout_split['spec']}: panel {hot_n} "
                  f"columns, {layout_split['coverage'] * 100:.1f}% "
                  f"nonzero coverage, "
                  f"{layout_split['panel_bytes'] / 2**20:.1f} MiB HBM, "
                  f"residual mean nnz "
                  f"{layout_split['residual_mean_nnz']:.1f} (max "
                  f"{layout_split['residual_max_nnz']})")

    def resolve_stats_knobs(n_, total_nnz_, hist_):
        """``--layout``/``--hotCols``/``--evalDense=auto`` resolved from
        dataset STATS alone — ONE implementation shared by the streaming
        pass-1 path and the whole-path warm loader so the two cannot
        drift (the cold whole path resolves from the parsed data via
        resolve_hot_cols: the pinned A/B control of this resolution).
        Returns ``(resolved_layout, hot_width, eval_dense)``; raises
        ValueError for the --hotCols-vs-layout rejection and the
        over-budget explicit panel."""
        from cocoa_tpu.data import hybrid as hybrid_knobs
        from cocoa_tpu.data.sharding import (eval_dense_fits,
                                             resolve_layout_stats)

        lay = resolve_layout_stats(n_, cfg.num_features, total_nnz_,
                                   cfg.layout, mesh)
        if extras["hotCols"] is not None and lay != "sparse":
            raise ValueError("--hotCols (the hot/cold column split) "
                             "only applies to the sparse layout")
        hot_w, ed = 0, eval_dense
        if lay == "sparse":
            hot_w = hybrid_knobs.resolve_hot_width(
                extras["hotCols"], hist_, n_, k, dtype)
            if ed_spec == "auto":
                ed = eval_dense_fits(n_, cfg.num_features, k, dtype)
        return lay, hot_w, ed

    import time as time_mod

    if ingest_mode == "stream":
        # streaming sharded ingest (svm only — resolve_ingest_mode
        # rejects lasso/fp): pass 1 builds the row index + global column
        # histogram from per-process partial scans, --hotCols resolves
        # from that histogram bit-identically to the whole-file build,
        # pass 2 parses only this process's shard byte ranges
        from cocoa_tpu.data import hybrid as hybrid_lib

        def stream_cache_status(index, sinfo):
            # one file's cache outcome: the shard status degraded to
            # "partial" when the index itself had to be re-scanned (a
            # warm run pays zero scan AND zero parse)
            if ingest_cache is None:
                return "off"
            if sinfo.cache_status == "hit" and index.scan_bytes:
                return "partial"
            return sinfo.cache_status

        try:
            index = ingest_lib.build_index(cfg.train_file,
                                           cfg.num_features,
                                           cache=ingest_cache)
            n = index.n
            resolved_layout, hot_n, eval_dense = resolve_stats_knobs(
                n, index.total_nnz, index.hist)
            if resolved_layout == "sparse" and ed_spec == "auto":
                announce_eval(eval_dense, hot_n)
            ds, sinfo = ingest_lib.stream_shard_dataset(
                cfg.train_file, cfg.num_features, k, layout=cfg.layout,
                dtype=dtype, mesh=mesh, eval_dense=eval_dense,
                hot_cols=hot_n, index=index, cache=ingest_cache)
            if resolved_layout == "sparse":
                layout_split = hybrid_lib.stats_from_counts(
                    extras["hotCols"], index.hist, hot_n,
                    (sinfo.residual_max_nnz if hot_n
                     else int(index.row_nnz.max(initial=0))),
                    n, k, dtype)
                announce_hot(layout_split, hot_n)
            ingest_reports.append(ingest_lib.IngestReport(
                mode="stream", path=cfg.train_file,
                file_bytes=index.file_bytes,
                processes=jax.process_count(),
                parse_seconds=index.scan_seconds + sinfo.parse_seconds,
                bytes_read=index.scan_bytes + sinfo.bytes_read,
                rows=sinfo.rows, nnz=sinfo.nnz,
                n=n, total_nnz=index.total_nnz,
                peak_rss_bytes=ingest_lib.peak_rss_bytes(),
                cache=stream_cache_status(index, sinfo)))
            record_cache(cfg.train_file,
                         stream_cache_status(index, sinfo), sinfo)
            if cfg.test_file:
                tindex = ingest_lib.build_index(cfg.test_file,
                                                cfg.num_features,
                                                cache=ingest_cache)
                test_ds, tinfo = ingest_lib.stream_shard_dataset(
                    cfg.test_file, cfg.num_features, k,
                    layout=cfg.layout, dtype=dtype, mesh=mesh,
                    eval_dense=eval_dense, hot_cols=hot_n, index=tindex,
                    cache=ingest_cache)
                ingest_reports.append(ingest_lib.IngestReport(
                    mode="stream", path=cfg.test_file,
                    file_bytes=tindex.file_bytes,
                    processes=jax.process_count(),
                    parse_seconds=(tindex.scan_seconds
                                   + tinfo.parse_seconds),
                    bytes_read=tindex.scan_bytes + tinfo.bytes_read,
                    rows=tinfo.rows, nnz=tinfo.nnz,
                    n=tindex.n, total_nnz=tindex.total_nnz,
                    peak_rss_bytes=ingest_lib.peak_rss_bytes(),
                    cache=stream_cache_status(tindex, tinfo)))
                record_cache(cfg.test_file,
                             stream_cache_status(tindex, tinfo), tinfo)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    else:
        # whole-file ingest: every process parses the full file, then
        # slices out its shards (the bit-exact A/B control; multi-process
        # dp runs still materialize only their local shards host-side).
        # An explicit --ingest=whole with --ingestCache still consults
        # AND populates the slab cache (docs/DESIGN.md §18): a warm full
        # hit skips the parse entirely (data/ingest.load_cached_dataset),
        # a cold parse publishes every built shard plus the file's stats
        # artifact for the next process.
        import numpy as _np

        from cocoa_tpu.data.sharding import resolve_layout_stats as _rls

        t_load = time_mod.perf_counter()

        def whole_handle(path):
            if ingest_cache is None or objective != "svm":
                return None
            try:
                return ingest_cache.for_file(path, cfg.num_features)
            except OSError:
                return None  # a vanished file fails the parse below
                # with its own clean error

        def whole_report(path, parsed, seconds, cache="off"):
            # one report per loaded file, like the stream branch, so the
            # stream-vs-whole telemetry is an apples-to-apples A/B;
            # parse seconds cover parse + shard/slab build, same span the
            # streamed pass-2 timer covers
            try:
                fsize = os.path.getsize(path)
            except OSError:
                fsize = 0
            return ingest_lib.IngestReport(
                mode="whole", path=path, file_bytes=fsize,
                processes=jax.process_count(), parse_seconds=seconds,
                bytes_read=fsize, rows=parsed.n,
                nnz=int(parsed.indptr[-1]), n=parsed.n,
                total_nnz=int(parsed.indptr[-1]),
                peak_rss_bytes=ingest_lib.peak_rss_bytes(), cache=cache)

        def warm_whole(handle, stats, path, hot_w, ed, t0):
            """(ds, report) served entirely from cache artifacts, or
            None — the caller cold-parses, which re-populates."""
            if handle is None or stats is None:
                return None
            lay = _rls(stats.n, cfg.num_features, stats.total_nnz,
                       cfg.layout, mesh)
            got = ingest_lib.load_cached_dataset(
                handle, stats, k, layout=lay, dtype=dtype, mesh=mesh,
                eval_dense=ed, hot_cols=hot_w)
            if got is None:
                return None
            ds_w, winfo = got
            record_cache(path, "hit", winfo)
            rep = ingest_lib.IngestReport(
                mode="whole", path=path, file_bytes=stats.file_bytes,
                processes=jax.process_count(),
                parse_seconds=time_mod.perf_counter() - t0,
                bytes_read=0, rows=0, nnz=0, n=stats.n,
                total_nnz=stats.total_nnz,
                peak_rss_bytes=ingest_lib.peak_rss_bytes(),
                cache="hit")
            return ds_w, winfo, rep

        def populate_whole(handle, parsed, path, snap, t0):
            """After a cold whole parse+build: store the file's stats
            artifact + (on a full miss) the cold cost, and emit the
            cache outcome (the shard slabs were published inside
            shard_dataset; ``snap`` is the :func:`cache_snap` taken
            before the build)."""
            handle.store_index(
                hist=_np.bincount(parsed.indices,
                                  minlength=cfg.num_features),
                n=parsed.n, total_nnz=int(parsed.indptr[-1]),
                max_row_nnz=int(parsed.max_nnz))
            hits = ingest_cache.shard_hits - snap[0]
            misses = ingest_cache.shard_misses - snap[1]
            if hits == 0:
                # only a FULL miss records the cold cost — a partial run
                # re-paid its missed shards only, and that sliver would
                # corrupt the seconds_saved estimate for good
                handle.store_cost(time_mod.perf_counter() - t0)
            status = "partial" if hits else "miss"
            record_cache(path, status, ingest_lib.StreamBuildInfo(
                rows=0, nnz=0, bytes_read=0, parse_seconds=0.0,
                residual_max_nnz=0, shards_cached=hits,
                shards_total=hits + misses,
                cache_bytes_mapped=ingest_cache.bytes_mapped - snap[2],
                cache_status=status))
            return status

        # the warm attempt resolves --layout/--hotCols/--evalDense=auto
        # from the CACHED stats — bit-identical to the parsed-data
        # resolution below (the stream-resolution parity pin) — so a
        # full hit never reads a byte of text
        train_handle = whole_handle(cfg.train_file)
        train_stats = (train_handle.load_index()
                       if train_handle is not None else None)
        warm = None
        if objective == "svm" and train_stats is not None:
            n = train_stats.n
            try:
                resolved_layout, hot_n, eval_dense = resolve_stats_knobs(
                    n, train_stats.total_nnz, train_stats.hist)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
            warm = warm_whole(train_handle, train_stats, cfg.train_file,
                              hot_n, eval_dense, t_load)
            if warm is not None:
                ds, winfo, rep = warm
                ingest_reports.append(rep)
                if resolved_layout == "sparse":
                    from cocoa_tpu.data import hybrid as hybrid_mod
                    if ed_spec == "auto":
                        announce_eval(eval_dense, hot_n)
                    layout_split = hybrid_mod.stats_from_counts(
                        extras["hotCols"], train_stats.hist, hot_n,
                        (winfo.residual_max_nnz if hot_n
                         else int(train_stats.max_row_nnz)),
                        n, k, dtype)
                    announce_hot(layout_split, hot_n)

        if warm is None:
            try:
                data = load_libsvm(cfg.train_file, cfg.num_features,
                                   classes=classes_flag)
            except (OSError, ValueError) as e:  # missing file, bad
                # numFeatures, a class count the file contradicts
                print(f"error: {e}", file=sys.stderr)
                return 2
            n = data.n
            if data.num_classes > 1:
                # what runs: dense rows (one class id a row: a class a
                # sublane of the dense kernel, or the classes on the lanes
                # of the block solve where T models outgrow its state
                # tiles) and sparse rows, a padded-CSR rectangle or a
                # stream as the loader's rule says (label sets too, the
                # classes on the lanes of the HBM-state chains).  What does
                # not, yet: a mesh, --accel, checkpoints (refused above and
                # in run_cocoa), and T x d past one chip's HBM
                sets = data.classes.ndim == 2
                lays = resolve_layout(data, cfg.layout, mesh)
                if sets and lays == "dense":
                    print(f"error: the file's rows carry label SETS (up to "
                          f"{data.classes.shape[1]} labels a row), which "
                          f"train on sparse rows, the class axis on the "
                          f"lanes, and --layout={cfg.layout} resolves "
                          f"dense for this file: pass --layout=sparse "
                          f"(docs/DESIGN.md, one-vs-rest)", file=sys.stderr)
                    return 2
                if lays != "dense":
                    from cocoa_tpu.data.sharding import class_pad

                    # the state alone against what the device says it
                    # has (a backend without the counter, the CPU, refuses
                    # nothing here)
                    state = 4.0 * class_pad(data.num_classes) * (
                        3 * cfg.num_features + n)
                    limit = _device_memory_limit()
                    if limit and state > limit:
                        print(f"error: {data.num_classes} classes on "
                              f"sparse rows hold W, two dW (d x T_pad) and "
                              f"alpha (n x T_pad) in one device's memory: "
                              f"{state / 1e9:.3g} GB of the {limit / 1e9:.3g}"
                              f" GB here; T x d past one chip is not "
                              f"carried yet (train the labels in batches: "
                              f"one-vs-rest models are independent)",
                              file=sys.stderr)
                        return 2
                if not quiet:
                    print(f"classes: {data.num_classes} found "
                          f"({list(data.class_values)[:12]}"
                          + (f"; label sets, up to {data.classes.shape[1]} "
                             f"a row" if sets else "")
                          + f"), trained one-vs-rest over the one copy of "
                          f"the rows"
                          + (" (the local solver's line says where the "
                             "class axis rides)" if lays == "dense" else
                             ", the class axis on the lanes"))

            # --hotCols=auto|off|<n>: the hot/cold column split (sparse
            # layout only, data/hybrid.py).  Resolved HERE — against the
            # measured column histogram, with the panel's HBM bytes
            # accounted explicitly — so the run_start manifest records
            # the split the run actually trains on.
            if objective == "svm":
                resolved_layout = resolve_layout(data, cfg.layout, mesh)
                if (extras["hotCols"] is not None
                        and resolved_layout != "sparse"):
                    print("error: --hotCols (the hot/cold column split) "
                          "only applies to the sparse layout",
                          file=sys.stderr)
                    return 2
                if resolved_layout == "sparse":
                    try:
                        hot_n, layout_split = resolve_hot_cols(
                            extras["hotCols"], data, k, dtype)
                    except ValueError as e:
                        print(f"error: {e}", file=sys.stderr)
                        return 2
                    if ed_spec == "auto":
                        # materialize the dense eval twin only when it
                        # fits the HBM budget; otherwise (with a hot
                        # panel) the certificate margins ride the panel
                        # matvec + residual stream (ops/rows.eval_margins)
                        from cocoa_tpu.data.sharding import eval_dense_fits

                        eval_dense = eval_dense_fits(n, cfg.num_features,
                                                     k, dtype)
                        announce_eval(eval_dense, hot_n)
                    announce_hot(layout_split, hot_n)

            try:
                if objective == "svm":
                    # --evalDense: dense eval twin for sparse layouts —
                    # the duality-gap certificate's full margins pass as
                    # one MXU matvec instead of an every-nonzero
                    # w-gather (31% of the rcv1 production round); costs
                    # K*n_shard*d*itemsize HBM
                    snap = cache_snap()
                    ds = shard_dataset(data, k=k, layout=cfg.layout,
                                       dtype=dtype, mesh=mesh,
                                       eval_dense=eval_dense,
                                       hot_cols=hot_n,
                                       cache=train_handle,
                                       rectangle=not cfg.just_cocoa)
                    status = "off"
                    if train_handle is not None:
                        status = populate_whole(
                            train_handle, data, cfg.train_file, snap,
                            t_load)
                    ingest_reports.append(whole_report(
                        cfg.train_file, data,
                        time_mod.perf_counter() - t_load, cache=status))
                else:
                    ingest_reports.append(whole_report(
                        cfg.train_file, data,
                        time_mod.perf_counter() - t_load))
            except (OSError, ValueError) as e:  # e.g. --layout=sparse
                # + --fp>1
                print(f"error: {e}", file=sys.stderr)
                return 2

        if objective == "svm" and cfg.test_file:
            try:
                t_test = time_mod.perf_counter()
                test_handle = whole_handle(cfg.test_file)
                test_stats = (test_handle.load_index()
                              if test_handle is not None else None)
                test_warm = warm_whole(test_handle, test_stats,
                                       cfg.test_file, hot_n, eval_dense,
                                       t_test)
                if test_warm is not None:
                    test_ds, _, rep = test_warm
                    ingest_reports.append(rep)
                else:
                    test_data = load_libsvm(
                        cfg.test_file, cfg.num_features,
                        classes=(None if classes_flag is None
                                 else data.num_classes))
                    if test_data.class_values != data.class_values:
                        raise ValueError(
                            f"{cfg.test_file}: its class labels "
                            f"{test_data.class_values} are not the "
                            f"training file's {data.class_values}")
                    snap = cache_snap()
                    test_ds = shard_dataset(test_data, k=k,
                                            layout=cfg.layout,
                                            dtype=dtype, mesh=mesh,
                                            eval_dense=eval_dense,
                                            hot_cols=hot_n,
                                            cache=test_handle,
                                            rectangle=not cfg.just_cocoa)
                    status = "off"
                    if test_handle is not None:
                        status = populate_whole(
                            test_handle, test_data, cfg.test_file,
                            snap, t_test)
                    ingest_reports.append(whole_report(
                        cfg.test_file, test_data,
                        time_mod.perf_counter() - t_test, cache=status))
            except (OSError, ValueError) as e:
                print(f"error: {e}", file=sys.stderr)
                return 2

    if layout_split is not None:
        cfg_manifest["layout_split"] = layout_split
        run_meta["config_hash"] = telemetry.events.config_hash(cfg_manifest)

    def emit_run_start(ds_solved, local_iters, mode, smoothing=0.0):
        """``run_start`` waits for the last thing it records: the local
        solver the SDCA-family drivers will resolve to for this dataset
        and flag set (solvers/cocoa.resolve_solver_path — the same call
        run_sdca_family makes), so the manifest says which kernel ran.
        ``mode``: the first algorithm's, for the one field that follows
        the algorithm (``SolverPath.margin``; each run's own is on its
        ``Trajectory.meta``)."""
        if not bus.active():
            return
        from cocoa_tpu.solvers.cocoa import resolve_solver_path

        manifest = telemetry.events.run_manifest(cfg_manifest,
                                                 dataset=cfg.train_file)
        if layout_split is not None:
            manifest["layout_split"] = dict(layout_split)
        if ingest_reports:
            # the TRAIN file's ingest record rides the manifest next to
            # layout_split (stats like parse seconds/RSS are run facts,
            # not config — they stay out of the config hash)
            manifest["ingest"] = ingest_reports[0].as_fields()
        manifest["solver_path"] = resolve_solver_path(
            ds_solved, local_iters, mesh, math=cfg.math,
            block_size=block_size, loss=cfg.loss,
        ).for_mode(mode, smoothing).as_dict()
        manifest["vector_len"] = int(ds_solved.num_features)
        bus.emit("run_start", manifest=manifest)
        for rep in ingest_reports:
            bus.emit("ingest", **rep.as_fields())
        for ev_fields in cache_events:
            bus.emit("ingest_cache", **ev_fields)

    params = cfg.to_params(n, k)
    debug = cfg.to_debug()
    gap_target = float(extras["gapTarget"]) if extras["gapTarget"] else None
    if gap_target is not None and dtype == jnp.bfloat16:
        # the duality gap sits below bf16's ~2^-8 relative resolution, so
        # a gap-targeted bf16 run cannot certify (docs/DESIGN.md §6;
        # measured in tests/test_bf16.py) — reject up front with the
        # remedy instead of burning the round budget
        print("error: --gapTarget cannot be certified at --dtype=bfloat16 "
              "(the gap is below bf16 resolution); use --dtype=float32 or "
              "drop --gapTarget", file=sys.stderr)
        return 2
    cfg.device_loop = (
        extras["deviceLoop"] is not None
        and str(extras["deviceLoop"]).lower() != "false"
    )
    if extras["scanChunk"]:
        try:
            cfg.scan_chunk = int(extras["scanChunk"])
        except ValueError:
            print(f"error: --scanChunk must be an integer, got "
                  f"{extras['scanChunk']!r}", file=sys.stderr)
            return 2
    elif not cfg.device_loop and cfg.scan_chunk <= 0:
        # default to device-side blocks at the eval cadence: the math and
        # the observable trajectory are identical to per-round stepping
        # (pinned by tests), but the host-stepped path pays a host↔device
        # dispatch PER ROUND, which costs more than a round's compute on
        # small problems.  Capped so one
        # chunk's (C, K, H) int32 index table stays modest even when
        # debugIter is huge (--scanChunk=1 restores per-round dispatch).
        cap = max(1, 32_000_000 // max(1, k * params.local_iters))
        cfg.scan_chunk = min(cfg.debug_iter if cfg.debug_iter > 0 else 50,
                             cap)
    if cfg.device_loop and cfg.debug_iter <= 0:
        print("error: --deviceLoop requires --debugIter > 0 (the eval "
              "cadence is the device loop's chunk axis)", file=sys.stderr)
        return 2
    # --deviceLoop + --chkptDir/--chkptIter is supported: the device-loop
    # driver saves at its super-block boundaries, every chkptIter rounds
    # rounded up to the debugIter chunk cadence (base.drive_device_full)
    resume = extras["resume"] is not None and str(extras["resume"]).lower() != "false"
    if resume and not cfg.chkpt_dir:
        print("error: --resume requires --chkptDir", file=sys.stderr)
        return 2
    block_auto = (extras["blockSize"] or "").lower() == "auto"
    block_size = 0
    if extras["blockSize"] and not block_auto:
        try:
            block_size = int(extras["blockSize"])
        except ValueError:
            print(f"error: --blockSize must be an integer or 'auto', got "
                  f"{extras['blockSize']!r}", file=sys.stderr)
            return 2
    if block_size < 0:
        print(f"error: --blockSize must be >= 0, got {block_size}",
              file=sys.stderr)
        return 2
    if (block_size or block_auto) and cfg.math != "fast":
        print("error: --blockSize requires --math=fast (the block kernel is "
              "a margins-decomposition variant)", file=sys.stderr)
        return 2
    if ds is not None and block_auto:
        # dense always blocks; sparse blocks only when the in-kernel CSR
        # Gram path fits (a densified sparse block LOSES to the sequential
        # sparse kernel)
        block_size = _resolve_auto_block(ds, mesh, k, dtype, quiet=quiet)

    guard = (extras["divergenceGuard"] or "auto").lower()
    if guard not in ("auto", "on", "off"):
        print(f"error: --divergenceGuard must be auto|on|off, got "
              f"{extras['divergenceGuard']!r}", file=sys.stderr)
        return 2
    if guard == "off" and (
            cfg.sigma == "auto"
            or (sigma_schedule == "anneal" and anneal_engages)):
        # the guard's firing IS the schedule's only exit from a bad σ′
        # guess (trial restart or in-loop anneal backoff alike)
        print("error: --sigma=auto / --sigmaSchedule=anneal require the "
              "divergence guard; drop --divergenceGuard=off",
              file=sys.stderr)
        return 2

    if objective == "lasso":
        # --objective=lasso: ProxCoCoA+ on 0.5||Ax-b||^2 + lambda||x||_1
        # (+ l2/2 ||x||^2), labels as the regression target; A's columns
        # sharded over the workers (data/columns.py)
        if fp > 1:
            print("error: --objective=lasso already shards the feature "
                  "axis over workers; --fp does not apply", file=sys.stderr)
            return 2
        if cfg.test_file:
            print("error: --testFile does not apply to --objective=lasso "
                  "(no classification error to report)", file=sys.stderr)
            return 2
        try:
            l2 = float(extras["l2"]) if extras["l2"] else 0.0
        except ValueError:
            print(f"error: --l2 must be a float, got {extras['l2']!r}",
                  file=sys.stderr)
            return 2
        if l2 < 0.0:
            print(f"error: --l2 is the elastic-net weight, needs >= 0, "
                  f"got {l2}", file=sys.stderr)
            return 2
        from cocoa_tpu.data.columns import shard_columns
        from cocoa_tpu.solvers import run_prox_cocoa

        try:
            ds_c = shard_columns(data, k, dtype=dtype, mesh=mesh,
                                 layout=cfg.layout)
        except ValueError as e:  # e.g. sparse columns + fp mesh
            print(f"error: {e}", file=sys.stderr)
            return 2
        if block_auto:
            block_size = _resolve_auto_block(ds_c, mesh, k, dtype,
                                             quiet=quiet)
        d = data.num_features
        # same H = max(1, localIterFrac·n/K) law, over coordinates
        lasso_params = dataclasses.replace(
            cfg.to_params(d, k), loss="lasso", smoothing=l2,
        )
        emit_run_start(ds_c, lasso_params.local_iters, "prox", l2)
        resume_kw = {}
        if resume:
            from cocoa_tpu import checkpoint as ckpt_lib

            path = ckpt_lib.latest(cfg.chkpt_dir, "ProxCoCoA+")
            if path is not None:
                meta, r0, x0 = ckpt_lib.load(path)
                print(f"resuming ProxCoCoA+ from round {meta['round']} "
                      f"({path})")
                from cocoa_tpu.data.sharding import rows_as_ordered

                # kept by coordinate as built; taken in ds_c's order
                resume_kw = dict(r_init=r0, x_init=rows_as_ordered(ds_c, x0),
                                 start_round=meta["round"] + 1)
        x, r, traj = run_prox_cocoa(
            ds_c, lasso_params, cfg.to_debug(), mesh=mesh, rng=cfg.rng,
            sampling=cfg.sampling, quiet=quiet,
            gap_target=gap_target, scan_chunk=cfg.scan_chunk,
            math=cfg.math, device_loop=cfg.device_loop,
            block_size=block_size, divergence_guard=guard, l2=l2,
            **resume_kw,
        )
        from cocoa_tpu.solvers.prox_cocoa import _metrics_fn

        final = [float(v) for v in
                 _metrics_fn(mesh, cfg.lam, l2)(r, x, ds_c.shard_arrays(),
                                                ds_c.target)]
        traj.meta.update(run_meta)
        traj.summary(final[0], gap=final[1], test_error=None)
        if extras["trajOut"]:
            traj.dump_jsonl(f"{extras['trajOut']}.ProxCoCoA+.jsonl")
        return 0

    emit_run_start(ds, params.local_iters, "plus")

    def restore(algorithm):
        """(w_init, alpha_init, start_round[, sched_init]) from the latest
        checkpoint.  ``sched_init`` (present on --sigmaSchedule/--warmStart
        runs) restores the σ′-schedule stage and stall-watch counters so a
        mid-schedule resume is bit-identical to the uninterrupted run."""
        if not resume:
            return dict()
        import numpy as _np

        from cocoa_tpu import checkpoint as ckpt_lib
        from cocoa_tpu.data.sharding import rows_as_ordered

        path = ckpt_lib.latest(cfg.chkpt_dir, algorithm)
        if path is None:
            return dict()
        meta, arrays = ckpt_lib.load_full(path)
        print(f"resuming {algorithm} from round {meta['round']} ({path})")
        out = dict(w_init=arrays["w"], start_round=meta["round"] + 1)
        # a checkpoint keeps α and the window bank by the rows' positions
        # as built; the solvers take them in the order ds has its rows in
        # (data/sharding.order_rows_by_length; the same until it is ordered)
        if arrays.get("alpha") is not None:
            out["alpha_init"] = rows_as_ordered(ds, arrays["alpha"])
        if meta.get("sched") is not None:
            out["sched_init"] = _np.asarray(meta["sched"], _np.float32)
        if arrays.get("hist") is not None:
            # the --accel secant window bank: restoring it (with the
            # sched accel slots) makes a mid-momentum resume bit-identical
            out["hist_init"] = rows_as_ordered(ds, arrays["hist"])
        return out

    def finish(traj, w, alpha=None):
        gaps = None
        if ds.num_classes > 1:
            # one-vs-rest: the worst class's objectives (what the job was
            # stopped on), the multi-class test error, every class's gap
            primal, gap, err, gaps = objectives.evaluate(
                ds, w, alpha, params.lam, test_ds=test_ds, loss=params.loss,
                smoothing=params.smoothing)
        else:
            primal = objectives.primal_objective(ds, w, params.lam,
                                                 params.loss,
                                                 params.smoothing)
            gap = (
                primal - objectives.dual_objective(ds, w, alpha, params.lam,
                                                   params.loss,
                                                   params.smoothing)
                if alpha is not None
                else None
            )
            err = (
                objectives.classification_error(test_ds, w)
                if test_ds is not None
                else None
            )
        traj.meta.update(run_meta)
        traj.summary(primal, gap=gap, test_error=err)
        if gaps is not None and not quiet:
            print(f" Duality gap by class: {gaps}\n")
        if extras["trajOut"]:
            path = f"{extras['trajOut']}.{traj.algorithm.replace(' ', '_')}.jsonl"
            traj.dump_jsonl(path)

    common = dict(mesh=mesh, test_ds=test_ds, rng=cfg.rng,
                  sampling=cfg.sampling, quiet=quiet)

    # resolve --overlapComm for this process: the checkpoint-write
    # overlap engages only where it is race-free — single process (the
    # multi-process save's alpha allgather is a collective that must not
    # run concurrently with a training dispatch)
    overlap_io = False
    if overlap_flag in ("on", "auto"):
        overlap_io = jax.process_count() == 1 and cfg.device_loop
        if overlap_flag == "on" and not overlap_io:
            # the flag must never pass silently inert (the same
            # loud-behavior principle as the --staleRounds rejection):
            # say exactly which precondition is missing
            if jax.process_count() != 1:
                print("overlapComm: checkpoint-write overlap disabled on "
                      "the multi-process path (the save's alpha allgather "
                      "is a collective); exchanges overlap via the gang "
                      "host-aggregation path instead", file=sys.stderr)
            else:
                print("note: --overlapComm's CLI consumer is the "
                      "device-resident driver's checkpoint-write overlap "
                      "— pass --deviceLoop (the host-stepped path has no "
                      "effect to enable)", file=sys.stderr)

    cocoa_kw = dict(gap_target=gap_target, scan_chunk=cfg.scan_chunk,
                    math=cfg.math, device_loop=cfg.device_loop,
                    block_size=block_size, divergence_guard=guard,
                    sigma_schedule=sigma_schedule, warm_start=warm_start,
                    accel=accel_flag, theta=theta_flag,
                    overlap_io=overlap_io)

    def run_all():
        w, alpha, traj = run_cocoa(ds, params, debug, plus=True,
                                   **cocoa_kw, **restore("CoCoA+"), **common)
        finish(traj, w, alpha)

        w, alpha, traj = run_cocoa(ds, params, debug, plus=False,
                                   **cocoa_kw, **restore("CoCoA"), **common)
        finish(traj, w, alpha)

        if not cfg.just_cocoa:  # hingeDriver.scala:93-110
            loop_kw = dict(scan_chunk=cfg.scan_chunk,
                           device_loop=cfg.device_loop)
            w, alpha, traj = run_minibatch_cd(
                ds, params, debug, math=cfg.math, block_size=block_size,
                divergence_guard=guard, **loop_kw,
                **restore("Mini-batch CD"), **common)
            finish(traj, w, alpha)

            w, traj = run_sgd(ds, params, debug, local=False, **loop_kw,
                              **restore("Mini-batch SGD"), **common)
            finish(traj, w)

            w, traj = run_sgd(ds, params, debug, local=True, **loop_kw,
                              **restore("Local SGD"), **common)
            finish(traj, w)

            w, traj = run_dist_gd(ds, params, debug, mesh=mesh,
                                  test_ds=test_ds, quiet=quiet, **loop_kw,
                                  **restore("Dist SGD"))
            finish(traj, w)

    if profile_window is not None:
        # --profile=DIR,START,STOP: trace only the round window, triggered
        # by the telemetry event stream — on the device-resident driver
        # the io_callback bridge is what makes a mid-while_loop trigger
        # possible at all (telemetry/profiling.py).  The windower is a bus
        # subscriber, which also activates the bus (and with it the
        # device event stream) for the duration of the run.
        from cocoa_tpu.telemetry.profiling import RoundWindowProfiler

        windower = RoundWindowProfiler(profile_dir, *profile_window)
        bus.subscribe(windower)
        try:
            run_all()
        finally:
            windower.close()
            bus.unsubscribe(windower)
            if not quiet:
                print(f"profiler trace of rounds "
                      f"[{profile_window[0]}, {profile_window[1]}) "
                      f"written to {profile_dir}")
    elif profile_dir:
        # --profile=DIR: capture a device trace of the whole run, viewable
        # in TensorBoard/Perfetto (the reference has no profiler at all —
        # SURVEY.md §5 requires one as a debug flag).  try/finally so the
        # trace — the artifact needed to debug a failing run — still flushes
        # when a solver raises.
        from jax import profiler

        profiler.start_trace(profile_dir)
        try:
            run_all()
        finally:
            profiler.stop_trace()
            if not quiet:
                print(f"profiler trace written to {profile_dir}")
    else:
        run_all()

    return 0


def _run_fleet_cli(cfg, extras, quiet, bus, cfg_manifest, fleet_lanes,
                   sigma_schedule, accel_flag, theta_flag):
    """The ``--fleet`` execution path: load + validate the manifest,
    stack the tenants, run the one compiled vmapped round
    (solvers/fleet.py), and report per-tenant certification + the
    models-per-second headline.  Reached from :func:`main` after the
    flag surface is validated; every remaining fleet-specific
    incompatibility is rejected here with a pointer."""
    import numpy as np

    from cocoa_tpu import telemetry
    from cocoa_tpu.data import build_fleet, load_fleet_manifest
    from cocoa_tpu.solvers import run_cocoa_fleet

    if extras["mesh"] and str(extras["mesh"]) != "1":
        print("error: --mesh does not combine with --fleet in v1: fleet "
              "lanes ride the tenant vmap on one chip; the multi-chip "
              "direction is the tenant mesh axis "
              "(parallel/mesh.make_fleet_mesh, docs/DESIGN.md §16)",
              file=sys.stderr)
        return 2
    if extras["fp"] and str(extras["fp"]) != "1":
        print("error: --fp does not combine with --fleet (feature "
              "sharding splits one model's columns; fleet lanes are "
              "whole independent models)", file=sys.stderr)
        return 2
    if cfg.sampling == "device":
        print("error: --sampling=device does not combine with --fleet "
              "(the fleet loop host-samples its stacked index tables "
              "once per run — solvers/fleet.py); use --sampling=auto",
              file=sys.stderr)
        return 2
    if theta_flag == "adaptive":
        print("error: --theta=adaptive does not combine with --fleet "
              "(the Θ ladder slices static index-table widths; fleet "
              "lanes share one table shape — docs/DESIGN.md §16)",
              file=sys.stderr)
        return 2
    if cfg.sigma == "auto" and sigma_schedule == "trial":
        print("error: --sigmaSchedule=trial does not combine with "
              "--fleet (the trial's restart is a solo-path control; "
              "fleets anneal in place — --sigmaSchedule=anneal)",
              file=sys.stderr)
        return 2

    gap_target = None
    if extras["gapTarget"]:
        try:
            gap_target = float(extras["gapTarget"])
        except ValueError:
            print(f"error: --gapTarget must be a float, got "
                  f"{extras['gapTarget']!r}", file=sys.stderr)
            return 2
    accel_on = accel_flag == "on"   # auto resolves OFF for fleets: the
    # plain certified path is the fleet default; opt in explicitly
    anneal_on = (cfg.sigma == "auto"
                 or (sigma_schedule == "anneal"
                     and isinstance(cfg.sigma, float)
                     and 0 < cfg.sigma < cfg.num_splits * cfg.gamma))
    if accel_on and anneal_on:
        print("error: --accel does not combine with --sigma=auto/"
              "--sigmaSchedule=anneal on --fleet (fleet accel rides the "
              "fixed safe σ′; drop one of the two)", file=sys.stderr)
        return 2
    drive_mode = ("accel" if accel_on
                  else "anneal" if anneal_on else "plain")

    try:
        specs = load_fleet_manifest(extras["fleet"])
        fleet = build_fleet(specs, k=cfg.num_splits,
                            dtype=jnp.dtype(cfg.dtype),
                            local_iter_frac=cfg.local_iter_frac,
                            default_gap_target=gap_target)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if fleet.loss not in ("hinge", "smooth_hinge"):
        print(f"error: fleet v1 runs the hinge family only (manifest "
              f"loss {fleet.loss!r}); the logistic dual rule divides by "
              f"λn in a way the traced-λ lane cannot mirror bit-exactly "
              f"(docs/DESIGN.md §16)", file=sys.stderr)
        return 2
    if cfg.loss != "hinge" and cfg.loss != fleet.loss:
        print(f"error: the fleet's loss comes from the manifest "
              f"({fleet.loss!r}); drop --loss={cfg.loss} or make them "
              f"agree", file=sys.stderr)
        return 2

    if bus.active():
        manifest = telemetry.events.run_manifest(cfg_manifest,
                                                 dataset=extras["fleet"])
        manifest["fleet"] = {"tenants": fleet.t, "k": fleet.k,
                             "n_shard": fleet.n_shard,
                             "d": fleet.num_features,
                             "h": fleet.local_iters,
                             "drive_mode": drive_mode,
                             "lane_exec": fleet_lanes}
        bus.emit("run_start", manifest=manifest)

    params = dataclasses.replace(
        cfg.to_params(0, fleet.k), local_iters=fleet.local_iters,
        loss=fleet.loss, smoothing=fleet.smoothing)
    debug = cfg.to_debug()
    try:
        result = run_cocoa_fleet(
            fleet, params, debug, plus=True, drive_mode=drive_mode,
            rng=cfg.rng, math=cfg.math, lane_exec=fleet_lanes,
            quiet=quiet)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    certified = int(result.certified.sum())
    if bus.active():
        bus.emit("run_end", algorithm=result.algorithm,
                 stopped=("target" if certified == fleet.t else None))
    if not quiet:
        # one host array fetch BEFORE the loop (the fleet-hygiene rule:
        # never a per-tenant device fetch inside a tenant loop)
        gaps = np.asarray(result.final_gap)
        rounds = np.asarray(result.cert_round)
        for ti, tenant in enumerate(result.tenants):
            status = (f"certified @ round {int(rounds[ti])}"
                      if result.certified[ti]
                      else "DIVERGED (stall watch)" if result.stalled[ti]
                      else "not certified")
            print(f"  {tenant}: lambda={fleet.lams[ti]:g} "
                  f"gap={gaps[ti]:.3e} {status}")
        print(f"fleet: {certified}/{fleet.t} tenants certified, "
              f"{result.rounds_run} rounds, {result.wall_s:.2f}s, "
              f"{result.models_per_second:.1f} models/s "
              f"(drive_mode={drive_mode}, lanes={fleet_lanes})")
    if extras["trajOut"]:
        import json as _json

        path = f"{extras['trajOut']}.fleet.jsonl"
        with open(path, "w") as f:
            f.write(_json.dumps({
                "config": "fleet", "type": "fleet",
                "tenants": fleet.t, "certified": certified,
                "rounds": int(result.rounds_run),
                "models_per_second": result.models_per_second,
                "stopped": ("target" if certified == fleet.t else None),
            }) + "\n")
            gaps = np.asarray(result.final_gap)
            rounds = np.asarray(result.cert_round)
            for ti, tenant in enumerate(result.tenants):
                f.write(_json.dumps({
                    "config": f"fleet/{tenant}", "type": "fleet-tenant",
                    "lam": float(fleet.lams[ti]),
                    "gap": float(gaps[ti]),
                    "rounds": int(rounds[ti]) or int(result.rounds_run),
                    "stopped": ("target" if result.certified[ti]
                                else None),
                }) + "\n")
    return 0


def _run_serve_fleet(cfg, extras, quiet, bus, port, buckets, sla_ms,
                     max_nnz, serve_dtype, n_replicas, route,
                     algorithm, n_tenants, trace_sample=0,
                     status_port=None):
    """The ``--serveReplicas>=2`` execution path (docs/DESIGN.md §21):
    spawn N ordinary single-process serve replicas against the same
    validated --chkptDir (each hot-swaps independently; slabs and
    checkpoints share the host page cache, so RSS stays ~one copy),
    put the router front door on the requested port, and relay the
    line protocol until ``shutdown`` or SIGTERM.  The front door holds
    no model and no JAX — replica death is a requeue, never a failed
    query, and the monitor respawns the dead.

    Tracing and the ops plane (docs/DESIGN.md §22) both live at the
    front door: the ROUTER samples ``trace=``-prefixed lines (it sees
    the whole lifecycle — queue, forward, requeues), and the
    ``--statusPort`` plane scrapes the front door's textfile plus every
    replica's ``.r<i>`` slot file with the router's own liveness map."""
    import signal

    from cocoa_tpu.serving.fleet import ServeFleet
    from cocoa_tpu.serving.router import Router

    rep_argv = [f"--chkptDir={cfg.chkpt_dir}",
                f"--numFeatures={cfg.num_features}",
                "--serveBatch=" + ",".join(str(b) for b in buckets),
                f"--serveSlaMs={sla_ms:g}",
                f"--serveMaxNnz={max_nnz}",
                f"--serveDtype={serve_dtype}", "--quiet"]
    # per-replica telemetry sinks ride the front door's --events and
    # --metrics paths with an .r<i> suffix — how the smoke counts
    # compiles per replica, and how the ops plane attributes merged
    # /metrics samples.  The suffix is the replica's SLOT: a respawn
    # reuses index i, so the new process inherits (atomically
    # overwrites) the dead one's files — two writers never interleave
    ev_path = extras["events"]
    metrics_path = extras["metrics"]
    extra_fn = None
    if ev_path or metrics_path:
        def extra_fn(i):
            argv = []
            if ev_path:
                argv.append(f"--events={ev_path}.r{i}")
            if metrics_path:
                argv.append(f"--metrics={metrics_path}.r{i}")
            return argv

    def echo(s):
        # replica pid/port notes are operational plumbing (the smoke
        # parses them for the SIGKILL drill) — printed even under
        # --quiet, like the announce line
        print(f"serve: {s}", flush=True)

    fleet = ServeFleet(rep_argv, n_replicas, extra_argv_fn=extra_fn,
                       echo=echo)
    try:
        members = fleet.start()
    except RuntimeError as e:
        fleet.stop()
        print(f"error: {e}", file=sys.stderr)
        return 1
    router = Router(members, sla_s=sla_ms / 1000.0, route=route,
                    port=port, algorithm=algorithm,
                    trace_sample=trace_sample)
    fleet.attach(router)
    router.emit_initial_state()
    host, bound = router.address[0], router.address[1]
    catalogue = ("" if n_tenants is None
                 else f", tenants={n_tenants}")
    print(f"serve: fleet listening on {host}:{bound} "
          f"(replicas={n_replicas}, route={route}, "
          f"buckets={','.join(str(b) for b in buckets)}, "
          f"slaMs={sla_ms:g}, maxNnz={max_nnz}, dtype={serve_dtype}"
          f"{catalogue})", flush=True)

    writer = getattr(bus, "metrics_writer", None)
    if writer is not None:
        writer.start_heartbeat(5.0)

    # --statusPort: the fleet ops plane — scrape the front door's own
    # textfile plus every replica's .r<i> slot file, with the router's
    # live map driving /healthz (a SIGKILLed replica shows live=false
    # until the monitor's respawn re-registers it)
    status = None
    if status_port is not None:
        from cocoa_tpu.telemetry.aggregate import StatusServer

        def _sources():
            out = {"router": metrics_path}
            for i in range(n_replicas):
                out[f"r{i}"] = f"{metrics_path}.r{i}"
            return out

        status = StatusServer(
            _sources, sla_s=sla_ms / 1000.0, port=status_port,
            algorithm=algorithm,
            liveness_fn=lambda: {r.name: r.live
                                 for r in router.replicas}).start()
        print(f"serve: status listening on "
              f"{status.address[0]}:{status.address[1]}", flush=True)

    def _stop(signum, frame):
        router.stop()

    prev = [signal.signal(signal.SIGTERM, _stop),
            signal.signal(signal.SIGINT, _stop)]
    try:
        router.serve_forever()
    finally:
        signal.signal(signal.SIGTERM, prev[0])
        signal.signal(signal.SIGINT, prev[1])
        if status is not None:
            status.stop()
        if writer is not None:
            writer.stop_heartbeat()
        fleet.stop()
        router.close()
    if bus.active():
        bus.emit("run_end", algorithm=algorithm, stopped="shutdown")
    if not quiet:
        print(f"serve: fleet shut down after {router.forwarded_total} "
              f"forwarded line(s), {router.shed_total} shed, "
              f"{router.requeue_total} requeued, "
              f"{router.failed_total} failed")
    return 0


def _run_serve_cli(cfg, extras, quiet, bus, cfg_manifest, serve_flag):
    """The ``--serve`` execution path (cocoa_tpu/serving/,
    docs/DESIGN.md §17): wait for the first VALIDATED checkpoint
    generation, build the compiled bucket scorer + double-buffered model
    slots, start the hot-swap watcher and the adaptive micro-batcher,
    and answer margin queries on a TCP line protocol until ``shutdown``
    (protocol line) or SIGTERM/SIGINT.  Reached from :func:`main` after
    the whitelist hardening; every remaining rejection here carries the
    numbers."""
    import signal

    import numpy as np

    from cocoa_tpu import serving, telemetry
    from cocoa_tpu.telemetry import tracing

    # --serve=PORT: 0 (or bare --serve) binds an ephemeral port and
    # announces it — what the smoke tests parse
    try:
        port = 0 if str(serve_flag).lower() == "true" else int(serve_flag)
    except ValueError:
        port = -1
    if port < 0 or port > 65535:
        print(f"error: --serve takes a TCP port (0 = ephemeral), got "
              f"{serve_flag!r}", file=sys.stderr)
        return 2
    buckets = serving.DEFAULT_BUCKETS
    if extras["serveBatch"]:
        try:
            buckets = tuple(sorted({int(b) for b in
                                    str(extras["serveBatch"]).split(",")}))
            if not buckets or buckets[0] < 1 or buckets[-1] > 8192:
                raise ValueError
        except ValueError:
            print(f"error: --serveBatch takes ascending bucket sizes in "
                  f"[1, 8192] (e.g. 64,256,1024), got "
                  f"{extras['serveBatch']!r}", file=sys.stderr)
            return 2
    sla_ms = 50.0
    if extras["serveSlaMs"]:
        try:
            sla_ms = float(extras["serveSlaMs"])
        except ValueError:
            sla_ms = -1.0
        if sla_ms <= 0:
            print(f"error: --serveSlaMs takes a positive latency budget "
                  f"in ms, got {extras['serveSlaMs']!r}", file=sys.stderr)
            return 2
    # --serveDtype: the serving precision (docs/DESIGN.md §20) — the
    # model is quantized ONCE per swap with a margin-error certificate;
    # queries and the compiled reduction stay f32
    serve_dtype = "f32"
    if extras["serveDtype"]:
        try:
            serve_dtype = serving.resolve_serve_dtype(
                extras["serveDtype"])
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    # --serveReplicas/--serveRoute (validated in main(), parsed again
    # here): >= 2 switches to the fleet branch — a router front door
    # over N spawned single-process replicas (docs/DESIGN.md §21)
    n_replicas = (int(extras["serveReplicas"])
                  if extras["serveReplicas"] else 1)
    route = extras["serveRoute"] or "rr"
    # --traceSample=N: 1 in N trace=-prefixed lines gets a sampled
    # query trace (docs/DESIGN.md §22); 0 disarms — the prefix is
    # peeled and answers stay byte-identical.  Bare --traceSample is
    # the documented default of 64.
    trace_sample = 0
    if extras["traceSample"]:
        raw = str(extras["traceSample"])
        try:
            trace_sample = 64 if raw.lower() == "true" else int(raw)
        except ValueError:
            trace_sample = -1
        if trace_sample < 0:
            print(f"error: --traceSample takes a sampling divisor "
                  f">= 0 (1 in N traced; 0 = off; bare flag = 64), "
                  f"got {extras['traceSample']!r}", file=sys.stderr)
            return 2
    # --statusPort=PORT (0/bare = ephemeral): the live ops plane
    # (telemetry/aggregate.py, docs/DESIGN.md §22) — /metrics /healthz
    # /slo over the metrics textfiles the serve processes write
    status_port = None
    if extras["statusPort"] is not None:
        raw = str(extras["statusPort"])
        try:
            status_port = 0 if raw.lower() == "true" else int(raw)
        except ValueError:
            status_port = -1
        if status_port < 0 or status_port > 65535:
            print(f"error: --statusPort takes a TCP port (0 = "
                  f"ephemeral), got {extras['statusPort']!r}",
                  file=sys.stderr)
            return 2
        if not extras["metrics"]:
            print("error: --statusPort serves the ops plane by "
                  "scraping the metrics textfile(s) and needs "
                  "--metrics", file=sys.stderr)
            return 2

    d = cfg.num_features
    dtype = jnp.dtype(cfg.dtype)
    algorithm = "CoCoA+"   # the production trainer's checkpoint key

    # optional hybrid query path: resolve the TRAINED hot/cold column
    # split from the training data's histogram, exactly like the trainer
    # does — queries then ride the same panel+residual kernels.  The
    # training data is parsed ONLY when --hotCols asks for the split (a
    # --trainFile alone would pay a full LIBSVM parse for nothing).
    hot_ids = None
    max_nnz = min(serving.DEFAULT_MAX_NNZ, d)
    if cfg.train_file and extras["hotCols"] is not None:
        from cocoa_tpu.data import hybrid as hybrid_lib

        try:
            data = load_libsvm(cfg.train_file, d)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        # queries are not training rows — the data's max row nnz only
        # ever RAISES the default budget, never tightens it
        max_nnz = min(d, max(max_nnz, int(data.max_nnz)))
        counts = hybrid_lib.column_counts(data)
        try:
            hot_n = hybrid_lib.resolve_hot_width(
                extras["hotCols"], counts, data.n, 1, dtype)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if hot_n:
            hot_ids = hybrid_lib.hottest_columns(counts, hot_n)
            if not quiet:
                print(f"serve: hot panel over {hot_n} columns — "
                      f"queries ride panel + residual")
    if extras["serveMaxNnz"]:
        try:
            max_nnz = int(extras["serveMaxNnz"])
        except ValueError:
            max_nnz = 0
        if max_nnz < 1:
            print(f"error: --serveMaxNnz takes a positive per-query "
                  f"nonzero budget, got {extras['serveMaxNnz']!r}",
                  file=sys.stderr)
            return 2
        max_nnz = min(max_nnz, d)

    path = serving.wait_for_model(cfg.chkpt_dir, algorithm,
                                  timeout_s=300.0, quiet=quiet)
    if path is None:
        print(f"error: no validated {algorithm} checkpoint appeared in "
              f"{cfg.chkpt_dir} within 300s — is the background trainer "
              f"running with --chkptDir pointed here?", file=sys.stderr)
        return 1
    w, info = serving.load_model(path)
    w = np.asarray(w)
    # the trained width may exceed --numFeatures by lane padding (the
    # loader pads d up; the pad columns carry no data, so their w slots
    # are inert) — queries only ever gather ids < numFeatures.  A model
    # NARROWER than the query surface is a real mismatch.  A 2-D (T, d)
    # checkpoint is a served CATALOGUE of T tenant models (the fleet
    # trainer's stacked w, docs/DESIGN.md §21): queries then carry a
    # tenant=<id>; prefix and the width rule applies to each row.
    n_tenants = int(w.shape[0]) if w.ndim == 2 else None
    if w.ndim not in (1, 2) or w.shape[-1] < d \
            or (w.ndim == 2 and w.shape[0] < 1):
        print(f"error: the serving checkpoint {path} carries w of shape "
              f"{tuple(w.shape)} but --numFeatures={d} — the query "
              f"width must fit inside the trained width, as a (d,) "
              f"model or a (T, d) tenant catalogue (fix the flag "
              f"or point --chkptDir at the right model)",
              file=sys.stderr)
        return 2
    if n_tenants is not None and serve_dtype != "f32":
        print(f"error: --serveDtype={serve_dtype} does not combine "
              f"with a (T, d) tenant catalogue (this checkpoint: "
              f"{tuple(w.shape)}): per-tenant quantization "
              f"certificates are not in the fleet v1 surface — serve "
              f"the catalogue at f32 (docs/DESIGN.md §21)",
              file=sys.stderr)
        return 2
    if n_tenants is not None and hot_ids is not None:
        print(f"error: --hotCols does not combine with a (T, d) tenant "
              f"catalogue (this checkpoint: {tuple(w.shape)}): "
              f"per-tenant hot panels are not in the fleet v1 surface "
              f"(docs/DESIGN.md §21)", file=sys.stderr)
        return 2

    if bus.active():
        manifest = telemetry.events.run_manifest(cfg_manifest,
                                                 dataset=cfg.chkpt_dir)
        manifest["serve"] = {
            "algorithm": algorithm, "buckets": list(buckets),
            "sla_ms": sla_ms, "max_nnz": max_nnz, "num_features": d,
            "hot_cols": 0 if hot_ids is None else int(len(hot_ids)),
            "serve_dtype": serve_dtype, "replicas": n_replicas,
            "route": route,
            "tenants": 0 if n_tenants is None else n_tenants,
        }
        bus.emit("run_start", manifest=manifest)

    if n_replicas >= 2:
        return _run_serve_fleet(cfg, extras, quiet, bus, port, buckets,
                                sla_ms, max_nnz, serve_dtype,
                                n_replicas, route, algorithm,
                                n_tenants, trace_sample, status_port)

    # the calibration ring the per-swap certificate is computed over:
    # warmup-seeded now, refilled by real traffic as it arrives
    calib = (serving.CalibrationBuffer(d, max_nnz=max_nnz,
                                       seed=cfg.seed)
             if serve_dtype != "f32" else None)
    slots = serving.ModelSlots(w, info, dtype=serve_dtype,
                               calibration=calib, algorithm=algorithm)
    scorer = serving.BatchScorer(d, dtype=serve_dtype, buckets=buckets,
                                 max_nnz=max_nnz, hot_ids=hot_ids,
                                 model_width=int(w.shape[-1]),
                                 n_tenants=n_tenants)
    serving.watcher.emit_model_swap(algorithm, info)   # the initial load
    with tracing.span("serve_warmup", buckets=len(buckets)):
        w_dev, scale, _ = slots.current()
        n_exec = scorer.warmup(w_dev, scale)
    if not quiet:
        print(f"serve: model {algorithm} r{info.round} "
              f"(gap={info.gap if info.gap is not None else 'n/a'}) — "
              f"{n_exec} bucket executables compiled, swaps are "
              f"compile-free from here")
        if serve_dtype != "f32":
            print(f"serve: quantized to {slots.served_dtype} at load "
                  f"(serveDtype={serve_dtype}, margin error bound "
                  f"{slots.last_bound:.3g} over the warmup calibration "
                  f"batch)" if slots.served_dtype != "f32" else
                  f"serve: certificate fallback at load — the "
                  f"{serve_dtype} margin error bound "
                  f"{slots.last_bound:.3g} could flip a calibrated "
                  f"sign; serving f32 until a generation certifies",
                  flush=True)

    batcher = serving.MicroBatcher(scorer, slots, sla_s=sla_ms / 1000.0,
                                   algorithm=algorithm,
                                   calibration=calib)

    def note_swap(inf):
        if not quiet:
            print(f"serve: hot-swapped to r{inf.round} "
                  f"(gap={inf.gap if inf.gap is not None else 'n/a'}, "
                  f"swap #{inf.seq})", flush=True)

    watcher = serving.SwapWatcher(slots, cfg.chkpt_dir, algorithm,
                                  poll_s=0.25, on_swap=note_swap).start()
    server = serving.MarginServer(batcher, d, max_nnz, port=port,
                                  n_tenants=n_tenants,
                                  trace_sample=trace_sample,
                                  algorithm=algorithm)
    host, bound = server.address[0], server.address[1]
    # the announce line is operational plumbing (the smoke parses it),
    # not chatter — it prints even under --quiet
    catalogue = ("" if n_tenants is None
                 else f", tenants={n_tenants}")
    print(f"serve: listening on {host}:{bound} "
          f"(buckets={','.join(str(b) for b in buckets)}, "
          f"slaMs={sla_ms:g}, maxNnz={max_nnz}, dtype={serve_dtype}"
          f"{catalogue})", flush=True)

    # gap-age heartbeat: the freshness gauge renders `now - birth` at
    # WRITE time, and writes are otherwise event-driven — a dead trainer
    # plus an idle server (the exact alert scenario) would freeze the
    # textfile.  A periodic unconditional rewrite keeps it climbing.
    writer = getattr(bus, "metrics_writer", None)
    if writer is not None:
        writer.start_heartbeat(5.0)

    # --statusPort: the solo ops plane — one source (this process's
    # own textfile), no router liveness to merge
    status = None
    if status_port is not None:
        from cocoa_tpu.telemetry.aggregate import StatusServer

        metrics_path = extras["metrics"]
        status = StatusServer(lambda: {"server": metrics_path},
                              sla_s=sla_ms / 1000.0, port=status_port,
                              algorithm=algorithm).start()
        print(f"serve: status listening on "
              f"{status.address[0]}:{status.address[1]}", flush=True)

    def _stop(signum, frame):
        server.stop()

    prev = [signal.signal(signal.SIGTERM, _stop),
            signal.signal(signal.SIGINT, _stop)]
    try:
        server.serve_forever()
    finally:
        signal.signal(signal.SIGTERM, prev[0])
        signal.signal(signal.SIGINT, prev[1])
        if status is not None:
            status.stop()
        if writer is not None:
            writer.stop_heartbeat()
        watcher.stop()
        batcher.stop()
        server.close()
    if bus.active():
        bus.emit("run_end", algorithm=algorithm, stopped="shutdown")
    if not quiet:
        print(f"serve: shut down after {batcher.requests_total} "
              f"request(s) in {batcher.batches_total} batch(es), "
              f"{watcher.swaps_total} hot-swap(s), final gap age "
              f"{slots.gap_age_s():.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
