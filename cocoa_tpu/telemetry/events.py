"""The host-side event bus and the device→host event bridge.

One process-global :class:`EventBus` carries every run's structured
telemetry: typed records appended to a JSONL sink (one JSON object per
line, ``seq``-ordered) and fanned out synchronously to subscribers (the
metrics writer, the round-windowed profiler, tests).  The bus is inert
until configured — ``emit`` on an inactive bus is a no-op costing one
attribute read, so the training hot paths carry no telemetry tax by
default.

The device bridge: the device-resident driver (solvers/base.py
``drive_on_device``) computes one ``[primal, gap, test_err, sigma_stage,
stall]`` row per eval inside its ``lax.while_loop``.  With the bus
active, an **ordered** ``jax.experimental.io_callback`` posts each row to
:func:`_device_sink` WHILE THE LOOP IS STILL ON DEVICE — the host sees
``round_eval`` (and decoded ``sigma_backoff``) events live, in eval
order.  Where ordered callbacks are unavailable (probed once per process
by :func:`io_callback_supported`), the driver replays the SAME rows
through the SAME :class:`DeviceTap` from its end-of-run fetch — the
fallback emits bit-identical events, just late.  Either way the callback
only reads values the loop already computes: the loop-carried state is
untouched, so telemetry cannot perturb the run.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import math
import os
import threading
import time

import numpy as np

EVENT_TYPES = (
    "run_start",        # manifest: full config + config hash + jax/device info
    "round_eval",       # one debugIter-cadence evaluation
    "sigma_backoff",    # the σ′ anneal schedule backed off a stage
    "checkpoint_write", # a round-stamped checkpoint landed on disk
    "restart",          # sigma=auto trial rerun, or an elastic gang restart
    "divergence",       # the stall watch bailed the run out
    "run_end",          # final summary (primal, gap, stopped reason)
    "compile",          # one finished XLA compile (analysis/sanitize.py
                        # bridge) — the compile-once invariant, observable
    "host_transfer",    # one sanctioned device→host fetch (intended_fetch)
    "momentum_restart", # --accel: a gap rise reset the outer momentum
    "theta_stage",      # --accel: the Θ local-accuracy ladder stepped up
    "ingest",           # one loaded LIBSVM file (data/ingest.IngestReport:
                        # mode, parse seconds, bytes read, rows/nnz this
                        # process materialized, peak host RSS, and the
                        # --ingestCache outcome: off|hit|partial|miss)
    "ingest_cache",     # one file's --ingestCache outcome in detail
                        # (data/slab_cache.py, docs/DESIGN.md §18):
                        # shards served warm vs total, bytes mapped,
                        # seconds the cache saved — what feeds
                        # cocoa_ingest_cache_hits_total /
                        # cocoa_ingest_cache_bytes
    "ingest_cache_corrupt",  # a cache artifact failed validation on
                        # load (torn/truncated/drifted file): the
                        # artifact is evicted and the shard falls back
                        # to a cold parse — never a crash, never a
                        # silently wrong slab
    "gang_resize",      # the elastic supervisor reformed the gang at
                        # P′ < P survivors (shrink-to-survivors,
                        # cocoa_tpu/elastic.py, docs/DESIGN.md §13)
    "checkpoint_corrupt",  # a checkpoint generation failed validation on
                        # load; the reader fell back to the previous one
                        # (checkpoint.latest)
    "span",             # one closed tracing span (telemetry/tracing.py):
                        # phase + worker + wall start + monotonic
                        # duration + call-site attributes — what
                        # trace_report.py assembles into the gang
                        # timeline / critical path / straggler table
    "events_rotate",    # the JSONL sink hit its size cap and rolled the
                        # full file to `<path>.1` (first event of the
                        # fresh file, so the rotation itself is in the
                        # machine-readable record)
    "comm_overlap",     # one joined overlapped exchange (--overlapComm,
                        # parallel/distributed.ExchangeHandle): hidden_s
                        # = exchange wall-clock that ran concurrently
                        # with the caller's compute, wait_s = the
                        # residual blocking wait at the join barrier
    "stale_join",       # a bounded-staleness contribution joined late
                        # (--staleRounds, solvers/cocoa.StaleJoinWindow):
                        # round r's Δw applied at round t = r +
                        # rounds_late, rounds_late <= S by construction
    "fleet_progress",   # one fleet eval boundary (--fleet,
                        # solvers/fleet.py): live tenant lanes +
                        # cumulative certifications; the final event of a
                        # fleet run also carries models_per_second —
                        # what feeds cocoa_fleet_tenants_active /
                        # cocoa_fleet_models_per_second
    "tenant_certified", # one tenant crossed its duality-gap target
                        # inside the fleet's vmapped loop — what feeds
                        # cocoa_tenants_certified_total
    "serve_request",    # one scored serving batch (--serve,
                        # serving/batcher.py): n real requests, the
                        # static bucket they padded into, fill ratio,
                        # queue vs device seconds, per-request latency
                        # max/mean, and the model round that answered —
                        # what feeds cocoa_serve_qps /
                        # cocoa_serve_latency_seconds /
                        # cocoa_serve_batch_fill_ratio
    "model_swap",       # the serving watcher published a new validated
                        # checkpoint generation into the live model slot
                        # (serving/watcher.py): round, path, certified
                        # gap, and the certificate's birth timestamp —
                        # what anchors cocoa_model_gap_age_seconds
    "model_quantize",   # one --serveDtype publish decision
                        # (serving/scorer.ModelSlots._publish): the
                        # configured serve dtype, the form actually
                        # published (== serve dtype, or f32 on a
                        # certificate fallback), the measured
                        # f32-vs-quantized margin-error bound over the
                        # calibration batch, its size, and the int8
                        # scale — what feeds
                        # cocoa_serve_margin_error_bound /
                        # cocoa_serve_dtype_fallbacks_total
    "serve_shed",       # the fleet router refused one request line at
                        # admission (serving/router.py): routing
                        # policy, the tenant (None when untagged), the
                        # best live replica's inflight depth and
                        # projected wait vs the SLA — what feeds
                        # cocoa_serve_shed_total
    "replica_state",    # one fleet replica liveness transition
                        # (serving/router.py / fleet.py): replica name,
                        # state (live / dead / requeue), live count
                        # after the transition, and whether a request
                        # line was requeued by it — what feeds
                        # cocoa_serve_replicas_live /
                        # cocoa_serve_requeue_total
    "query_trace",      # one sampled end-to-end query trace
                        # (--traceSample, docs/DESIGN.md §22): the
                        # client-chosen trace id plus per-hop seconds —
                        # router queue, forward (network + relay),
                        # replica admission queue, device dispatch,
                        # protocol parse/serialize — stamped with the
                        # answering model generation, its gap age, the
                        # serving dtype, the bucket, and how many times
                        # the line requeued.  Emitted by the router in
                        # fleet mode (it sees the whole lifecycle) and
                        # by the solo server otherwise — what feeds
                        # cocoa_query_traces_total and what
                        # trace_report --queries assembles into the
                        # per-hop waterfall
    "slo_status",       # one /slo evaluation (telemetry/aggregate.py):
                        # rolling SLA attainment over the fleet-wide
                        # latency histogram plus the fast/slow
                        # multi-window burn rates against the
                        # attainment objective — the ops plane's
                        # machine-readable answer to "is the fleet
                        # inside its SLA right now"
)


def _clean(v):
    """JSON-safe scalars: numpy numerics → python, NaN → None (JSON has no
    NaN; a NaN metric means 'not applicable' everywhere in this codebase)."""
    if isinstance(v, np.ndarray) and v.ndim == 0:
        v = v.item()
    if isinstance(v, np.floating):
        v = float(v)
    if isinstance(v, np.integer):
        v = int(v)
    if isinstance(v, float) and math.isnan(v):
        return None
    if isinstance(v, dict):
        return {k: _clean(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_clean(x) for x in v]
    return v


class EventBus:
    """Ordered, typed event stream with a JSONL sink and subscribers.

    ``emit`` is thread-safe: the device bridge fires from the runtime's
    callback thread while the main thread blocks on the run's host fetch.
    Subscriber callbacks run inline under the lock — they must be cheap
    (the metrics writer's atomic rewrite is ~µs at these event rates).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self.jsonl_path = None
        self.metrics_path = None
        self.metrics_writer = None   # the MetricsWriter configure()
        # attached (None otherwise) — owners that need more than the
        # subscriber protocol (the serving loop's gap-age heartbeat)
        # reach it here instead of poking _subscribers
        self.max_bytes = None
        self._subscribers = []
        self._seq = 0

    def configure(self, jsonl_path=None, metrics_path=None,
                  max_bytes=None, metrics_interval_s=0.0):
        """Attach sinks; either may be None.  The metrics path attaches a
        :class:`cocoa_tpu.telemetry.metrics.MetricsWriter` subscriber
        (``metrics_interval_s`` is its write-debounce window).  Any
        active sink also installs the compile→event bridge, so
        ``compiles_total``/``compile`` events come for free on telemetry
        runs (the sanitizer invariants, observable in production).

        ``max_bytes`` (``--eventsMaxMB``): size cap on the JSONL sink —
        when an append pushes the file past it, the full file atomically
        rolls to ``<path>.1`` (replacing any previous rollover) and the
        fresh file opens with a typed ``events_rotate`` event, so a
        long serving/elastic run holds at most ~2× the cap on disk
        instead of growing without bound."""
        with self._lock:
            self.jsonl_path = jsonl_path or None
            if max_bytes is not None:
                self.max_bytes = int(max_bytes) or None
            if metrics_path and metrics_path != self.metrics_path:
                from cocoa_tpu.telemetry.metrics import MetricsWriter

                self.metrics_writer = MetricsWriter(
                    metrics_path, flush_interval_s=metrics_interval_s)
                self.subscribe(self.metrics_writer)
                self.metrics_path = metrics_path
        if self.active():
            from cocoa_tpu.analysis import sanitize

            sanitize.install_compile_events(self)
        return self

    def active(self) -> bool:
        return bool(self.jsonl_path or self._subscribers)

    def subscribe(self, fn):
        with self._lock:
            self._subscribers.append(fn)
        return fn

    def unsubscribe(self, fn):
        with self._lock:
            if fn in self._subscribers:
                self._subscribers.remove(fn)

    def reset(self):
        """Detach every sink and zero the sequence (tests)."""
        with self._lock:
            self.jsonl_path = None
            self.metrics_path = None
            if self.metrics_writer is not None:
                self.metrics_writer.stop_heartbeat()
            self.metrics_writer = None
            self.max_bytes = None
            self._subscribers = []
            self._seq = 0

    def emit(self, event: str, **fields):
        """Append one typed record; returns it (or None when inactive).

        The record is sanitized ONCE (numpy scalars → python, NaN → None)
        so the JSONL line and every subscriber see identical values — the
        io_callback-path vs fetch-fallback parity the tests pin rests on
        this single normalization point."""
        if not self.active():
            return None
        if event not in EVENT_TYPES:
            raise ValueError(f"unknown event type {event!r}; "
                             f"expected one of {EVENT_TYPES}")
        reserved = {"event", "seq", "pid", "ts"} & fields.keys()
        if reserved:
            # a payload field named like the envelope would silently
            # overwrite it — the model_swap 'seq' collision class of bug
            raise ValueError(f"event field(s) {sorted(reserved)} collide "
                             f"with the record envelope; rename them")
        with self._lock:
            self._seq += 1
            # pid identifies the EMITTER: a supervised run interleaves
            # several processes' appends (elastic supervisor + worker
            # generations, each with its own seq counter) in one JSONL,
            # and the schema checker orders per emitter
            rec = {"event": event, "seq": self._seq, "pid": os.getpid(),
                   "ts": time.time(),
                   **{k: _clean(v) for k, v in fields.items()}}
            rotated = None
            if self.jsonl_path:
                # open-append per event: whole-line writes interleave
                # safely with other emitters of the same file (the elastic
                # supervisor appends restart events between generations)
                with open(self.jsonl_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                    size = f.tell()
                if (self.max_bytes and size >= self.max_bytes
                        and event != "events_rotate"):
                    rotated = self._rotate(size)
            for fn in list(self._subscribers):
                fn(rec)
            if rotated is not None:
                for fn in list(self._subscribers):
                    fn(rotated)
        return rec

    def _rotate(self, size: int):
        """Roll the full JSONL sink to ``<path>.1`` (atomic rename,
        replacing any previous rollover — the cap bounds disk at ~2×,
        it does not archive history) and open the fresh file with a
        typed ``events_rotate`` record.  Caller holds the lock.

        Concurrent emitters: each shared file has exactly ONE rotating
        owner (cli.py arms ``max_bytes`` on the workers only — the
        supervisor appends to worker 0's file uncapped), so the re-stat
        below is a belt-and-suspenders guard, not the coordination
        mechanism: if the file on disk is already below the cap, some
        other process rotated between our append and now — renaming
        again would clobber the just-archived ``.1`` with a near-empty
        fresh file."""
        rolled = self.jsonl_path + ".1"
        try:
            if os.path.getsize(self.jsonl_path) < self.max_bytes:
                return None
            os.replace(self.jsonl_path, rolled)
        except OSError:
            return None  # the file vanished under us — nothing to roll
        self._seq += 1
        rec = {"event": "events_rotate", "seq": self._seq,
               "pid": os.getpid(), "ts": time.time(),
               "path": self.jsonl_path, "rotated_to": rolled,
               "bytes": int(size)}
        with open(self.jsonl_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        return rec


_BUS = EventBus()


def get_bus() -> EventBus:
    """The process-global bus every emitter and sink shares."""
    return _BUS


# --- run manifest -----------------------------------------------------------


def config_hash(config: dict) -> str:
    """Stable short hash of a config mapping (the run's identity in the
    manifest and the trajectory header)."""
    blob = json.dumps(_clean(config), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def environment_manifest() -> dict:
    """jax/device provenance for the run manifest.  Requires the backend
    to be selected already (callers emit after CLI setup)."""
    import jax

    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "process_count": jax.process_count(),
    }


def run_manifest(config: dict, dataset=None) -> dict:
    """The ``run_start`` payload: the full config, its hash, and the
    jax/device environment."""
    return {
        "dataset": dataset,
        "config": _clean(config),
        "config_hash": config_hash(config),
        **environment_manifest(),
    }


# --- the device bridge ------------------------------------------------------

_IO_CALLBACK_OK = None


def io_callback_supported() -> bool:
    """Whether ordered ``io_callback`` works inside a jitted
    ``lax.while_loop`` on this jax/backend (probed once per process with a
    trivial three-iteration loop).  When False, the device driver falls
    back to replaying events from its end-of-run fetch — same events,
    same values, just not live."""
    global _IO_CALLBACK_OK
    if _IO_CALLBACK_OK is None:
        try:
            import jax
            import jax.numpy as jnp
            from jax import lax
            from jax.experimental import io_callback

            seen = []

            def probe(x):
                def body(s):
                    i, x = s
                    io_callback(lambda i, v: seen.append(int(i)), None,
                                i, x, ordered=True)
                    return i + 1, x + 1.0
                return lax.while_loop(lambda s: s[0] < 3, body,
                                      (jnp.int32(0), x))

            jax.jit(probe)(jnp.float32(0.0))[0].block_until_ready()
            jax.effects_barrier()
            _IO_CALLBACK_OK = seen == [0, 1, 2]
            why = f"the probe loop saw {seen}, expected [0, 1, 2]"
        except Exception as e:
            _IO_CALLBACK_OK = False
            why = f"{type(e).__name__}: {e}"
        if not _IO_CALLBACK_OK:
            # the probe's outcome changes the compiled --deviceLoop
            # program under --events; say once why it went the other way
            import warnings

            warnings.warn(
                f"ordered io_callback is unavailable on this backend "
                f"({why}); device-loop telemetry replays its events from "
                f"the end-of-run fetch instead of streaming them live",
                RuntimeWarning)
    return _IO_CALLBACK_OK


_DEVICE_TAP = None


def _device_sink(i, row):
    """The io_callback target: forward one eval row to the installed tap.
    A row arriving with no tap installed (e.g. a cached executable rerun
    outside a telemetry context) is dropped — side-effect-only either way."""
    tap = _DEVICE_TAP
    if tap is not None:
        tap(i, row)


@contextlib.contextmanager
def device_tap(tap):
    """Install ``tap`` as the destination for in-flight device events for
    the duration of one dispatch+fetch.  Runs are sequential within a
    process (the driver's fetch joins the loop before returning), so a
    single slot suffices."""
    global _DEVICE_TAP
    prev = _DEVICE_TAP
    _DEVICE_TAP = tap
    try:
        yield tap
    finally:
        _DEVICE_TAP = prev


def per_class_fields(gaps, gap_target) -> dict:
    """What a one-vs-rest eval adds to its record and event: ``class_gaps``
    (every class's gap, by class id) and ``classes_done`` (how many are at
    or under ``gap_target``; None without one).  ``{}`` for no gaps: a
    T = 1 job's records and events are what they were."""
    gaps = [float(g) for g in gaps]
    if not gaps:
        return {}
    done = (None if gap_target is None
            else sum(g <= gap_target for g in gaps))
    return dict(class_gaps=gaps, classes_done=done)


class DeviceTap:
    """Decode device eval rows into bus events.

    One instance serves BOTH bridge paths — the live io_callback stream
    and the end-of-run fetch replay feed rows through the same
    ``__call__`` — so the two paths emit identical events by construction
    (the parity the tests pin).

    Row layout (solvers/base.py ``_build_device_run``):
    ``[primal, gap, test_err, sigma_stage, stall, theta_stage,
    restarts]`` — gap/test_err NaN when not applicable, sigma_stage NaN
    outside σ′-anneal runs, theta_stage/restarts NaN outside ``--accel``
    runs (and absent entirely on pre-widening 5-col rows, which decode
    unchanged).  A one-vs-rest job's rows carry every class's gap past
    those seven (``gap`` is then the worst): they ride the ``round_eval``
    event as ``class_gaps``, with ``classes_done`` counted against
    ``gap_target``.

    ``init_stage`` / ``init_theta_stage`` / ``init_restarts`` seed
    transition detection with the values the state ENTERED this dispatch
    at (the sched leaf rides super-block boundaries), so a resumed or
    multi-block run never fabricates a backoff / Θ-step / restart event
    for its first eval.
    """

    def __init__(self, bus, algorithm: str, start_round: int, cadence: int,
                 sigma_levels=None, init_stage=None, theta_hs=None,
                 init_theta_stage=None, init_restarts=None,
                 gap_target=None):
        self.bus = bus
        self.gap_target = gap_target
        self.algorithm = algorithm
        self.start_round = start_round
        self.cadence = cadence
        self.levels = sigma_levels
        self._prev_stage = init_stage
        self.theta_hs = theta_hs
        self._prev_theta = init_theta_stage
        self._prev_restarts = init_restarts
        self.count = 0

    def __call__(self, i, row):
        # jaxlint: allow=f64 -- host-side decode of an already-fetched f32
        # row; never enters device compute
        r = np.asarray(row, dtype=np.float64)
        t = self.start_round - 1 + (int(i) + 1) * self.cadence
        primal, gap, test_err, stage_f, stall = (float(v) for v in r[:5])
        stage = None if math.isnan(stage_f) else int(stage_f)
        sigma = (self.levels[stage]
                 if self.levels is not None and stage is not None else None)
        self.bus.emit(
            "round_eval", algorithm=self.algorithm, t=t, primal=primal,
            gap=gap, test_error=test_err, sigma=sigma, sigma_stage=stage,
            stall=None if math.isnan(stall) else int(stall),
            **per_class_fields(r[7:], self.gap_target),
        )
        if (stage is not None and self._prev_stage is not None
                and stage != self._prev_stage):
            self.bus.emit(
                "sigma_backoff", algorithm=self.algorithm, t=t,
                sigma=sigma, from_sigma=self.levels[self._prev_stage],
                stage=stage,
            )
        if stage is not None:
            self._prev_stage = stage
        if r.shape[0] >= 7:
            theta_f, restarts_f = float(r[5]), float(r[6])
            theta = None if math.isnan(theta_f) else int(theta_f)
            restarts = None if math.isnan(restarts_f) else int(restarts_f)
            if (restarts is not None and self._prev_restarts is not None
                    and restarts > self._prev_restarts):
                self.bus.emit("momentum_restart", algorithm=self.algorithm,
                              t=t, restarts_total=restarts)
            if restarts is not None:
                self._prev_restarts = restarts
            if (theta is not None and self._prev_theta is not None
                    and theta != self._prev_theta):
                self.bus.emit(
                    "theta_stage", algorithm=self.algorithm, t=t,
                    stage=theta,
                    h=(self.theta_hs[theta]
                       if self.theta_hs is not None else None))
            if theta is not None:
                self._prev_theta = theta
        self.count += 1
