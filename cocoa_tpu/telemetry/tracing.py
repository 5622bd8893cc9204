"""Gang-wide span tracing: what phase, on which worker, burned the time.

The event bus (telemetry/events.py) answers *what happened* — evals,
backoffs, resizes.  This module answers *where the wall-clock went*: a
span is one timed phase execution (an ingest pass, a KV exchange, a
local-solve super-block, an eval window, a checkpoint save, a supervisor
generation), emitted through the bus as a typed ``span`` event when it
CLOSES.  The offline assembler (telemetry/trace_report.py) merges the
per-process span streams of a gang run into one timeline, exports a
Perfetto/Chrome trace, and attributes stragglers per worker × phase.

Design constraints, in order:

- **Zero perturbation.**  Spans are host-side bookkeeping around code
  that is already host-side (dispatch boundaries, file IO, KV waits);
  nothing a span does reads or writes device values, so a traced run's
  ``(w, α)`` and sched leaf are bit-identical to an untraced run — the
  same contract the PR-4 telemetry bridge carries, pinned the same way
  (tests/test_tracing.py).  The jaxlint ``span-hygiene`` rule
  (cocoa_tpu/analysis) enforces the corollary statically: a span
  enter/exit must never appear inside jit/lax bodies, where it would be
  a trace-time no-op at best and a host sync at worst.
- **Two halves, two clocks** (docs/DESIGN.md "Observability").  Every
  span, armed or not, opens a ``jax.profiler.TraceAnnotation`` named
  ``cocoa/<phase>`` for its lifetime: with no profiler session running
  that is a ``TraceMe`` that records nothing (2 us a span, measured), and
  under ``jax.profiler`` it lands in the ``.xplane.pb`` on the
  *profiler's* clock, the one the device's ops are on — which is what
  lets a reader put each idle gap of the device down to the phase the
  host was in (chipbench/phases.py).  The *bus half* — ids, ``start_ts``,
  ``dur_s``, the ``span`` event — exists only on an armed tracer with an
  active bus and keeps the *host's* clocks: durations from
  ``time.monotonic()`` (immune to NTP steps mid-span), placement on the
  merged timeline from wall-clock ``start_ts`` (``time.time()`` at
  enter).  Cross-process alignment is therefore wall-clock-grade (NTP
  skew bounds it); per-span durations — what the critical path and
  straggler slack are computed from — are exact per process.  Within
  one process, nesting is tracked by a thread-local stack, so a span's
  ``parent_id`` names the span it ran inside (the KV gets inside an
  allgather inside a round).
- **Inert by default.**  On a disabled tracer ``span()`` is the
  annotation and one attribute read: no id, no clock read, no event.
- **The drive ladder's spans** (solvers/cocoa.py ``run_sdca_family``,
  solvers/base.py ``drive_device_full`` / ``drive_on_device``), which the
  benchmark's readers of a job's fixed cost go by name and which
  therefore open on every job, however short: ``init_state`` (a job from
  nothing: its one start program, dispatched and not waited for; an init
  handed in: a leaf at a time), ``wait_indices`` (a block's tables: a
  NumPy spec of round numbers where the kernels sample in-jit, else the
  driving thread held up by the staging thread), ``local_solve`` around
  ``dispatch`` (the loop program; the spec's upload rides it) and
  ``fetch`` (the one read of the stop header and the whole trajectory
  buffer, ``base.fetch_loop_result``), ``decode_trajectory``,
  ``checkpoint_save``.  ``stage_indices`` runs on the staging thread and
  exists only where the tables are host work (``--sampling=host``,
  ``--rng=reference`` past int32).
- **Device scopes.**  Inside ``jit`` nothing can be timed from the host;
  there the phases carry ``jax.named_scope`` names (:data:`SCOPES`), which
  change an op's metadata and nothing else, and reach the profiler's
  file as the scope path of every device op.  One name per phase, no
  ``/`` in a name, the same names on every drive path.

- **The cold path's spans** (:meth:`Tracer.cold_span`): what a process's
  first job pays that a warm one does not, in seconds and in bytes.  They
  open only in branches a warm job never enters, each a cache's miss:
  ``order_rows`` (data/sharding.order_rows_by_length, once a dataset),
  ``fold_rows`` and ``row_lengths`` (the per-dataset caches of
  ``run_sdca_family``), ``build_start`` (the start program's first call,
  through to ready), ``resolve_path`` (``resolve_solver_path`` in a call
  that ``build_start`` made a first job: the kernels' modules' first
  import, ``jax.experimental.pallas`` a second of it, and the fits;
  :meth:`Tracer.first_job_span`), ``build_loop`` (the loop program's
  first dispatch, the ``_DEVICE_RUNS`` miss: trace, lower, compile or
  cache load) and ``first_run`` (from that dispatch's return to the first
  fetch's); and, around the rest of a solver entry's call from the first
  of them on, ``first_job`` (:func:`cold_entry`).  A cold span is the
  same annotation and, armed, the same ``span`` event; armed or not it
  also leaves one record on ``Tracer.cold`` (bounded, oldest dropped),
  with an HBM reading (``memory_stats()`` of each device the job's data
  spans) at open and at close, the close after what the span launched
  is ready.  A span that built a program keeps the jitted callable and
  its arguments' shapes: :func:`program_memory` compiles from them on
  demand, and nothing on a job's path pays for it.  A warm job opens
  none of these, reads no clock and no allocator for them.
- **The build account** (:func:`observe_builds`): how a cold
  span's seconds divide between tracing, lowering, compiling and loading,
  program by program.  jax times each of them itself and hands the time
  to ``jax.monitoring``'s listeners on the thread that builds; the tracer
  registers its own once a process and makes of every event one *build
  record*: ``stage`` (``trace`` the Python function to a jaxpr, ``lower``
  the jaxpr to MLIR, Mosaic's kernels with it, ``compile`` the backend's
  build, ``load`` the same where the persistent cache answered), the
  program's ``fun_name``, ``start_ts`` / ``dur_s`` (jax's own clock reads),
  ``job`` (the ordinal of the solver entry's call it fell in, else None),
  ``span`` (the phase of the innermost cold span open on that thread,
  else None), ``jobs_opened`` (how many entry calls the process had
  opened by then), ``order`` (its place in the process's sequence of
  builds) and, of a backend build the cache was asked for, ``cache``
  (``hit`` / ``miss``: an entry was written).  A traced function's inner
  ``jit``s (``sin``, ``matmul`` inside ``run``) report their own seconds
  inside the outer's: only a build that ran inside no other leaves a
  record, with the number of builds inside it (``inner``) and the seconds
  of those directly inside it (``inner_s``), so records add up to wall
  clock.  A record goes to the bounded ``Tracer.builds`` and to each cold
  span open on its thread, whose closing record and ``span`` event gain
  ``builds`` and the sums ``trace_s``, ``lower_s``, ``compile_s``,
  ``load_s``, ``cache_misses`` (schema.COLD_BUILD_FIELDS); one inside an
  entry's call and under no cold span (a later super-block's shape
  retracing ``run``, a host-driven job's chunk step) is the call's
  *stray* (:func:`stray_builds`), which the entry names on the console.
  A warm call of a jitted function records no event, so a warm job runs
  no listener; a listener reads no device and never raises into jax.

Span event fields: ``phase`` (the instrument point's name), ``span_id``
/ ``parent_id`` (per-process, thread-safe counter), ``worker`` (the
process index the tracer was configured with), ``start_ts`` (wall),
``dur_s`` (monotonic), plus free-form attributes (``round``, ``path``,
``key``, ``generation``, ...) the call site tags on; a cold span's event
adds ``job``, ``hbm_open`` and ``hbm_close`` (schema.COLD_SPAN_FIELDS) and
the build account's ``builds`` and sums (schema.COLD_BUILD_FIELDS).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import re
import threading
import time

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

# the profiler-clock name of a span: ``cocoa/<phase>``
ANNOTATION_PREFIX = "cocoa/"

# jax.named_scope names of the phases inside jit (what runs on the device)
SCOPE_LOCAL_SOLVE = "cocoa_local_solve"   # one round's per-shard solve
SCOPE_DW_REDUCE = "cocoa_dw_reduce"       # the dw sum/psum and its apply
SCOPE_EVAL = "cocoa_eval"                 # the certificate evaluation
SCOPE_INDICES = "cocoa_indices"           # a round's sampled row indices
SCOPE_ACCEL_JUMP = "cocoa_accel_jump"     # --accel: the secant jump, and the
                                          # pass over the rows that moves w
SCOPE_SPARSE_GATHER = "cocoa_sparse_gather"  # sparse rows past VMEM: the
                                          # gathers, local-id remap and
                                          # scatters that feed and drain the
                                          # chain (ops/pallas_sparse_hbm.py)
SCOPE_ROW_ALIGN = "cocoa_row_align"       # the dense fold cache relaid into
                                          # lane-aligned rows, once a dispatch
                                          # (ops/pallas_sdca.lane_aligned)
# the two halves of a block round of T class models on the lanes of dense
# rows (ops/block_lanes.py), both INSIDE the local solve's scope, which
# holds the whole round: an op's innermost scope says which half paces it
SCOPE_WIDE_PRODUCTS = "cocoa_wide_products"  # a block's rows gathered, its
                                          # margins, its Gram matrix and its
                                          # update: the matrix products
SCOPE_WIDE_REPLAY = "cocoa_wide_replay"   # the block's steps in order on
                                          # lane vectors, a row's alphas in
                                          # and out
SCOPES = (SCOPE_LOCAL_SOLVE, SCOPE_DW_REDUCE, SCOPE_EVAL, SCOPE_INDICES,
          SCOPE_ACCEL_JUMP, SCOPE_SPARSE_GATHER, SCOPE_ROW_ALIGN,
          SCOPE_WIDE_PRODUCTS, SCOPE_WIDE_REPLAY)

# the cold path (module docstring): the records ``Tracer.cold`` keeps, the
# span a cold job's entry wears, and what one HBM reading holds of a device
COLD_CAP = 256
FIRST_JOB = "first_job"
HBM_KEYS = ("bytes_in_use", "peak_bytes_in_use")

# the build account (module docstring): jax.monitoring's names for the
# stages of a build, for what the persistent cache answered, and the sums
# a cold span's closing record keeps of its build records
BUILDS_CAP = 1024
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_OF_EVENT = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
BUILD_STAGES = ("trace", "lower", "compile", "load")
BUILD_SUMS = tuple(stage + "_s" for stage in BUILD_STAGES) + ("cache_misses",)
_PROGRAM_RE = re.compile(r"^(?:jit|pmap)\((.*)\)$")


def build_sums(builds) -> dict:
    """Of build records the seconds by stage and the programs compiled
    and written to the persistent cache."""
    sums = {stage + "_s": sum(b["dur_s"] for b in builds
                              if b["stage"] == stage)
            for stage in BUILD_STAGES}
    sums["cache_misses"] = sum(b.get("cache") == "miss" for b in builds)
    return sums


def memory_stats(device):
    """``device.memory_stats()``: the allocator's counters, or None where
    the backend keeps none (the CPU).  Every HBM reading is taken here."""
    return device.memory_stats()


def hbm_reading(devices) -> list:
    """One HBM reading: per device its id and :data:`HBM_KEYS` (each None
    where the backend has no counters)."""
    stats = [(d.id, memory_stats(d) or {}) for d in devices]
    return [{"device": i, **{k: s.get(k) for k in HBM_KEYS}}
            for i, s in stats]


def _struct(x):
    """``x`` as the compiler sees an argument: an array's shape, dtype and
    none of its memory; anything else as it is.  The sharding only of an
    array that was placed (committed): one the runtime put where it liked
    lowers with no sharding, and a struct that names one is another
    program to the persistent compile cache (a compile, not a load)."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class _Job:
    """One call of a solver entry (:func:`cold_entry`): the ordinal its
    cold spans share, the dataset whose devices they read, the depth of
    the span stack the entry was called at (where ``first_job`` belongs),
    once a cold branch was taken ``first_job`` and the records, and the
    builds that fell in the call under no cold span (``strays``)."""

    __slots__ = ("ordinal", "ds", "depth", "first", "records", "strays")

    def __init__(self, ordinal, ds, depth):
        self.ordinal, self.ds, self.depth = ordinal, ds, depth
        self.first = None
        self.records = []
        self.strays = None


def _devices_of(job) -> list:
    """The devices a job's data spans, in id order; outside a solver entry
    (or for a dataset that names none) every local device."""
    labels = getattr(getattr(job, "ds", None), "labels", None)
    if isinstance(labels, jax.Array):
        return sorted(labels.sharding.device_set, key=lambda d: d.id)
    return jax.local_devices()


class ColdSpan:
    """One cold span (:meth:`Tracer.cold_span`): a context manager that
    nests like a span."""

    def __init__(self, tracer: "Tracer", phase: str, attrs: dict):
        self.tracer, self.phase, self.attrs = tracer, str(phase), attrs
        self._made = self._program = None
        self.builds = []        # the builds that fell in it
        self.closed = False

    def made(self, *values) -> None:
        """What the span launched on the device: waited for before the
        closing reading, so that reading is of the finished step."""
        self._made = values

    def built(self, fn, *args) -> None:
        """The jitted program the span builds and the arguments of its
        first call, kept as shapes (:func:`program_memory`)."""
        self._program = (fn, jax.tree.map(_struct, args))

    def __enter__(self) -> "ColdSpan":
        tr = self.tracer
        job = self.job = getattr(tr._local, "job", None)
        first = self.phase == FIRST_JOB
        if job is not None and job.first is None and not first:
            # the first cold branch of this call: the job is a first job
            job.first = ColdSpan(tr, FIRST_JOB, {}).__enter__()
        self._note = TraceAnnotation(ANNOTATION_PREFIX + self.phase)
        self._note.__enter__()
        # first_job is around the entry's whole call: under whatever was
        # open when the entry was called, not under the span it opened in
        self.sid, self.parent = tr._push(job.depth if first else None)
        self.devices = (job.first.devices if job is not None
                        and job.first is not None else _devices_of(job))
        self.hbm_open = hbm_reading(self.devices)
        tr._cold_open().append(self)
        self.start_ts = time.time()
        self.t0 = time.perf_counter()
        return self

    def close(self, error: str = None) -> dict:
        tr = self.tracer
        self.closed = True
        if self._made is not None and error is None:
            jax.block_until_ready(self._made)
        dur = time.perf_counter() - self.t0
        tr._cold_open().remove(self)
        hbm_close = hbm_reading(self.devices)
        tr._stack().remove(self.sid)
        self._note.__exit__(None, None, None)
        job = None if self.job is None else self.job.ordinal
        record = dict(phase=self.phase, span_id=self.sid,
                      parent_id=self.parent, job=job, start_s=self.t0,
                      dur_s=dur, hbm_open=self.hbm_open, hbm_close=hbm_close,
                      program=self._program, builds=self.builds,
                      **build_sums(self.builds), **self.attrs)
        if error is not None:
            record["error"] = error
        tr.cold.append(record)
        if self.job is not None:
            self.job.records.append(record)
        bus = tr._sink()
        if bus is not None:
            bus.emit("span", worker=tr.worker, start_ts=self.start_ts,
                     **{k: v for k, v in record.items()
                        if k not in ("start_s", "program")})
        return record

    def __exit__(self, exc_type, exc, tb):
        self.close(None if exc_type is None else exc_type.__name__)
        return False


def _fullest(reading: list) -> dict:
    """Of one HBM reading the device whose peak is highest (the first
    where the backend has no counters)."""
    return max(reading, key=lambda r: r["peak_bytes_in_use"] or 0)


def cold_summary(records) -> list:
    """What ``Trajectory.meta["cold"]`` holds of a job's cold records: per
    span its phase, seconds, the rise of the peak across it in bytes, on
    the device that ends fullest (None where the backend has no counters),
    and how its builds divide (:func:`build_sums`, with their number)."""
    out = []
    for r in records:
        last = _fullest(r["hbm_close"])
        first = next(d for d in r["hbm_open"]
                     if d["device"] == last["device"])
        peak, was = last["peak_bytes_in_use"], first["peak_bytes_in_use"]
        out.append(dict(phase=r["phase"], dur_s=r["dur_s"],
                        peak_rise=None if peak is None else peak - was,
                        builds=len(r["builds"]),
                        compiled=sum(b["stage"] == "compile"
                                     for b in r["builds"]),
                        **{k: r[k] for k in BUILD_SUMS}))
    return out


def cold_line(summary: list) -> str:
    """A job's :func:`cold_summary` as the console's ``cold path:`` line:
    ``first_job`` first, then the spans in the order they closed; of a
    span that built programs, how the build's seconds divide (``compiled``
    with the number of programs where the backend built and no cache
    answered, ``load`` where one did)."""
    def one(c):
        rise = c["peak_rise"]
        split = [f"{name} {c[name + '_s']:.3f}"
                 for name in ("trace", "lower") if c.get("builds")]
        if c.get("compiled"):
            split.append(f"compiled {c['compiled']} in {c['compile_s']:.3f}")
        if c.get("load_s"):
            split.append(f"load {c['load_s']:.3f}")
        return (f"{c['phase']} {c['dur_s']:.3f} s"
                + (f" ({', '.join(split)})" if split else "")
                + ("" if not rise else f" (peak +{rise / 1e9:.3f} GB)"))

    spans = sorted(summary, key=lambda c: c["phase"] != FIRST_JOB)
    return ", ".join(one(c) for c in spans)


def program_memory(record: dict):
    """The compiler's account of the program a cold span built
    (``build_start``, ``build_loop``): argument, output, alias, temp and
    generated-code bytes a device, from ``lower(...).compile()`` on the
    kept shapes.  The same program as the job's, so where a persistent
    compile cache is on it is a load; called on demand (a benchmark's
    reader, a debugger), never on a job's path.  None for a record that
    kept no program or a backend that gives no analysis."""
    if record.get("program") is None:
        return None
    fn, args = record["program"]
    analysis = fn.lower(*args).compile().memory_analysis()
    if analysis is None:
        return None
    return {name: int(getattr(analysis, name + "_size_in_bytes"))
            for name in ("argument", "output", "alias", "temp",
                         "generated_code")}


class Tracer:
    """Process-global span source.  ``configure(enabled=True, worker=i)``
    arms it (the CLI does this under ``--trace``); ``span`` is the
    instrumentation form.  Spans are emitted through the
    process-global EventBus, so they ride the same JSONL sink, metrics
    writer, and flight-recorder ring as every other event — and an
    armed tracer with an inert bus emits nothing (one more cheap guard).
    """

    def __init__(self):
        self.enabled = False
        self.worker = None
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._jobs = itertools.count(1)
        self.cold = collections.deque(maxlen=COLD_CAP)
        # the build account: the records, their order, the entry calls
        # opened so far, the listeners' calls (what observing costs is
        # this many times a call's microseconds) and who else is told
        self.builds = collections.deque(maxlen=BUILDS_CAP)
        self._order = itertools.count(1)
        self.jobs_opened = 0
        self.listener_calls = 0
        self.listener_error = None
        self._watchers = []

    def configure(self, enabled: bool = True, worker=None) -> "Tracer":
        self.enabled = bool(enabled)
        if worker is not None:
            self.worker = int(worker)
        return self

    def reset(self):
        """Disarm and forget the worker tag + id counter, the job
        ordinals, the cold records and the build records (tests); the
        builds' watchers stay, and so does the one registration with
        ``jax.monitoring``."""
        self.enabled = False
        self.worker = None
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._jobs = itertools.count(1)
        self.cold.clear()
        self.builds.clear()
        self._order = itertools.count(1)
        self.jobs_opened = self.listener_calls = 0
        self.listener_error = None
        observe_builds()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _cold_open(self) -> list:
        """The cold spans open on this thread, innermost last."""
        spans = getattr(self._local, "cold_open", None)
        if spans is None:
            spans = self._local.cold_open = []
        return spans

    def _push(self, at=None) -> tuple:
        """A new span's id, put on this thread's stack (on top, or at
        depth ``at``), and its parent's: the id under it."""
        stack = self._stack()
        at = len(stack) if at is None else min(at, len(stack))
        sid = next(self._ids)
        stack.insert(at, sid)
        return sid, (stack[at - 1] if at else None)

    def _sink(self):
        """The event bus where a ``span`` event would go: None unless the
        tracer is armed and the bus has a sink."""
        if not self.enabled:
            return None
        from cocoa_tpu.telemetry import events as _events

        bus = _events.get_bus()
        return bus if bus.active() else None

    @contextlib.contextmanager
    def span(self, phase: str, **attrs):
        """Time one phase execution; emits the ``span`` event at exit.

        Always open for the span's lifetime: the profiler annotation
        ``cocoa/<phase>``.  Yields the span id (or None when disabled).
        The event is emitted even when the body raises — a phase that
        died mid-way is exactly what the flight recorder wants on its
        ring — with an ``error`` attribute naming the exception type.
        """
        with TraceAnnotation(ANNOTATION_PREFIX + phase):
            bus = self._sink()
            if bus is None:
                yield None
                return
            sid, parent = self._push()
            start_ts = time.time()
            t0 = time.monotonic()
            err = None
            try:
                yield sid
            except BaseException as e:
                err = type(e).__name__
                raise
            finally:
                dur = time.monotonic() - t0
                self._stack().pop()
                fields = dict(phase=str(phase), span_id=sid,
                              parent_id=parent, worker=self.worker,
                              start_ts=start_ts, dur_s=dur, **attrs)
                if err is not None:
                    fields["error"] = err
                bus.emit("span", **fields)

    def cold_span(self, phase: str, **attrs) -> ColdSpan:
        """A span of the cold path: ``with tracer.cold_span("fold_rows")
        as cold: ...; cold.made(folded)``.  Only in a branch a warm job
        never enters: it reads the clock and the allocator of every device
        the job's data spans twice, and waits for what
        :meth:`ColdSpan.made` names."""
        return ColdSpan(self, phase, attrs)

    def first_job_span(self, phase: str, **attrs):
        """A cold span where this thread's solver-entry call has already
        taken a cold branch (its ``first_job`` is open), else nothing: for
        a stretch every job walks and only a first job pays for (the
        kernels' modules' first import under ``resolve_path``).  A warm
        job reads a thread-local for it."""
        job = getattr(self._local, "job", None)
        if job is None or job.first is None:
            return contextlib.nullcontext()
        return ColdSpan(self, phase, attrs)

    def cold_entry(self, fn):
        """Decorator of a solver entry ``fn(ds, ...)``: gives the call its
        job ordinal, and closes the ``first_job`` span that the call's first
        cold span opened (none on a warm call, which pays a counter and a
        thread-local for this and reads no clock)."""
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            local = self._local
            ds = args[0] if args else kwargs.get("ds")
            outer = getattr(local, "job", None)
            local.job = _Job(next(self._jobs), ds, len(self._stack()))
            self.jobs_opened = local.job.ordinal
            error = None
            try:
                return fn(*args, **kwargs)
            except BaseException as e:
                error = type(e).__name__
                raise
            finally:
                self.finish_job(error)
                local.job = outer
        return entry

    def finish_job(self, error: str = None) -> list:
        """What an entry calls where its result is ready: closes its
        ``first_job`` and returns the summary of the call's cold records
        (:func:`cold_summary`), empty for a warm call.  :meth:`cold_entry`
        closes it for a call that raised and an entry that does not ask."""
        job = getattr(self._local, "job", None)
        if job is None or job.first is None:
            return []
        if not job.first.closed:
            job.first.close(error)
        return cold_summary(job.records)

    def stray_builds(self) -> list:
        """The builds of this thread's solver-entry call that fell under
        no cold span, in order (empty outside an entry, and for a
        call that built nothing so)."""
        job = getattr(self._local, "job", None)
        return list(getattr(job, "strays", None) or ())

    # -- the build account: jax.monitoring's listeners (module docstring).
    # Each runs on the thread that builds, inside jax: it touches the
    # tracer's own bookkeeping and nothing else, and whatever goes wrong in
    # it stays here (``listener_error``).

    def _build_opens(self, event: str) -> None:
        """A stage begins (jax records its start as a scalar): one level
        deeper on this thread; a backend build forgets what the cache told
        an earlier one that never finished."""
        local = self._local
        local.depth = getattr(local, "depth", 0) + 1
        if event == BACKEND_EVENT:
            local.cache = local.retrieval_s = None

    def _build_closes(self, event: str, start: float, end: float,
                      fun_name: str = "") -> None:
        """A stage ended.  Inside another build (most are: a traced
        function's inner ``jit``s) it is counted on the outer's record to
        come and, a backend build apart, nothing else; else it leaves its
        build record, kept and handed to the watchers."""
        local = self._local
        depth = local.depth = max(getattr(local, "depth", 1) - 1, 0)
        if depth:
            local.inner = getattr(local, "inner", 0) + 1
            if depth == 1:
                local.inner_s = getattr(local, "inner_s", 0.0) + end - start
            if event != BACKEND_EVENT or not self._watchers:
                return
        job = getattr(local, "job", None)
        spans = getattr(local, "cold_open", None) or ()
        named = _PROGRAM_RE.match(str(fun_name))
        record = dict(
            order=next(self._order), stage=STAGE_OF_EVENT[event],
            fun_name=named.group(1) if named else str(fun_name),
            start_ts=start, dur_s=end - start,
            job=None if job is None else job.ordinal,
            span=spans[-1].phase if spans else None,
            jobs_opened=self.jobs_opened)
        if event == BACKEND_EVENT:
            cache = getattr(local, "cache", None)
            if cache is not None:
                record["cache"] = cache
            if cache == "hit":
                record["stage"] = "load"
                record["retrieval_s"] = getattr(local, "retrieval_s", None)
            local.cache = local.retrieval_s = None
        if not depth:
            record["inner"] = getattr(local, "inner", 0)
            record["inner_s"] = getattr(local, "inner_s", 0.0)
            local.inner, local.inner_s = 0, 0.0
            self.builds.append(record)
            for cold in spans:
                cold.builds.append(record)
            if job is not None and not spans:
                if job.strays is None:
                    job.strays = []
                job.strays.append(record)
        for told in tuple(self._watchers):
            told(record)

    def watch_builds(self, told) -> None:
        """``told(record)`` for every build record from now on, on the
        thread that builds (analysis/sanitize.py's compile watch and the
        bus's ``compile`` event hang here)."""
        self._watchers.append(told)

    def unwatch_builds(self, told) -> None:
        if told in self._watchers:
            self._watchers.remove(told)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer every instrument point shares."""
    return _TRACER


def configure(enabled: bool = True, worker=None) -> Tracer:
    return _TRACER.configure(enabled=enabled, worker=worker)


def span(phase: str, **attrs):
    """Module-level convenience: ``with tracing.span("eval", round=t):``"""
    return _TRACER.span(phase, **attrs)


def cold_span(phase: str, **attrs) -> ColdSpan:
    """Module-level convenience: ``with tracing.cold_span("fold_rows"):``"""
    return _TRACER.cold_span(phase, **attrs)


def first_job_span(phase: str, **attrs):
    """Module-level convenience (:meth:`Tracer.first_job_span`)."""
    return _TRACER.first_job_span(phase, **attrs)


def cold_entry(fn):
    """Module-level convenience decorator (:meth:`Tracer.cold_entry`)."""
    return _TRACER.cold_entry(fn)


def finish_job() -> list:
    """Module-level convenience (:meth:`Tracer.finish_job`)."""
    return _TRACER.finish_job()


def stray_builds() -> list:
    """Module-level convenience (:meth:`Tracer.stray_builds`)."""
    return _TRACER.stray_builds()


def stray_line(build: dict) -> str:
    """A stray build as the console names it."""
    return (f"built outside the cold path: {build['fun_name']} "
            f"{build['stage']} {build['dur_s']:.3f} s")


# jax.monitoring's listeners.  Each filters by the event's name before
# anything else (most events are not a build's), counts itself, and keeps
# what goes wrong in it to itself: never let observing break a build.

def _on_scalar(event, value, **kw):
    if event in STAGE_OF_EVENT:
        try:
            _TRACER.listener_calls += 1
            _TRACER._build_opens(event)
        except Exception as e:
            _TRACER.listener_error = repr(e)


def _on_time_span(event, start, end, fun_name="", **kw):
    if event in STAGE_OF_EVENT:
        try:
            _TRACER.listener_calls += 1
            _TRACER._build_closes(event, start, end, fun_name)
        except Exception as e:
            _TRACER.listener_error = repr(e)


def _on_event(event, **kw):
    if event in CACHE_OF_EVENT:
        try:
            _TRACER.listener_calls += 1
            _TRACER._local.cache = CACHE_OF_EVENT[event]
        except Exception as e:
            _TRACER.listener_error = repr(e)


def _on_duration(event, seconds, **kw):
    if event == CACHE_RETRIEVAL_EVENT:
        try:
            _TRACER.listener_calls += 1
            _TRACER._local.retrieval_s = seconds
        except Exception as e:
            _TRACER.listener_error = repr(e)


_LISTENERS = (
    (_on_scalar, monitoring.register_scalar_listener,
     monitoring.unregister_scalar_listener),
    (_on_time_span, monitoring.register_event_time_span_listener,
     monitoring.unregister_event_time_span_listener),
    (_on_event, monitoring.register_event_listener,
     monitoring.unregister_event_listener),
    (_on_duration, monitoring.register_event_duration_secs_listener,
     monitoring.unregister_event_duration_listener),
)
_observing = False


def observe_builds(on: bool = True) -> None:
    """Register the build account's listeners with ``jax.monitoring``,
    once a process however often it is asked (at import, and by
    :func:`reset`); ``on=False`` takes them off again (a measurement of
    what observing costs)."""
    global _observing
    if on == _observing:
        return
    for listener, register, unregister in _LISTENERS:
        if on:
            register(listener)
        else:
            try:
                unregister(listener)
            except AssertionError:      # someone cleared jax's lists
                pass
    _observing = on


observe_builds()


def watch_builds(told) -> None:
    """Module-level convenience (:meth:`Tracer.watch_builds`)."""
    _TRACER.watch_builds(told)


def unwatch_builds(told) -> None:
    _TRACER.unwatch_builds(told)


def reset():
    """Disarm the process-global tracer (tests)."""
    _TRACER.reset()
