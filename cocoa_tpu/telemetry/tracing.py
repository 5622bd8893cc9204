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
  through to ready), ``build_loop`` (the loop program's first dispatch,
  the ``_DEVICE_RUNS`` miss: trace, lower, compile or cache load) and
  ``first_run`` (from that dispatch's return to the first fetch's); and,
  around the rest of a solver entry's call from the first of them on,
  ``first_job`` (:func:`cold_entry`).  A cold span is
  the same annotation and, armed, the same ``span`` event; armed or not it
  also leaves one record on ``Tracer.cold`` (bounded, oldest dropped),
  with an HBM reading (``memory_stats()`` of each device the job's data
  spans) at open and at close, the close after what the span launched
  is ready.  A span that built a program keeps the jitted callable and
  its arguments' shapes: :func:`program_memory` compiles from them on
  demand, and nothing on a job's path pays for it.  A warm job opens
  none of these, reads no clock and no allocator for them.

Span event fields: ``phase`` (the instrument point's name), ``span_id``
/ ``parent_id`` (per-process, thread-safe counter), ``worker`` (the
process index the tracer was configured with), ``start_ts`` (wall),
``dur_s`` (monotonic), plus free-form attributes (``round``, ``path``,
``key``, ``generation``, ...) the call site tags on; a cold span's event
adds ``job``, ``hbm_open`` and ``hbm_close`` (schema.COLD_SPAN_FIELDS).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time

import jax
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
SCOPES = (SCOPE_LOCAL_SOLVE, SCOPE_DW_REDUCE, SCOPE_EVAL, SCOPE_INDICES,
          SCOPE_ACCEL_JUMP, SCOPE_SPARSE_GATHER, SCOPE_ROW_ALIGN)

# the cold path (module docstring): the records ``Tracer.cold`` keeps, the
# span a cold job's entry wears, and what one HBM reading holds of a device
COLD_CAP = 256
FIRST_JOB = "first_job"
HBM_KEYS = ("bytes_in_use", "peak_bytes_in_use")


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
    and, once a cold branch was taken, ``first_job`` and the records."""

    __slots__ = ("ordinal", "ds", "depth", "first", "records")

    def __init__(self, ordinal, ds, depth):
        self.ordinal, self.ds, self.depth = ordinal, ds, depth
        self.first = None
        self.records = []


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
        self.start_ts = time.time()
        self.t0 = time.perf_counter()
        return self

    def close(self, error: str = None) -> dict:
        tr = self.tracer
        self.closed = True
        if self._made is not None and error is None:
            jax.block_until_ready(self._made)
        dur = time.perf_counter() - self.t0
        hbm_close = hbm_reading(self.devices)
        tr._stack().remove(self.sid)
        self._note.__exit__(None, None, None)
        job = None if self.job is None else self.job.ordinal
        record = dict(phase=self.phase, span_id=self.sid,
                      parent_id=self.parent, job=job, start_s=self.t0,
                      dur_s=dur, hbm_open=self.hbm_open, hbm_close=hbm_close,
                      program=self._program, **self.attrs)
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
    span its phase, seconds, and the rise of the peak across it in bytes,
    on the device that ends fullest (None where the backend has no
    counters)."""
    out = []
    for r in records:
        last = _fullest(r["hbm_close"])
        first = next(d for d in r["hbm_open"]
                     if d["device"] == last["device"])
        peak, was = last["peak_bytes_in_use"], first["peak_bytes_in_use"]
        out.append(dict(phase=r["phase"], dur_s=r["dur_s"],
                        peak_rise=None if peak is None else peak - was))
    return out


def cold_line(summary: list) -> str:
    """A job's :func:`cold_summary` as the console's ``cold path:`` line:
    ``first_job`` first, then the spans in the order they closed."""
    def one(c):
        rise = c["peak_rise"]
        return (f"{c['phase']} {c['dur_s']:.3f} s"
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
    arms it (the CLI does this under ``--trace``); ``span``/``traced``
    are the two instrumentation forms.  Spans are emitted through the
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

    def configure(self, enabled: bool = True, worker=None) -> "Tracer":
        self.enabled = bool(enabled)
        if worker is not None:
            self.worker = int(worker)
        return self

    def reset(self):
        """Disarm and forget the worker tag + id counter, the job
        ordinals and the cold records (tests)."""
        self.enabled = False
        self.worker = None
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._jobs = itertools.count(1)
        self.cold.clear()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

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

    def traced(self, phase: str, **attrs):
        """Decorator form: ``@tracer.traced("checkpoint_save")``."""
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                with self.span(phase, **attrs):
                    return fn(*args, **kwargs)
            return wrapper
        return deco


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer every instrument point shares."""
    return _TRACER


def configure(enabled: bool = True, worker=None) -> Tracer:
    return _TRACER.configure(enabled=enabled, worker=worker)


def span(phase: str, **attrs):
    """Module-level convenience: ``with tracing.span("eval", round=t):``"""
    return _TRACER.span(phase, **attrs)


def traced(phase: str, **attrs):
    """Module-level convenience decorator."""
    return _TRACER.traced(phase, **attrs)


def cold_span(phase: str, **attrs) -> ColdSpan:
    """Module-level convenience: ``with tracing.cold_span("fold_rows"):``"""
    return _TRACER.cold_span(phase, **attrs)


def cold_entry(fn):
    """Module-level convenience decorator (:meth:`Tracer.cold_entry`)."""
    return _TRACER.cold_entry(fn)


def finish_job() -> list:
    """Module-level convenience (:meth:`Tracer.finish_job`)."""
    return _TRACER.finish_job()


def reset():
    """Disarm the process-global tracer (tests)."""
    _TRACER.reset()
