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

Span event fields: ``phase`` (the instrument point's name), ``span_id``
/ ``parent_id`` (per-process, thread-safe counter), ``worker`` (the
process index the tracer was configured with), ``start_ts`` (wall),
``dur_s`` (monotonic), plus free-form attributes (``round``, ``path``,
``key``, ``generation``, ...) the call site tags on.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
import time

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
SCOPES = (SCOPE_LOCAL_SOLVE, SCOPE_DW_REDUCE, SCOPE_EVAL, SCOPE_INDICES,
          SCOPE_ACCEL_JUMP, SCOPE_SPARSE_GATHER)


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

    def configure(self, enabled: bool = True, worker=None) -> "Tracer":
        self.enabled = bool(enabled)
        if worker is not None:
            self.worker = int(worker)
        return self

    def reset(self):
        """Disarm and forget the worker tag + id counter (tests)."""
        self.enabled = False
        self.worker = None
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

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
            bus = None
            if self.enabled:
                from cocoa_tpu.telemetry import events as _events

                bus = _events.get_bus()
            if bus is None or not bus.active():
                yield None
                return
            sid = next(self._ids)
            stack = self._stack()
            parent = stack[-1] if stack else None
            stack.append(sid)
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
                stack.pop()
                fields = dict(phase=str(phase), span_id=sid,
                              parent_id=parent, worker=self.worker,
                              start_ts=start_ts, dur_s=dur, **attrs)
                if err is not None:
                    fields["error"] = err
                bus.emit("span", **fields)

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


def reset():
    """Disarm the process-global tracer (tests)."""
    _TRACER.reset()
