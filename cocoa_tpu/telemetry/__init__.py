"""In-loop telemetry: structured event stream, run manifest, metrics
textfile, and round-windowed profiler capture.

The paper's certificate story (duality gap per comm-round) is only as
credible as the ability to OBSERVE it while a run is in flight — and since
the drive* ladder went device-resident, the fast path surfaces nothing
until its final host sync.  This package closes that gap:

- :mod:`cocoa_tpu.telemetry.events` — the host-side event bus: typed,
  ordered records (``run_start`` with a full config manifest,
  ``round_eval``, ``sigma_backoff``, ``checkpoint_write``, ``restart``,
  ``divergence``, ``run_end``) appended to a JSONL sink and fanned out to
  subscribers; plus the device bridge glue (``DeviceTap`` /
  ``io_callback_supported``) that streams each eval out of the
  device-resident ``lax.while_loop`` (solvers/base.py).
- :mod:`cocoa_tpu.telemetry.metrics` — a Prometheus-style textfile
  refreshed on every event (rounds_total, evals_total,
  sigma_backoffs_total, restarts_total, last_gap, round_seconds
  histogram) — what elastic.py's supervisor and external scrapers watch.
- :mod:`cocoa_tpu.telemetry.schema` — the JSONL schema checker shared by
  the tests and CI (event streams, trajectory dumps, benchmark results).
- :mod:`cocoa_tpu.telemetry.profiling` — the round-windowed
  ``--profile=<dir>,<start>,<stop>`` capture riding the event stream.
- :mod:`cocoa_tpu.telemetry.tracing` — the program's phases, named: on
  the host, spans (ingest passes, KV exchanges, the drive ladder's
  state init / index wait / dispatch / fetch, eval windows, checkpoints,
  supervisor generations) that always open a ``cocoa/<phase>`` profiler
  annotation and, under ``--trace``, are also emitted through the bus
  as typed per-worker ``span`` events; inside ``jit``, the
  ``jax.named_scope`` names of local solve, Δw reduce, certificate eval,
  index sampling and the ``--accel`` jump.
- :mod:`cocoa_tpu.telemetry.trace_report` — the offline assembler:
  merges a gang's per-process span streams, exports Perfetto/Chrome
  trace JSON, computes the per-round critical path, and attributes
  stragglers worker × phase by slack.
- :mod:`cocoa_tpu.telemetry.recorder` — the crash flight recorder: a
  bounded ring of recent events dumped to ``<events>.flightrec`` on
  divergence/exception/SIGTERM, plus the supervisor-side dump of a
  SIGKILLed worker's stream tail.

Soundness: telemetry is side-effect-only.  The device bridge adds an
ordered ``io_callback`` that READS the eval row the loop already
computes; the loop-carried compute state (w, alpha, sched) is untouched,
so a telemetry-on run is bit-identical to a telemetry-off run
(tests/test_telemetry.py pins this).
"""

from cocoa_tpu.telemetry import events  # noqa: F401
from cocoa_tpu.telemetry.events import get_bus  # noqa: F401
