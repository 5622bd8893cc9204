"""Dynamic sanitizers: the compile-once and no-silent-transfer invariants.

The static rules (rules.py) catch host syncs and recompiles you can see
in the source; this module catches the ones you can't — a shape that
quietly retraces per super-block, a scalar read that blocks on the
device inside the round loop — by wiring two runtime probes around the
drive loops:

- **compile watch** — every XLA compile is observable.  jax hands each
  backend build's seconds to ``jax.monitoring``; the program's one
  observer of builds (telemetry/tracing.py, "The build account") makes a
  record of it, ``compile`` or, where the persistent cache answered,
  ``load``.  :func:`watch_compiles` collects those records, and
  :func:`install_compile_events` bridges them onto the telemetry bus as
  typed ``compile`` events for the production ``--metrics`` counters.
  The invariant the tests pin: the device loop executable compiles
  exactly ONCE per config — a second identical run compiles nothing.
- **transfer guard** — :func:`sanitizer(strict="all")` arms the
  device-loop contract: inside each dispatch→fetch region (which the
  driver marks via :func:`device_loop_guard`) jax's transfer guards
  disallow EVERY host↔device crossing on the driving thread, so any
  un-sanctioned sync raises at its exact line; the drivers mark their
  deliberate fetch points with :func:`intended_fetch`, which re-allows
  the transfer, counts it, and emits a ``host_transfer`` event when
  telemetry is active.  The invariant: zero unintended device→host
  transfers inside the round loop, telemetry-on and -off.  (On CPU,
  whole-array device→host reads are zero-copy and unguarded, but the
  host→device half of an accidental ``float(x[i])`` — the index-constant
  upload — still trips, so the CPU fixtures are a real gate and the TPU
  run of the same fixtures is strictly stricter, never looser.)

Both probes are observational: neither changes what the run computes,
and ``intended_fetch`` costs one context-manager enter per super-block
fetch — nothing rides the per-round path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

# process-lifetime count of sanctioned device→host fetches (the
# production mirror of what a sanitizer context observes per run)
_counters_lock = threading.Lock()
intended_fetches_total = 0
# process-lifetime count of the programs the drive ladder issued, counted
# on the host where it issues them (:func:`count_launch`)
launches_total = 0


def count_launch(n: int = 1) -> None:
    """The drive ladder issued ``n`` device programs: a start program, a
    loop program, a host-stepped chunk or eval, a leaf made eagerly from
    an init that was handed in.  Counted where the ladder issues them, so
    a CPU test and a user can read a job's launches without a trace
    (``Trajectory.meta["launches"]``); an eager op that lowers to more
    than one program (``jnp.zeros``: two) still counts one, so off the
    one-start-program path the chip's trace is the exact count."""
    global launches_total
    with _counters_lock:
        launches_total += n


@dataclasses.dataclass
class CompileRecord:
    name: str
    seconds: float


def _compile_sink(sink):
    """What the build account's watchers are handed, narrowed to the
    backend's builds: ``sink(CompileRecord)`` for every ``compile`` and
    ``load`` record (the seconds jax's own compile log prints), nested in
    another build or not."""
    def told(build: dict):
        if build["stage"] in ("compile", "load"):
            sink(CompileRecord(name=build["fun_name"],
                               seconds=build["dur_s"]))
    return told


@contextlib.contextmanager
def watch_compiles():
    """Yield a list that accumulates one :class:`CompileRecord` per XLA
    compile (or cache load) finishing while the context is open, on any
    thread."""
    from cocoa_tpu.telemetry import tracing

    records: list = []
    told = _compile_sink(records.append)
    tracing.watch_builds(told)
    try:
        yield records
    finally:
        tracing.unwatch_builds(told)


_BUS_BRIDGE = None


def install_compile_events(bus) -> None:
    """Bridge compile records onto the telemetry bus as ``compile``
    events (idempotent; installed by ``EventBus.configure`` so any run
    with ``--metrics``/``--events`` gets ``compiles_total`` for free).
    The watcher stays for the process lifetime — ``emit`` on an inactive
    bus is a no-op, and a warm call of a compiled program reaches no
    watcher at all, so there is no tax once sinks detach."""
    global _BUS_BRIDGE
    if _BUS_BRIDGE is not None:
        return
    from cocoa_tpu.telemetry import tracing

    _BUS_BRIDGE = _compile_sink(
        lambda rec: bus.emit("compile", name=rec.name, seconds=rec.seconds))
    tracing.watch_builds(_BUS_BRIDGE)


@contextlib.contextmanager
def intended_fetch(label: str):
    """Mark a deliberate device→host sync point (the driver's one fetch
    per super-block, the eval fetch on host-stepped paths).  Inside a
    :func:`no_host_transfers` guard this is the ONLY way data may cross
    device→host; each use is counted and — when telemetry is active —
    emitted as a ``host_transfer`` event so production runs expose
    ``host_transfers_total``."""
    import jax

    from cocoa_tpu.telemetry import events as _tele

    global intended_fetches_total
    # allow every guard axis: the fetch itself is d2h, but decoding it
    # (scalar indexing) can upload index constants — all sanctioned here
    with jax.transfer_guard("allow"):
        yield
    with _counters_lock:
        intended_fetches_total += 1
    bus = _tele.get_bus()
    if bus.active():
        bus.emit("host_transfer", label=label)


@contextlib.contextmanager
def allow_transfers():
    """Plain un-counted allow — for runtime machinery of sanctioned
    paths (the ordered io_callback's zero-byte effect-token handshake at
    dispatch), which is neither a host fetch nor a leak."""
    import jax

    with jax.transfer_guard("allow"):
        yield


@contextlib.contextmanager
def allow_uploads():
    """Un-counted host→device allow, for a dispatch whose arguments
    include a host array (the device-mode chunk spec, a few dozen
    integers built with NumPy, rides the loop program's dispatch).
    Device→host stays as the surrounding guard has it."""
    import jax

    with jax.transfer_guard_host_to_device("allow"):
        yield


@contextlib.contextmanager
def no_host_transfers():
    """Disallow device→host transfers except through
    :func:`intended_fetch` — an unintended sync raises XlaRuntimeError
    at the exact offending line (thread-local, so the io_callback
    telemetry tap's rows, which arrive on the runtime's callback thread,
    stay unaffected — that path is sanctioned by design)."""
    import jax

    with jax.transfer_guard_device_to_host("disallow"):
        yield


_tls = threading.local()


def device_loop_guard():
    """The guard the device-resident driver wraps its dispatch→fetch
    region in (solvers/base.py ``drive_on_device``).  Inert (a
    nullcontext) unless a :func:`sanitizer` with ``strict="all"`` is
    active on this thread: solver SETUP legitimately uploads (state
    init, shard placement, index staging), so the no-transfer contract
    starts where the loop does — after the last staged argument, ending
    at the sanctioned fetch."""
    if getattr(_tls, "arm_device_loop", False):
        return no_transfers()
    return contextlib.nullcontext()


@contextlib.contextmanager
def _arm_device_loop():
    prev = getattr(_tls, "arm_device_loop", False)
    _tls.arm_device_loop = True
    try:
        yield
    finally:
        _tls.arm_device_loop = prev


@contextlib.contextmanager
def no_transfers():
    """Disallow transfers on EVERY guard axis except through
    :func:`intended_fetch`.  This is the device-loop contract: once the
    dispatch is in flight, nothing crosses the host↔device boundary on
    the driving thread until the sanctioned fetch — no index-constant
    uploads from stray scalar reads, no implicit device math on host
    values.  (It is also what gives the sanitizer teeth on CPU, where
    array device→host reads are zero-copy and unguarded but the
    host→device half of an accidental ``float(x[i])`` still trips.)
    Host-side staging that legitimately uploads (the index-table
    prefetch) runs on its own daemon thread, which the thread-local
    guard deliberately does not cover."""
    import jax

    with jax.transfer_guard("disallow"):
        yield


@dataclasses.dataclass
class SanitizerStats:
    compiles: list                  # CompileRecord per XLA compile
    fetches_before: int = 0

    def compile_count(self, name_substr: str = "") -> int:
        return sum(1 for c in self.compiles if name_substr in c.name)

    @property
    def intended_fetches(self) -> int:
        return intended_fetches_total - self.fetches_before


@contextlib.contextmanager
def sanitizer(strict="all"):
    """The combined harness the sanitizer fixtures run drive loops
    under: compile watch + a transfer guard.  ``strict="all"`` arms the
    device-loop contract — inside each dispatch→fetch region (marked by
    the driver via :func:`device_loop_guard`) NOTHING crosses
    host↔device outside :func:`intended_fetch`; solver setup/staging
    outside the loop is unconstrained.  ``"d2h"`` disallows device→host
    reads across the whole context instead (host-stepped paths, which
    legitimately upload index tables from the driving thread each
    chunk).  ``False`` = compile watch only.  Yields
    :class:`SanitizerStats`; an unintended transfer raises from the
    guarded code itself, so "zero unintended transfers" is simply "the
    run completed"."""
    with contextlib.ExitStack() as stack:
        records = stack.enter_context(watch_compiles())
        stats = SanitizerStats(compiles=records,
                               fetches_before=intended_fetches_total)
        if strict in (True, "all"):
            stack.enter_context(_arm_device_loop())
        elif strict == "d2h":
            stack.enter_context(no_host_transfers())
        yield stats
