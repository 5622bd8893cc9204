"""The program's own phases in a traced run: which phase the host was in
while the device sat idle, and which phase each device op belongs to.

The program under test names its phases itself (``cocoa_tpu/telemetry/
tracing.py``): on the host every span opens a profiler annotation
``cocoa/<phase>``, which lands in the ``.xplane.pb`` on the clock of the
device's ops; inside ``jit`` a phase is a ``jax.named_scope`` whose name
starts with ``cocoa_`` and reaches the file as part of each op's scope
path.  This module reads both, once per process and trace, from the file
``run.py`` left at ``<chipbench>/out/<cell>.trace``:

- ``Phases.jobs``: per ``job`` span, device 0's idle time inside it, every
  idle instant put down to the innermost ``cocoa/`` span open *on the
  thread that holds the job span*.  Another thread's spans (the staging
  thread's ``cocoa/stage_indices``) overlap the driving thread's and say
  nothing of what the job was waiting on, so they never label a gap.
- ``Phases.scopes``: op name -> the innermost ``cocoa_*`` scope of its
  path, ``None`` for an op under no such scope (compiler-made copies, loop
  shells and bookkeeping).  ``jax.profiler.ProfileData`` hands out
  event-level stats only, and the scope path is a stat of the event's
  *metadata* (``tf_op``), so that table is read from the file's wire
  format: ``XSpace.planes[].event_metadata[].stats[]``, fifty lines, no
  dependency.

A trace of a program that has neither (the parent of the PR that brought
them) gives ``spanned`` and ``scoped`` false and every reader built on this
returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
import sys

from chipbench import reduce_trace

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
SPAN_PREFIX = "cocoa/"
# a path component that is a program scope, bare or inside the transforms
# JAX wraps around it: cocoa_eval, vmap(cocoa_local_solve), ...
SCOPE = re.compile(r"^(?!p?jit\()(?:\w+\()*(cocoa_\w+)\)*$")


@dataclasses.dataclass
class Phases:
    jobs: list      # per job span: idle_s of device 0, by_span {name: s}
    scopes: dict    # op name -> innermost program scope, or None
    paths: dict     # op name -> its whole scope path ("" where it has none)
    spans: list     # per host thread: [(name, start_ns, end_ns)], job too
    spanned: bool   # the job's thread holds at least one cocoa/ span
    scoped: bool    # at least one device op sits under a cocoa_ scope


# --- the wire format of XSpace, as far as the scope path needs it ----------


def _varint(buf: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield tag >> 3, value


def _map_entries(plane: bytes, field: int) -> dict:
    """A ``map<int64, Message>`` field of an XPlane as ``{key: bytes}``."""
    out = {}
    for number, entry in _fields(plane):
        if number == field:
            kv = dict(_fields(entry))
            out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def op_paths(raw: bytes) -> dict:
    """``{op name: scope path}`` over the device planes of a serialized
    XSpace: the ``tf_op`` stat of each event's metadata (XPlane fields:
    name 2, event_metadata 4, stat_metadata 5; XEventMetadata: name 2,
    stats 5; XStat: metadata_id 1, str_value 5, ref_value 7).  Where two
    programs of one trace hold an op of the same name, a path with a
    program scope wins: the reducer sums such ops under the one name too."""
    paths: dict = {}
    for number, plane in _fields(raw):
        if number != 1:
            continue
        name = next((v for n, v in _fields(plane) if n == 2), b"").decode()
        if not reduce_trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {key: dict(_fields(meta)).get(2, b"").decode()
                      for key, meta in _map_entries(plane, 5).items()}
        for meta in _map_entries(plane, 4).values():
            op, path = None, ""
            for n, v in _fields(meta):
                if n == 2:
                    op = reduce_trace.parse_op(v.decode())[0]
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    path = (stat[5].decode() if 5 in stat
                            else stat_names.get(stat.get(7), ""))
            if op is not None and (op not in paths or scope_of(path)):
                paths[op] = path
    return paths


def scope_of(path: str):
    """The innermost program scope of a scope path, or None."""
    for part in reversed(path.rstrip(":").split("/")):
        m = SCOPE.match(part)
        if m:
            return m.group(1)
    return None


# --- the host's spans against the device's idle time -----------------------


def _host_lines(profile) -> list:
    """``[[(name, start_ns, end_ns)]]`` per host thread: its ``job`` and
    ``cocoa/`` events."""
    lines = []
    for plane in profile.planes:
        if reduce_trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events
                      if ev.name == "job" or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append(sorted(events, key=lambda ev: ev[1]))
    return lines


def _device0_busy(profile) -> list:
    """Merged busy intervals of the first device that ran an op."""
    planes = sorted((p for p in profile.planes
                     if reduce_trace.DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in planes:
        ops = reduce_trace._line_events(plane, reduce_trace.OPS_LINE)
        if ops:
            return reduce_trace._union([(s, e) for _, s, e in ops])
    return []


def attribute(idle: list, spans: list) -> dict:
    """``{span name or None: ns}``: every instant of the ``idle`` intervals
    under the innermost of ``spans`` (``[(name, start, end)]``, one
    thread's) open at it."""
    out: dict = {}
    for lo, hi in idle:
        inside = [sp for sp in spans if sp[1] < hi and sp[2] > lo]
        cuts = sorted({lo, hi, *(t for _, s, e in inside for t in (s, e)
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [sp for sp in inside if sp[1] <= a and sp[2] >= b]
            name = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                    if open_ else None)
            out[name] = out.get(name, 0) + (b - a)
    return out


def read_bytes(raw: bytes) -> Phases:
    """A serialized XSpace (the bytes of an ``.xplane.pb``) as Phases."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(raw)
    lines = _host_lines(profile)
    busy = _device0_busy(profile)
    jobs, spanned = [], False
    for line in lines:
        mine = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in line
                if n != "job"]
        for _, s, e in (ev for ev in line if ev[0] == "job"):
            inside = [sp for sp in mine if sp[1] < e and sp[2] > s]
            spanned |= bool(inside)
            idle = reduce_trace._subtract([[s, e]],
                                          reduce_trace._clip(busy, s, e))
            by_span = {k: 1e-9 * v
                       for k, v in attribute(idle, inside).items()}
            jobs.append({"start_ns": s, "idle_s": sum(by_span.values()),
                         "by_span": by_span})
    jobs.sort(key=lambda j: j["start_ns"])
    paths = op_paths(raw)
    scopes = {op: scope_of(path) for op, path in paths.items()}
    return Phases(jobs=jobs, scopes=scopes, paths=paths, spans=lines,
                  spanned=spanned, scoped=any(scopes.values()))


@functools.lru_cache(maxsize=4)
def read_file(path: str) -> Phases:
    with open(path, "rb") as f:
        return read_bytes(f.read())


def load(cell: dict):
    """The Phases of the cell's traced run, or None where no trace was
    left."""
    try:
        return read_file(reduce_trace.find_xplane(
            os.path.join(OUT, cell["name"] + ".trace")))
    except FileNotFoundError:
        return None


def scope_seconds(phases: Phases, ops: dict) -> dict:
    """``{scope or None: seconds}`` of ``TraceSummary.ops`` (op name -> self
    seconds): every op under exactly one key, so the values add up to the
    busy time."""
    out: dict = {}
    for op, seconds in ops.items():
        scope = phases.scopes.get(op)
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def main(argv=None) -> int:
    """``python -m chipbench.phases CELL``: the whole table of the trace a
    ``--trace 1`` run of CELL left, as JSON."""
    import json
    import statistics

    cell = {"name": (argv or sys.argv[1:])[0]}
    phases = load(cell)
    if phases is None:
        print(f"no trace under {OUT}/{cell['name']}.trace", file=sys.stderr)
        return 1
    trace = reduce_trace.summarize_file(os.path.join(
        OUT, cell["name"] + ".trace"))
    names = sorted({k for j in phases.jobs for k in j["by_span"]}, key=str)
    by_scope = scope_seconds(phases, trace.ops)
    print(json.dumps({
        "jobs": len(phases.jobs), "busy_s": trace.busy_s,
        "idle_s_per_job": statistics.median(
            j["idle_s"] for j in phases.jobs) if phases.jobs else None,
        "idle_s_by_span": {str(n): statistics.median(
            j["by_span"].get(n, 0.0) for j in phases.jobs) for n in names},
        "busy_share_by_scope": {str(k): 100.0 * v / trace.busy_s
                                for k, v in by_scope.items()},
        "unscoped_ops": sorted(
            ((s, op, phases.paths.get(op, "")) for op, s in trace.ops.items()
             if phases.scopes.get(op) is None), reverse=True)[:8]},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
