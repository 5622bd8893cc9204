"""From the profiler's ``.xplane.pb`` to what the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  What is taken:

- device planes (``/device:TPU:<i>``), line ``XLA Ops``: every HLO op that
  ran, with start and duration.  *Busy* is the union of those intervals
  inside the traced window, so ops that overlap or nest are counted once.
  An op that contains other ops (a ``while`` or ``conditional`` shell
  spans its whole body) keeps only its *self* time — the instants no op
  inside it covers — so per-op totals add up to busy time and a shell
  never tops the list for work its children did.
- device planes, line ``XLA Modules``: one event per program launch.
- the host plane: the benchmark's own ``jax.profiler.TraceAnnotation``
  spans (``window``, ``job``, ``job/call``, ``job/wait``, ``job/fetch``),
  on the same clock, so that every idle gap of the device can be labelled
  with what the host was doing.

Several devices: times are averaged over the device planes (an SPMD
program keeps them in step); idle gaps are those of the first device.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("window", "job")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


@dataclasses.dataclass
class TraceSummary:
    window_s: float            # length of the traced window
    n_devices: int
    busy_s: float              # union of device-op intervals, mean per device
    ops: dict                  # op name -> self seconds, mean per device
    collective_s: float        # self seconds of collective ops
    collective_exposed_s: float  # ... during which no other op ran
    launches: float            # program launches, mean per device
    jobs: list                 # per ``job`` span, averaged alike: start_s,
                               # end_s, busy_s, launches
    gaps: list                 # [(label, seconds)] idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list) -> list:
    """Sorted, merged copy of ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _subtract(merged: list, cover: list) -> list:
    """The parts of ``merged`` that ``cover`` (merged too) leaves open."""
    out, j = [], 0
    for s, e in merged:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < e:
            out.append([at, e])
    return out


def _self_times(events: list) -> list:
    """``[(name, start, end, self_ns, shell)]``: each op's duration less the
    time ops that start inside it cover (direct children; theirs is taken
    off in turn), and whether it is a shell — an op that wholly contains
    another."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    inside = [0.0] * len(order)
    shell = [False] * len(order)
    stack = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent_end = order[stack[-1]][2]
            inside[stack[-1]] += min(e, parent_end) - s
            shell[stack[-1]] |= e <= parent_end
        stack.append(i)
    return [(n, s, e, max(0.0, (e - s) - inside[i]), shell[i])
            for i, (n, s, e) in enumerate(order)]


def parse_op(text: str) -> tuple:
    """``(name, opcode)`` of an op event.  The TPU's trace prints an op as
    its whole HLO line, ``%psum.19 = f32[160000]{0} all-reduce(...)``: the
    instruction's name says which op (and stays the same when shapes
    change), the opcode says what it is — JAX names an all-reduce after
    its primitive, ``psum``.  A bare name is its own opcode."""
    if " = " not in text:
        return text, text
    name, rest = text.split(" = ", 1)
    end = rest.find(" ")
    if rest.startswith("("):            # a tuple shape: skip to its close
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    return name.lstrip("%"), rest[end:].lstrip().split("(", 1)[0]


def _line_events(plane, line_name: str, opcodes: dict = None) -> list:
    events = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            name, opcode = parse_op(ev.name)
            if opcodes is not None:
                opcodes[name] = opcode
            events.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def host_spans(profile) -> list:
    """The benchmark's own spans, ``[(name, start_ns, end_ns)]``."""
    spans = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split("/")[0] in SPAN_PREFIXES:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda sp: sp[1])


def _label(spans: list, at: float) -> str:
    """The innermost benchmark span open at time ``at``."""
    open_ = [sp for sp in spans if sp[1] <= at < sp[2] and sp[0] != "window"]
    if not open_:
        return "between jobs"
    return min(open_, key=lambda sp: sp[2] - sp[1])[0]


def summarize(profile, max_gaps: int = 10) -> TraceSummary:
    """Reduce a ``ProfileData`` to a :class:`TraceSummary`."""
    planes = sorted((p for p in profile.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    opcodes: dict = {}
    per_dev = [(_line_events(p, OPS_LINE, opcodes),
                _line_events(p, MODULES_LINE)) for p in planes]
    per_dev = [(ops, mods) for ops, mods in per_dev if ops]
    if not per_dev:
        raise ValueError("the trace holds no device operation: no plane "
                         f"{DEVICE_PLANE.pattern} with a line {OPS_LINE!r}")
    spans = host_spans(profile)
    window = [sp for sp in spans if sp[0] == "window"]
    if window:
        lo, hi = window[0][1], window[-1][2]
    else:
        lo = min(s for ops, _ in per_dev for _, s, _ in ops)
        hi = max(e for ops, _ in per_dev for _, _, e in ops)
    n = len(per_dev)
    job_spans = [sp for sp in spans if sp[0] == "job"]
    jobs = [dict(start_s=(s - lo) * 1e-9, end_s=(e - lo) * 1e-9, busy_s=0.0,
                 launches=0.0) for _, s, e in job_spans]
    ops_total: dict = {}
    busy = coll = exposed = launches = 0.0
    gaps = []
    for dev, (ops, mods) in enumerate(per_dev):
        ops = [(nm, max(s, lo), min(e, hi)) for nm, s, e in ops
               if min(e, hi) > max(s, lo)]
        merged = _union([(s, e) for _, s, e in ops])
        busy += _length(merged) / n
        coll_iv, other_iv = [], []
        for name, s, e, self_ns, shell in _self_times(ops):
            ops_total[name] = ops_total.get(name, 0.0) + self_ns * 1e-9 / n
            if COLLECTIVE.match(opcodes[name]):
                coll += self_ns * 1e-9 / n
                coll_iv.append((s, e))
            elif not shell:
                other_iv.append((s, e))
        exposed += _length(_subtract(_union(coll_iv),
                                     _union(other_iv))) * 1e-9 / n
        starts = [s for _, s, _ in mods if lo <= s < hi]
        launches += len(starts) / n
        for job, (_, s, e) in zip(jobs, job_spans):
            job["busy_s"] += _length(_clip(merged, s, e)) * 1e-9 / n
            job["launches"] += sum(s <= t < e for t in starts) / n
        if dev == 0:
            idle = sorted(_subtract([[lo, hi]], merged),
                          key=lambda g: g[0] - g[1])[:max_gaps]
            gaps = [(_label(spans, s), (e - s) * 1e-9) for s, e in idle]
    return TraceSummary(window_s=(hi - lo) * 1e-9, n_devices=n,
                        busy_s=busy * 1e-9, ops=ops_total, collective_s=coll,
                        collective_exposed_s=exposed, launches=launches,
                        jobs=jobs, gaps=gaps)


def summarize_file(path: str, **kw) -> TraceSummary:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return summarize(ProfileData.from_file(path), **kw)
