"""Profiler trace capture and its reduction to busy time, the time of the
slot loop and of the collectives, and idle gaps.

A traced run wraps its measured window in ``jax.profiler`` tracing and
marks its own host spans with ``jax.profiler.TraceAnnotation`` (names
starting ``bench.``), so they land in the same trace, on the same clock,
as the device's operations.  :func:`load` turns the ``.xplane.pb`` file
into plain interval lists; everything after that is arithmetic on
``(name, start_ns, end_ns)`` tuples, checked by the tests on a recorded
trace and on synthetic events.

Which events are device operations:

* on a TPU, every event on the ``XLA Ops`` line of a ``/device:TPU:<k>``
  plane (device ``k``);
* on the CPU backend (the recorded test fixture), every host event that
  carries an ``hlo_op`` stat (device ``device_ordinal``).

A TPU names each op by its HLO text (``%all_to_all.13 = f32[2,16345,1]
all-to-all(%reshape.148), ...``), so an op's kind is its opcode, read
from that text: ``all-to-all`` (with ``-start``/``-done`` when async) for
the exchange's collectives, ``while`` for a loop, whose body's ops run
inside its interval on the same line.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OPS_LINE = "XLA Ops"
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
COLLECTIVES = ("all-to-all", "all-to-all-start", "all-to-all-done")
LOOPS = ("while",)
NS = 1e-9


@dataclasses.dataclass
class Events:
    """A trace as interval lists: device operations per device id, the
    benchmark's own spans, and every event of the host thread that
    recorded those spans (for naming what the host did in an idle gap)."""

    device_ops: Dict[int, List[Event]]
    spans: List[Event]
    host_thread: List[Event]


def _event(e) -> Event:
    return (e.name, e.start_ns, e.start_ns + e.duration_ns)


def op_name(name: str) -> str:
    """A device op's HLO instruction name: a TPU trace names each op by
    its whole HLO text (``%fusion.7 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """A device op's HLO opcode, from the text a TPU trace names it by.
    The CPU backend names an op by its instruction name alone, which for
    an all-to-all or a loop is its opcode and a number (``while.13``)."""
    if " = " not in name:
        return re.sub(r"\.\d+$", "", name)
    m = OPCODE.search(name.split(" = ", 1)[1])
    return m.group(1) if m else ""


def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """ns per op name of one device's ops, each op's nested ops (a while
    loop's body on the same line) taken out of its own time."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Event] = []
    for ev in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= ev[1]:
            stack.pop()
        if stack and ev[2] <= stack[-1][2]:
            out[op_name(stack[-1][0])] -= ev[2] - ev[1]
        out[op_name(ev[0])] += ev[2] - ev[1]
        stack.append(ev)
    return out


def load(path: str) -> Events:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = defaultdict(list)
    spans: List[Event] = []
    host_thread: List[Event] = []
    on_tpu = any(DEVICE_PLANE.match(p.name) for p in pd.planes)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops[int(m.group(1))].extend(
                        _event(e) for e in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name.startswith(SPAN_PREFIX) for e in events):
                host_thread.extend(_event(e) for e in events)
                spans.extend(_event(e) for e in events
                             if e.name.startswith(SPAN_PREFIX))
            if on_tpu:
                continue
            for e in events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    device_ops[int(stats.get("device_ordinal", 0))].append(
                        _event(e))
    by_start = lambda ev: (ev[1], -ev[2])
    return Events(dict(device_ops), sorted(spans, key=by_start),
                  sorted(host_thread, key=by_start))


# -- interval arithmetic -----------------------------------------------------

class Merged:
    """Sorted, disjoint union of closed intervals, searchable by time."""

    def __init__(self, intervals: Sequence[Interval]):
        out: List[List[float]] = []
        for s, e in sorted(intervals):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        self.starts = [s for s, _ in out]
        self.ends = [e for _, e in out]

    def covered(self, lo: float, hi: float) -> float:
        """Length of ``[lo, hi]`` that the union covers."""
        i = bisect.bisect_right(self.ends, lo)
        total = 0.0
        while i < len(self.starts) and self.starts[i] < hi:
            total += min(self.ends[i], hi) - max(self.starts[i], lo)
            i += 1
        return total

    def gaps(self, lo: float, hi: float) -> List[Interval]:
        """The parts of ``[lo, hi]`` that the union does not cover."""
        out, t = [], lo
        i = bisect.bisect_right(self.ends, lo)
        while i < len(self.starts) and self.starts[i] < hi:
            if self.starts[i] > t:
                out.append((t, self.starts[i]))
            t = max(t, self.ends[i])
            i += 1
        if t < hi:
            out.append((t, hi))
        return out


class Nest:
    """Properly nested events of one thread (sorted by start, longer
    first on ties), searchable for the innermost one holding a time."""

    def __init__(self, events: Sequence[Event]):
        self.events = list(events)
        self.starts = [ev[1] for ev in self.events]
        self.parent: List[int] = []
        stack: List[int] = []
        for i, (_, s, _) in enumerate(self.events):
            while stack and self.events[stack[-1]][2] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def innermost(self, t: float) -> str:
        """Name of the innermost event that contains ``t``; "" if none.
        A container of ``t`` contains the last event that starts before
        ``t``, so it is found among that event's ancestors."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.events[i][2] < t:
            i = self.parent[i]
        return self.events[i][0] if i >= 0 else ""


def host_activity(ev: Events, lo: float, hi: float):
    """What the host thread did over ``[lo, hi]``, piecewise: boundaries
    ``b`` and a label for each ``[b[i], b[i+1]]``, "<span>" or
    "<span>><event>": the innermost benchmark span ("window" outside
    them) and the innermost other host event inside it."""
    spans = Nest([s for s in ev.spans if s[0] != WINDOW_SPAN])
    thread = Nest([h for h in ev.host_thread if h[0] != WINDOW_SPAN])
    bounds = sorted({t for _, s, e in ev.host_thread for t in (s, e)
                     if lo < t < hi} | {lo, hi})
    labels = []
    for a, b in zip(bounds, bounds[1:]):
        t = (a + b) / 2
        where = spans.innermost(t) or "window"
        what = thread.innermost(t)
        labels.append(where if not what or what.startswith(SPAN_PREFIX)
                      else f"{where}>{what}")
    return bounds, labels


# -- the reduction -----------------------------------------------------------

@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from one traced window (seconds)."""

    window_s: float
    busy_s: Dict[int, float]                 # per device, inside the window
    span_walls: Dict[str, List[float]]       # per span name, each duration
    span_host_s: Dict[str, List[float]]      # each span's wall minus the
    #                                          time any device was busy in it
    span_busy_dev: Dict[str, Dict[int, float]]  # per device, summed over spans
    span_loop_dev: Dict[str, Dict[int, float]]  # ... only ``while`` ops
    span_collective_dev: Dict[str, Dict[int, float]]  # ... all-to-alls
    device_ops: List[Tuple[str, float]]      # top ops by self time, s
    #                                          averaged over the devices
    idle_gaps: List[Tuple[str, float]]       # idle s by host activity

    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def reduce(ev: Events, devices: Sequence[int], top: int = 10) -> Summary:
    """Reduce a trace to a :class:`Summary` over ``devices`` (the chips the
    cell uses), inside the ``bench.window`` span."""
    win = [s for s in ev.spans if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"trace holds {len(win)} {WINDOW_SPAN} spans")
    _, w0, w1 = win[0]
    ops = {d: ev.device_ops.get(d, []) for d in devices}
    merged = {d: Merged([(s, e) for _, s, e in ops[d]]) for d in devices}
    any_busy = Merged([(s, e) for d in devices for _, s, e in ops[d]])

    def of_kind(kinds):
        return {d: Merged([(s, e) for n, s, e in ops[d]
                           if opcode(n) in kinds]) for d in devices}
    by_kind = {"busy": merged, "loop": of_kind(LOOPS),
               "collective": of_kind(COLLECTIVES)}

    walls: Dict[str, List[float]] = defaultdict(list)
    host: Dict[str, List[float]] = defaultdict(list)
    sdev = {k: defaultdict(lambda: defaultdict(float)) for k in by_kind}
    for name, s, e in ev.spans:
        if name == WINDOW_SPAN or s < w0 or e > w1:
            continue
        walls[name].append((e - s) * NS)
        host[name].append((e - s - any_busy.covered(s, e)) * NS)
        for k, per_dev in by_kind.items():
            for d in devices:
                sdev[k][name][d] += per_dev[d].covered(s, e) * NS
    per_op: Dict[str, float] = defaultdict(float)
    for d in devices:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops[d]
                   if e > w0 and s < w1]
        for n, t in self_times(clipped).items():
            per_op[n] += t * NS / len(devices)
    idle: Dict[str, float] = defaultdict(float)
    bounds, labels = host_activity(ev, w0, w1)
    for s, e in any_busy.gaps(w0, w1):
        i = max(0, bisect.bisect_right(bounds, s) - 1)
        while i < len(labels) and bounds[i] < e:
            idle[labels[i]] += (min(e, bounds[i + 1]) - max(s, bounds[i])) * NS
            i += 1
    return Summary(
        window_s=(w1 - w0) * NS,
        busy_s={d: merged[d].covered(w0, w1) * NS for d in devices},
        span_walls=dict(walls), span_host_s=dict(host),
        span_busy_dev={k: dict(v) for k, v in sdev["busy"].items()},
        span_loop_dev={k: dict(v) for k, v in sdev["loop"].items()},
        span_collective_dev={k: dict(v)
                             for k, v in sdev["collective"].items()},
        device_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top])


class Tracer:
    """``jax.profiler`` tracing into a fresh directory under ``TMPDIR``,
    read back and deleted on :meth:`stop`."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-function host events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self, devices: Sequence[int]) -> Summary:
        import jax
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            return reduce(load(files[0]), devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
