"""The program's own spans and scopes in a profiler trace.

The program marks its host steps with ``jax.profiler.TraceAnnotation``
(names starting ``repro.``: ``repro.apply`` and its steps ``repro.pack``,
``repro.stage``, ``repro.dispatch``, ``repro.fetch``, ``repro.unpack``;
``repro.cg.init`` and ``repro.cg.iteration``) and names its device work
with ``jax.named_scope`` (``repro.exchange.<phase>``, ``repro.buffers``,
``repro.local``, ``repro.abft``).  :mod:`bench.trace` reads the
benchmark's own spans and the device ops; this module adds, on the same
``Events``, what the program recorded:

* each program step's wall and host time (wall minus the time any chip
  was busy in it) and how many there were;
* device time per (host span, scope, device), with ``unscoped`` for
  busy time under no program scope;
* the time of each span name nested in each other;
* idle gaps labelled ``<bench span>><program step>[><host event>]``.

Neither the TPU's trace nor the CPU's carries an op's ``op_name``
metadata: a TPU op is named by its HLO text without it, a CPU op by its
instruction name.  So :func:`program_scopes` reads the metadata from the
HLO text of every executable the process holds, once after the window,
and a device op finds its module by the ``XLA Modules`` line of its TPU
plane, or by the ``hlo_module`` stat on the CPU.  An executable read from
a persistent compilation cache keeps the metadata it was compiled with:
jax leaves metadata out of the cache key.

``bench/run.py`` does not call this module yet: that takes an edit of
``bench/trace.py`` and ``bench/run.py`` (PERF.md, open questions).  Run a
cell with the program's view beside its result line:

    python bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import re
import shutil
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as T  # noqa: E402
from bench.roofline import share_pct  # noqa: E402

PROGRAM_PREFIX = "repro."
UNSCOPED = "unscoped"
EXCHANGE = "repro.exchange."
APPLY_STEPS = ("repro.pack", "repro.stage", "repro.dispatch", "repro.fetch",
               "repro.unpack")
HLO_LINE = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"',
                      re.MULTILINE)
SCOPE = re.compile(r"(?:^|/)(repro\.[^/]+)")
MODULES_LINE = "XLA Modules"

Scopes = Dict[str, Dict[str, str]]      # module -> instruction -> scope


def scope_of(op_path: str) -> str:
    """The innermost ``repro.`` scope of an ``op_name`` path, "" if none."""
    found = SCOPE.findall(op_path)
    return found[-1] if found else ""


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> program scope, from an HLO module's text."""
    out = {}
    for name, path in HLO_LINE.findall(text):
        if scope_of(path):
            out[name] = scope_of(path)
    return out


def program_scopes() -> Scopes:
    """Module name -> instruction name -> scope, over every executable
    the process holds.  An instruction that two modules of one name scope
    differently is left out."""
    import jax
    out: Scopes = {}
    clash = set()
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            mine = out.setdefault(module.name, {})
            for name, scope in hlo_scopes(module.to_string()).items():
                if mine.setdefault(name, scope) != scope:
                    clash.add((module.name, name))
    for module, name in clash:
        del out[module][name]
    return out


def op_scopes(path: str, scopes: Scopes) -> Dict[int, List[str]]:
    """The scope of each device op of :func:`bench.trace.load`'s
    ``device_ops``, in the same order; "" for none."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[int, List[str]] = defaultdict(list)
    on_tpu = any(T.DEVICE_PLANE.match(p.name) for p in pd.planes)
    for plane in pd.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              re.sub(r"\(\d+\)$", "", e.name))
                             for e in lines.get(MODULES_LINE, ()))
            starts = [mod[0] for mod in modules]
            for e in lines.get(T.DEVICE_OPS_LINE, ()):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = (modules[i][2] if i >= 0
                          and e.start_ns < modules[i][1] else "")
                out[int(m.group(1))].append(
                    scopes.get(module, {}).get(T.op_name(e.name), ""))
        elif plane.name.startswith("/host:") and not on_tpu:
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        out[int(stats.get("device_ordinal", 0))].append(
                            scopes.get(stats.get("hlo_module", ""), {})
                            .get(stats["hlo_op"], ""))
    return dict(out)


@dataclasses.dataclass
class ProgramSummary:
    """What the program recorded inside one traced window (seconds)."""

    counts: Dict[str, int]                   # spans per name, bench and
    #                                          program, inside the window
    step_walls: Dict[str, List[float]]       # per program span name
    step_host_s: Dict[str, List[float]]      # ... wall minus any chip busy
    busy_dev: Dict[str, Dict[int, float]]    # per span name, per device
    scope_dev: Dict[str, Dict[str, Dict[int, float]]]  # per span name,
    #                                          per scope, per device
    nested_s: Dict[str, Dict[str, float]]    # per span name, s of each
    #                                          span name nested in it
    idle_gaps: List[Tuple[str, float]]       # idle s by host activity


def _nested(spans: Sequence[T.Event]) -> Dict[str, Dict[str, float]]:
    """Seconds of each span name inside each enclosing span name (a name
    that encloses a span twice counts it once)."""
    nest = T.Nest(spans)
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for i, (name, s, e) in enumerate(nest.events):
        seen = set()
        j = nest.parent[i]
        while j >= 0:
            outer, os_, oe = nest.events[j]
            if os_ <= s and e <= oe and outer not in seen:
                seen.add(outer)
                out[outer][name] += (e - s) * T.NS
            j = nest.parent[j]
    return {k: dict(v) for k, v in out.items()}


def _labels(ev: T.Events, steps: Sequence[T.Event], lo: float, hi: float):
    """:func:`bench.trace.host_activity` with the program step between
    the benchmark span and the host event: "<span>[><step>][><event>]"."""
    spans = T.Nest([s for s in ev.spans if s[0] != T.WINDOW_SPAN])
    program = T.Nest(steps)
    thread = T.Nest([h for h in ev.host_thread if h[0] != T.WINDOW_SPAN])
    bounds = sorted({t for _, s, e in ev.host_thread for t in (s, e)
                     if lo < t < hi} | {lo, hi})
    labels = []
    for a, b in zip(bounds, bounds[1:]):
        t = (a + b) / 2
        what = thread.innermost(t)
        if what.startswith((T.SPAN_PREFIX, PROGRAM_PREFIX)):
            what = ""
        parts = (spans.innermost(t) or "window", program.innermost(t), what)
        labels.append(">".join(p for p in parts if p))
    return bounds, labels


def reduce(ev: T.Events, scopes: Dict[int, List[str]],
           devices: Sequence[int], top: int = 12) -> ProgramSummary:
    """Reduce the program's part of a trace over ``devices``, inside the
    ``bench.window`` span; ``scopes`` from :func:`op_scopes`."""
    (w0, w1), = [(s, e) for n, s, e in ev.spans if n == T.WINDOW_SPAN]
    steps = [h for h in ev.host_thread if h[0].startswith(PROGRAM_PREFIX)]
    ops = {d: ev.device_ops.get(d, []) for d in devices}
    kinds = {d: scopes.get(d, [""] * len(ops[d])) for d in devices}
    names = sorted({k for d in devices for k in kinds[d] if k})

    def union(want):
        return {d: T.Merged([(s, e) for (_, s, e), k in zip(ops[d], kinds[d])
                             if want(k)]) for d in devices}
    busy = union(lambda k: True)
    scoped = union(bool)
    by_scope = {k: union(lambda x, k=k: x == k) for k in names}
    any_busy = T.Merged([(s, e) for d in devices for _, s, e in ops[d]])

    inside = [sp for sp in ev.spans + steps
              if sp[0] != T.WINDOW_SPAN and w0 <= sp[1] and sp[2] <= w1]
    counts: Dict[str, int] = defaultdict(int)
    walls: Dict[str, List[float]] = defaultdict(list)
    host: Dict[str, List[float]] = defaultdict(list)
    busy_dev = defaultdict(lambda: defaultdict(float))
    scope_dev = defaultdict(lambda: defaultdict(lambda: defaultdict(float)))
    for name, s, e in inside:
        counts[name] += 1
        if name.startswith(PROGRAM_PREFIX):
            walls[name].append((e - s) * T.NS)
            host[name].append((e - s - any_busy.covered(s, e)) * T.NS)
        for d in devices:
            b = busy[d].covered(s, e) * T.NS
            busy_dev[name][d] += b
            scope_dev[name][UNSCOPED][d] += b - scoped[d].covered(s, e) * T.NS
            for k in names:
                scope_dev[name][k][d] += by_scope[k][d].covered(s, e) * T.NS
    idle: Dict[str, float] = defaultdict(float)
    bounds, labels = _labels(ev, steps, w0, w1)
    for s, e in any_busy.gaps(w0, w1):
        i = max(0, bisect.bisect_right(bounds, s) - 1)
        while i < len(labels) and bounds[i] < e:
            idle[labels[i]] += (min(e, bounds[i + 1])
                                - max(s, bounds[i])) * T.NS
            i += 1
    return ProgramSummary(
        counts=dict(counts), step_walls=dict(walls), step_host_s=dict(host),
        busy_dev={k: dict(v) for k, v in busy_dev.items()},
        scope_dev={k: {sc: dict(per) for sc, per in v.items()}
                   for k, v in scope_dev.items()},
        nested_s=_nested(sorted(inside, key=lambda sp: (sp[1], -sp[2]))),
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top])


# -- what the per-layer metrics would read ----------------------------------

def host_ms(ps: ProgramSummary, steps: Sequence[str]) -> Optional[float]:
    """Per apply (``repro.apply``), the host time of the program steps
    ``steps``, in ms; None where the trace holds none of them."""
    n = ps.counts.get("repro.apply", 0)
    host = [h for s in steps for h in ps.step_host_s.get(s, ())]
    if not n or not host:
        return None
    return sum(host) / n * 1e3


def pack_ms(ps: ProgramSummary) -> Optional[float]:
    """``repro.pack`` + ``repro.unpack``: the executor's numpy layout
    work."""
    return host_ms(ps, ("repro.pack", "repro.unpack"))


def transfer_ms(ps: ProgramSummary) -> Optional[float]:
    """``repro.stage`` + ``repro.fetch``: host to device and back."""
    return host_ms(ps, ("repro.stage", "repro.fetch"))


def scope_ms(ps: ProgramSummary, prefix: str,
             span: str = "bench.apply") -> Optional[float]:
    """Per ``span``, the device time of the ops under the scopes that
    start with ``prefix``, on the busiest chip, in ms; None where no op
    of such a scope ran."""
    n = ps.counts.get(span, 0)
    per = [v for k, v in ps.scope_dev.get(span, {}).items()
           if k.startswith(prefix) and k != UNSCOPED]
    if not n or not per or max(sum(p.values()) for p in per) <= 0.0:
        return None
    return max(sum(p.get(d, 0.0) for p in per) for d in per[0]) / n * 1e3


def exchange_ms(ps: ProgramSummary) -> Optional[float]:
    """Every exchange phase, its packing gathers and collectives."""
    return scope_ms(ps, EXCHANGE)


def local_spmv_roofline(run, ps: ProgramSummary) -> Optional[float]:
    """The local product's share of its HBM roofline, in %: the time of
    the ops under ``repro.local`` inside the applies, lowest chip, over
    the CSR-minimum bytes (``bench/roofline.py``); whatever implements
    the product."""
    local = ps.scope_dev.get("bench.apply", {}).get("repro.local")
    if not local or min(local.values()) <= 0.0:
        return None
    return share_pct(run, local)


def cg_host_ms(ps: ProgramSummary) -> Optional[float]:
    """Per CG iteration, the self time of ``repro.cg.iteration``: its
    wall minus the ``repro.apply`` spans inside it, in ms."""
    walls = ps.step_walls.get("repro.cg.iteration")
    if not walls:
        return None
    applies = ps.nested_s.get("repro.cg.iteration", {}).get("repro.apply",
                                                            0.0)
    return (sum(walls) - applies) / len(walls) * 1e3


def scopes_line(ps: ProgramSummary, span: str = "repro.apply") -> str:
    """Device ms per ``span`` per scope on the busiest chip, the share of
    its busy time under a program scope, and each step's host ms."""
    n = ps.counts.get(span, 0)
    busy = ps.busy_dev.get(span, {})
    if not n or not busy:
        return f"scopes: no {span} spans in the window"
    d = max(busy, key=busy.get)
    per = {k: v.get(d, 0.0) for k, v in ps.scope_dev[span].items()}
    scoped = busy[d] - per.get(UNSCOPED, 0.0)
    share = 100.0 * scoped / busy[d] if busy[d] > 0 else 0.0
    parts = [f"busy={busy[d] / n * 1e3:.6f}"]
    parts += [f"{k}={v / n * 1e3:.6f}"
              for k, v in sorted(per.items(), key=lambda kv: -kv[1])]
    steps = [f"{k}={sum(ps.step_host_s.get(k, ())) / n * 1e3:.6f}"
             for k in APPLY_STEPS]
    return (f"scopes (device ms per {span}, chip {d}, n={n}): "
            + " ".join(parts) + f" scoped_share={share:.3f}%"
            + " | host ms per apply: " + " ".join(steps))


# -- a traced run with the program's view -------------------------------------

def traced_run(cell, seed: int, seconds: float, devices, peaks):
    """``bench.run.run_cell`` with ``--trace 1``, keeping the program's
    view of the trace: returns (result, ProgramSummary, the Run the
    metric readers read)."""
    import glob
    import jax
    from bench import run as R
    seen: list = []
    runs: list = []

    class Tracer(T.Tracer):
        """bench.trace.Tracer that also reduces the program's view."""

        def stop(self, devices):
            jax.profiler.stop_trace()
            try:
                path, = glob.glob(os.path.join(
                    self.dir, "plugins", "profile", "*", "*.xplane.pb"))
                ev = T.load(path)
                seen.append(reduce(ev, op_scopes(path, program_scopes()),
                                   devices))
                return T.reduce(ev, devices)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    class Keep:
        """A metric reader that keeps the run it reads."""

        def __init__(self, reader):
            self.reader = reader

        def read(self, run):
            runs.append(run)
            return self.reader.read(run)

    cell.readers = {k: Keep(v) for k, v in cell.readers.items()}
    plain, T.Tracer = T.Tracer, Tracer
    try:
        result = R.run_cell(cell, seed, seconds, True, devices,
                            R.CompileClock(), peaks)
    finally:
        T.Tracer = plain
    return result, seen[0], runs[0]


def view(ps: ProgramSummary, run) -> Dict[str, Optional[float]]:
    """Every reading of the program's view, by name."""
    return {"pack_ms": pack_ms(ps), "transfer_ms": transfer_ms(ps),
            "exchange_ms": exchange_ms(ps),
            "buffers_ms": scope_ms(ps, "repro.buffers"),
            "local_ms": scope_ms(ps, "repro.local"),
            "local_spmv_roofline": local_spmv_roofline(run, ps),
            "cg_host_ms": cg_host_ms(ps)}


def main(argv=None) -> int:
    """Run one cell as ``bench/run.py --trace 1`` does, and print the
    program's view on stderr before the result line."""
    import argparse
    import json
    from bench import run as R
    from bench.peaks import peaks_for

    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        devices = R.find_chips(cell.chips)
    except R.NoChip as e:
        R.log(f"bench: {e}; nothing was measured")
        return 2
    R.log(f"compile cache: {R.use_compile_cache()}")
    result, ps, run = traced_run(cell, args.seed, args.seconds, devices,
                                 peaks_for(devices[0].device_kind))
    R.log(scopes_line(ps))
    R.log("program idle gaps (s): " + json.dumps(ps.idle_gaps))
    R.log("program view: " + json.dumps(view(ps, run)))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
