"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

A TPU device plane (`/device:TPU:<i>`) holds a line of XLA ops, one event
per op executed on the chip, and a line of XLA modules, one event per
program run, named after the jitted function (`jit__decode(...)`). Host
planes hold the harness's `TraceAnnotation` spans on the Python thread.
All events share one clock in nanoseconds.

- busy: the union of the op intervals inside the window, per chip;
- idle share: 1 - busy / window;
- device time of a program: the summed durations of its module events;
- breakdown: the ops that took most time, and the longest gaps between ops
  named by the innermost host span open at the gap's middle.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    end: float            # ns


@dataclasses.dataclass
class Trace:
    ops: list[list[Event]]        # per chip, sorted by start
    modules: list[list[Event]]    # per chip
    host: list[Event]             # host spans, every host line


def _events(line) -> list[Event]:
    return sorted((Event(e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                   for e in line.events), key=lambda e: e.start)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                ops.append(_events(lines[OPS_LINE]))
                modules.append(_events(lines[MODULES_LINE])
                               if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.end > e.start)
    return Trace(ops, modules, sorted(host, key=lambda e: e.start))


def union(events: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged intervals of the events, clipped to [t0, t1]."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, f = max(e.start, t0), min(e.end, t1)
        if f <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], f)
        else:
            out.append([s, f])
    return [(s, f) for s, f in out]


def busy_s(trace: Trace, t0: float, t1: float) -> float:
    """Seconds in which an op ran, averaged over the chips."""
    per_chip = [sum(f - s for s, f in union(ops, t0, t1)) for ops in trace.ops]
    return sum(per_chip) / max(1, len(per_chip)) / 1e9


def idle_share(trace: Trace, t0: float, t1: float) -> float:
    return 1.0 - busy_s(trace, t0, t1) / ((t1 - t0) / 1e9)


def module_name(event_name: str) -> str:
    """The HLO module name of a module event, without the run id XLA
    appends in parentheses (`jit__decode(123)` -> `jit__decode`)."""
    return re.sub(r"\(\d*\)$", "", event_name)


def program_s(trace: Trace, names: set[str], t0: float, t1: float) -> float:
    """Device seconds of the programs whose HLO module is one of `names`,
    averaged over the chips."""
    per_chip = [sum(min(e.end, t1) - max(e.start, t0) for e in mods
                    if module_name(e.name) in names and e.end > t0 and e.start < t1)
                for mods in trace.modules]
    return sum(per_chip) / max(1, len(per_chip)) / 1e9


def span_window(trace: Trace, name: str) -> tuple[float, float]:
    """From the first start to the last end of the host spans `name`."""
    spans = [e for e in trace.host if e.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def top_ops(trace: Trace, t0: float, t1: float, n: int = 10) -> list[list]:
    """[name, seconds] of the ops with most device time (first chip)."""
    tot: dict[str, float] = defaultdict(float)
    for e in trace.ops[0] if trace.ops else []:
        if e.end > t0 and e.start < t1:
            tot[e.name] += (min(e.end, t1) - max(e.start, t0)) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _label_gaps(host: list[Event], gaps: list[tuple[float, float]]) -> list[str]:
    """The innermost (shortest) host span open at each gap's middle; gaps
    sorted by start, host spans by start."""
    import heapq

    out, active, i = [], [], 0
    for s, f in gaps:
        t = (s + f) / 2
        while i < len(host) and host[i].start <= t:
            heapq.heappush(active, (host[i].end, i))
            i += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        best = min((host[j] for _, j in active),
                   key=lambda e: e.end - e.start, default=None)
        out.append(best.name if best else "none")
    return out


def idle_gaps(trace: Trace, t0: float, t1: float, n: int = 10,
              min_gap_ns: float = 10e3) -> list[list]:
    """[host span, seconds] of the gaps between ops (first chip), summed by
    the host span open in them, longest first. Gaps under `min_gap_ns`
    (between the ops of one program) are summed as "between ops"."""
    if not trace.ops:
        return []
    busy = union(trace.ops[0], t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    long = [g for g in gaps if g[1] - g[0] >= min_gap_ns]
    tot: dict[str, float] = defaultdict(float)
    tot["between ops"] = sum(f - s for s, f in gaps
                             if f - s < min_gap_ns) / 1e9
    for (s, f), name in zip(long, _label_gaps(trace.host, long)):
        tot[name] += (f - s) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]
            if v > 0]
