"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reads: device busy time, per-kernel and per-program device time, the
program's host spans, and idle gaps attributed to the host spans open
during them.

:func:`load` reads the file into plain event lists; :func:`reduce` does the
arithmetic on those lists, so it can be checked on events written by hand.

* Device events: every ``/device:TPU:<i>`` plane's ``XLA Ops`` line (one
  event per operation run) and ``XLA Modules`` line (one per program run).
  An operation is named by its HLO name without the numeric suffix
  (``%select_topk.7 = ...`` -> ``select_topk``); a program by its name
  without the fingerprint (``jit_run(4796...)`` -> ``jit_run``).
* Host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` events,
  whose names start with ``bench.``, and the program's ``repro.*`` spans
  with their numeric stats (counts), taken only from the host thread that
  holds ``bench.window``: the window's thread drives the timed path, and
  spans of one thread nest.  ``bench.window`` bounds the window.
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; the idle share is 1 - busy / window.
* Each instant of the window belongs to the innermost host span open then
  (a span's self time: its time less its children's).  A device's idle
  interval is split over the spans whose self time it overlaps.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

#: (name, start_ns, duration_ns); a host span may carry its counts as a
#: fourth field, ``{stat: number}``
Event = Tuple[str, float, float]

WINDOW = "bench.window"
PROGRAM = "repro."
OUTSIDE = "outside bench spans"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo: str) -> str:
    """``%select_topk.7 = (...) custom-call(...)`` -> ``select_topk``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(name: str) -> str:
    """``jit_run(4796221817080798425)`` -> ``jit_run``."""
    return name.split("(", 1)[0]


def load(path: str) -> Dict:
    """The events of the trace file at ``path`` (see :func:`from_planes`)."""
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes)


def counts(stats) -> Dict[str, float]:
    """The numeric stats of an event (bools and strings left out)."""
    return {k: v for k, v in stats
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def from_planes(planes) -> Dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}``: device events as ``(name, start_ns, dur_ns)``, host
    spans ``bench.*`` likewise and ``repro.*`` with their counts."""
    out = {"devices": {}, "host": []}
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in events
                                if e.name.startswith("bench.")]
                if any(e.name == WINDOW for e in events):
                    out["host"] += [(e.name, e.start_ns, e.duration_ns,
                                     counts(e.stats)) for e in events
                                    if e.name.startswith(PROGRAM)]
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(a: float, b: float, lo: float, hi: float):
    return max(a, lo), min(b, hi)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over devices
    op_s: Dict[str, float]              # op name -> device seconds
    op_calls: Dict[str, int]
    module_s: Dict[str, float]          # program name -> device seconds
    module_calls: Dict[str, int]
    module_op_s: Dict[Tuple[str, str], float]
    idle_by_span: Dict[str, float]      # host span -> idle device seconds
    span_s: Dict[str, float]            # host span -> summed seconds
    span_self_s: Dict[str, float]       # host span -> its self seconds
    span_calls: Dict[str, int]          # host span -> spans in the window
    span_counts: Dict[str, Dict[str, float]]   # host span -> summed counts

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, op: str, module: str = None) -> float:
        if module is None:
            return self.op_s.get(op, 0.0)
        return self.module_op_s.get((module, op), 0.0)

    def idle_under(self, prefix: str) -> Optional[float]:
        """Idle device seconds in the self time of the spans named
        ``prefix...``; None where no such span overlaps the window."""
        if not any(n.startswith(prefix) for n in self.span_calls):
            return None
        return sum(s for n, s in self.idle_by_span.items()
                   if n.startswith(prefix))

    def self_ms_per_call(self, name: str) -> Optional[float]:
        """Mean self time (ms) of the span ``name`` over its calls that
        overlap the window, a call cut by the window's edge counting the
        part inside it; None where none overlaps the window."""
        n = self.span_calls.get(name, 0)
        return 1e3 * self.span_self_s.get(name, 0.0) / n if n else None

    def breakdown(self) -> Dict[str, List]:
        ops = collections.Counter({f"{m}:{o}": s for (m, o), s
                                   in self.module_op_s.items()})
        gaps = collections.Counter(self.idle_by_span)
        return {"device_ops": [[k, v] for k, v in ops.most_common(10)],
                "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def reduce(trace: Dict) -> Reduced:
    host = [(e[0], e[1], e[2], e[3] if len(e) > 3 else {})
            for e in trace["host"]]
    wins = [(s, s + d) for n, s, d, _ in host if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = wins[0]
    window_ns = hi - lo
    spans = []
    span_s, span_calls = collections.Counter(), collections.Counter()
    span_counts = collections.defaultdict(collections.Counter)
    for n, s, d, c in host:
        if n == WINDOW:
            continue
        a, b = clip(s, s + d, lo, hi)
        if b > a:
            spans.append((n, a, b))
            span_s[n] += (b - a) / 1e9
        # calls and counts of the same spans whose self time is summed
        if b > a or lo <= s < hi:
            span_calls[n] += 1
            span_counts[n].update(c)
    owners = self_intervals(spans, lo, hi)
    span_self_s = collections.Counter()
    for a, b, n in owners:
        if n != OUTSIDE:
            span_self_s[n] += (b - a) / 1e9

    op_s, op_calls = collections.Counter(), collections.Counter()
    module_s, module_calls = collections.Counter(), collections.Counter()
    module_op_s = collections.Counter()
    idle = collections.Counter()
    busy_total = 0.0
    devices = trace["devices"]
    n_dev = max(len(devices), 1)
    for dev in devices.values():
        mods = sorted((s, s + d, module_name(n)) for n, s, d in dev["modules"]
                      if lo <= s < hi)
        for a, b, m in mods:
            module_s[m] += (b - a) / 1e9
            module_calls[m] += 1
        ops = sorted((s, s + d, op_name(n)) for n, s, d in dev["ops"]
                     if lo <= s < hi)
        j = 0
        for a, b, o in ops:
            op_s[o] += (b - a) / 1e9
            op_calls[o] += 1
            while j < len(mods) and mods[j][1] < a:
                j += 1
            m = mods[j][2] if j < len(mods) and mods[j][0] <= a else "-"
            module_op_s[(m, o)] += (b - a) / 1e9
        busy = union([clip(a, b, lo, hi) for a, b, _ in ops])
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, ns in split(gaps, owners).items():
            idle[name] += ns / n_dev / 1e9
    return Reduced(window_s=window_ns / 1e9,
                   busy_s=busy_total / n_dev / 1e9,
                   op_s=dict(op_s), op_calls=dict(op_calls),
                   module_s=dict(module_s), module_calls=dict(module_calls),
                   module_op_s=dict(module_op_s),
                   idle_by_span=dict(idle), span_s=dict(span_s),
                   span_self_s=dict(span_self_s),
                   span_calls=dict(span_calls),
                   span_counts={n: dict(c) for n, c in span_counts.items()})


def self_intervals(spans, lo: float, hi: float):
    """``[(a, b, name)]``: the window ``[lo, hi)`` cut where the innermost
    open span changes, each piece named by that span (``OUTSIDE`` where none
    is open).  ``spans`` are ``(name, start, end)`` inside the window, from
    nested ``with`` blocks of one thread, so a stack of the open ones is
    enough; a span that outlives its parent keeps the time it outlives it."""
    out, stack, cur = [], [], lo

    def emit(t):
        nonlocal cur
        if t > cur:
            out.append((cur, t, stack[-1][0] if stack else OUTSIDE))
            cur = t

    def close_to(t):
        while stack and stack[-1][2] <= t:
            emit(stack[-1][2])
            stack.pop()

    for sp in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_to(sp[1])
        emit(sp[1])
        stack.append(sp)
    close_to(hi)
    emit(hi)
    return out


def split(gaps, owners) -> Dict[str, float]:
    """Each of the ascending disjoint ``gaps`` split over the ``owners``
    (:func:`self_intervals`) it overlaps: ``{name: overlapped ns}``."""
    out = collections.Counter()
    j = 0
    for a, b in gaps:
        while j < len(owners) and owners[j][1] <= a:
            j += 1
        k = j
        while k < len(owners) and owners[k][0] < b:
            x, y, name = owners[k]
            out[name] += min(b, y) - max(a, x)
            k += 1
    return out
