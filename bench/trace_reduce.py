"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reads: device busy time, per-kernel and per-program device time, and idle
gaps attributed to the host span that was open during them.

:func:`load` reads the file into plain event lists; :func:`reduce` does the
arithmetic on those lists, so it can be checked on events written by hand.

* Device events: every ``/device:TPU:<i>`` plane's ``XLA Ops`` line (one
  event per operation run) and ``XLA Modules`` line (one per program run).
  An operation is named by its HLO name without the numeric suffix
  (``%select_topk.7 = ...`` -> ``select_topk``); a program by its name
  without the fingerprint (``jit_run(4796...)`` -> ``jit_run``).
* Host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` events,
  whose names start with ``bench.``.  ``bench.window`` bounds the window.
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; the idle share is 1 - busy / window.
* Each idle interval of a device inside the window is attributed to the
  innermost ``bench.`` span that contains its midpoint.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]        # (name, start_ns, duration_ns)

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo: str) -> str:
    """``%select_topk.7 = (...) custom-call(...)`` -> ``select_topk``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(name: str) -> str:
    """``jit_run(4796221817080798425)`` -> ``jit_run``."""
    return name.split("(", 1)[0]


def load(path: str) -> Dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}`` with every event as ``(name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
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
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith("bench.")]
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

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, op: str, module: str = None) -> float:
        if module is None:
            return self.op_s.get(op, 0.0)
        return self.module_op_s.get((module, op), 0.0)

    def breakdown(self) -> Dict[str, List]:
        ops = collections.Counter({f"{m}:{o}": s for (m, o), s
                                   in self.module_op_s.items()})
        gaps = collections.Counter(self.idle_by_span)
        return {"device_ops": [[k, v] for k, v in ops.most_common(10)],
                "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def reduce(trace: Dict) -> Reduced:
    host = trace["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = wins[0]
    window_ns = hi - lo
    spans = [(n, s, s + d) for n, s, d in host if n != WINDOW]
    span_s = collections.Counter()
    for n, a, b in spans:
        a, b = clip(a, b, lo, hi)
        if b > a:
            span_s[n] += (b - a) / 1e9

    op_s, op_calls = collections.Counter(), collections.Counter()
    module_s, module_calls = collections.Counter(), collections.Counter()
    module_op_s = collections.Counter()
    idle = collections.Counter()
    busy_total = 0.0
    devices = trace["devices"]
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
        for (a, b), name in zip(gaps, spans_at([(a + b) / 2
                                                for a, b in gaps], spans)):
            idle[name] += (b - a) / 1e9
    n_dev = max(len(devices), 1)
    return Reduced(window_s=window_ns / 1e9,
                   busy_s=busy_total / n_dev / 1e9,
                   op_s=dict(op_s), op_calls=dict(op_calls),
                   module_s=dict(module_s), module_calls=dict(module_calls),
                   module_op_s=dict(module_op_s),
                   idle_by_span=dict(idle), span_s=dict(span_s))


OUTSIDE = "outside bench spans"


def spans_at(times: List[float], spans) -> List[str]:
    """The innermost host span open at each of the ascending ``times``.
    The spans come from nested ``with`` blocks of one thread, so a stack of
    the open ones is enough."""
    order = sorted(spans, key=lambda x: (x[1], -x[2]))
    stack, i, out = [], 0, []
    for t in times:
        while i < len(order) and order[i][1] <= t:
            while stack and stack[-1][2] < order[i][1]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else OUTSIDE)
    return out
