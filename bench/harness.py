"""The benchmark's harness: finds a cell's files by name, times set-up and
the window, counts compiles, traces, and assembles the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``  the configuration (its ``kind`` names
  the driver ``bench/drivers/<kind>.py``);
* ``bench/traffic/<traffic>.json`` the traffic mix's parameters;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric, with
  ``read(red, rec, ctx) -> float | None``;
* ``bench/work/<kernel>.py``       the least work of one kernel's calls.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run writes its profile; removed once it is reduced
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    def __init__(self, bench: Dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.traffic = load_json(BENCH, "traffic",
                                 self.entry["traffic"] + ".json")
        self.kind = self.config["kind"]
        self.driver_path = os.path.join(BENCH, "drivers",
                                        self.kind + ".py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


class CompileCounter:
    """Counts compile requests that missed the in-memory caches, and those
    that the persistent cache served."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


class Harness:
    """Set-up and window clocks, compile counts, spans and the trace of one
    run.  ``t_process`` is the process's start on the host clock."""

    def __init__(self, cell: Cell, seed: int, seconds: int, trace: bool,
                 t_process: float):
        import jax
        self.jax = jax
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace = trace
        self.t_process = t_process
        self.compiles = CompileCounter()
        jax.monitoring.register_event_listener(self.compiles)
        self.setup_s: Optional[float] = None
        self.window_compiles = (0, 0)
        self._c0 = (0, 0)
        self.trace_path: Optional[str] = None
        self.peak_bytes: Optional[int] = None

    def annotate(self, name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        return self.jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts.  A traced run
        records the profiler's trace over exactly this window."""
        self.setup_s = time.time() - self.t_process
        self._c0 = self.compiles.snapshot()
        print("bench: the window opens", file=sys.stderr, flush=True)
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with self.annotate("bench.window"):
                yield
        finally:
            if self.trace:
                self.jax.profiler.stop_trace()
                found = []
                for d, _, files in os.walk(TRACE_DIR):
                    found += [os.path.join(d, f) for f in files
                              if f.endswith(".xplane.pb")]
                self.trace_path = found[0] if found else None
            c1 = self.compiles.snapshot()
            self.window_compiles = (c1[0] - self._c0[0], c1[1] - self._c0[1])

    def read_peak(self) -> int:
        """Peak device memory of the fullest chip, read once the window has
        closed and before any reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.local_devices()]
        self.peak_bytes = int(max(peaks))
        return self.peak_bytes

    def device(self) -> Dict[str, Any]:
        devs = self.jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": self.peak_bytes}


def roofline_share(work: Dict[str, float], seconds: float,
                   peaks: Dict[str, float]) -> Optional[float]:
    """Share (%) of the roofline: the least time the chip could take for
    ``work`` (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s) over the measured ``seconds``.  None where nothing ran."""
    if seconds <= 0 or (work["flops"] <= 0 and work["bytes"] <= 0):
        return None
    least = max(work["flops"] / peaks["flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def work(kernel: str, **shape) -> Dict[str, float]:
    mod = load_module(os.path.join(BENCH, "work", kernel + ".py"),
                      "bench_work_" + kernel)
    return mod.work(**shape)


def read_per_layer(cell: Cell, red, rec: Dict, ctx: Dict) -> Dict[str, Dict]:
    """Each per-layer metric of the cell that its reader finds; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(red, rec, ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict], device: Dict,
                checks: List[Dict], breakdown: Optional[Dict] = None) -> str:
    """The run's last line.  The compared numbers come last, each with its
    limit."""
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return json.dumps(out)


def check(name: str, value: float, limit: float) -> Dict:
    """One compared number: it passes when it is at most its limit."""
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(value <= limit)}
