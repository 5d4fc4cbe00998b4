"""Tiny versions of the benchmark's cells, for tests on the CPU: the same
drivers, harness and references at sizes a test run holds."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness as H  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def tiny_cell(name: str) -> H.Cell:
    """The cell ``name`` of BENCHMARK.json, cut to a test's size by
    ``bench/tests/tiny/<name>.json``: keys that replace those of the
    configuration (``config``) and of the traffic mix (``traffic``)."""
    cell = H.Cell(H.load_json(ROOT, "BENCHMARK.json"), name)
    cut = H.load_json(TINY, name + ".json")
    cell.config = dict(cell.config, **cut["config"])
    cell.traffic = dict(cell.traffic, **cut["traffic"])
    return cell


def run_tiny(name: str, seed: int = 12345, seconds: int = 1,
             control=None, trace: bool = False):
    """One run of the tiny cell through its driver on this host's JAX;
    returns the driver's output and the harness."""
    cell = tiny_cell(name)
    h = H.Harness(cell, seed, seconds, trace, time.time())
    driver = H.load_module(cell.driver_path, "bench_driver_" + cell.kind)
    out = driver.run(h) if control is None else driver.run(
        h, control=control)
    return out, h, driver


def correct(out) -> bool:
    return all(c["ok"] for c in out["checks"]) and out["failed"] == 0
