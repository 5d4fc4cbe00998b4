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


def with_held_out() -> dict:
    """BENCHMARK.json with the cells of ``bench/held_out.json`` added: their
    configurations, and each metric's ``workloads`` widened to them."""
    bench = H.load_json(ROOT, "BENCHMARK.json")
    held = H.load_json(H.BENCH, "held_out.json")
    out = dict(bench, configs=bench["configs"] + held["configs"],
               workloads=bench["workloads"] + held["workloads"])
    for key in ("end_to_end", "per_layer"):
        metrics = {m["name"]: dict(m) for m in bench[key]}
        for m in held[key]:
            if m["name"] in metrics:
                metrics[m["name"]]["workloads"] = \
                    metrics[m["name"]]["workloads"] + m["workloads"]
            else:
                metrics[m["name"]] = dict(m)
        out[key] = list(metrics.values())
    return out


def tiny_cell(name: str) -> H.Cell:
    """The cell ``name`` of BENCHMARK.json or of the held-out cells, cut to
    a test's size."""
    cell = H.Cell(with_held_out(), name)
    if cell.kind == "tune":
        # scale 0.02 of the GUPS trace: 655 pages, every rate preserved
        cell.config = dict(cell.config, scale=0.02, n_pages=655)
        cell.traffic = dict(cell.traffic, budget=8,
                            batch_size=min(4, cell.traffic["batch_size"]))
    else:
        cell.config = dict(
            cell.config, num_hidden_layers=2, num_key_value_heads=2,
            head_dim=16, num_attention_heads=8, max_position_embeddings=64,
            page_tokens=4, batch=4, engine_every=4)
        roomy = cell.traffic["hbm_pages"] > 128
        cell.traffic = dict(
            cell.traffic, hbm_pages=60 if roomy else 12, inputs=7,
            target_len=dict(median=24, sigma=0.5, lo=8, hi=64, n=16),
            check_share=0.3, check_pages=8)
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
