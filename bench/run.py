"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in BENCHMARK.json,
sets up (weights or traces made from the seed, every shape warmed), measures
for ``--seconds`` and checks what the timed path produced against the plain
reference under ``bench/reference``.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from the profiler's trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.

The run refuses, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache is kept in
``<checkout>/.jax_cache``.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=int, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness as H
    bench = H.load_json(ROOT, "BENCHMARK.json")
    cell = H.Cell(bench, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2

    os.environ["JAX_COMPILATION_CACHE_DIR"] = H.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", H.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    chips = cell.entry["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    print(f"bench: device {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}", flush=True)

    h = H.Harness(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    driver = H.load_module(cell.driver_path, "bench_driver_" + cell.kind)
    out = driver.run(h)
    print(f"bench: setup_s {h.setup_s:.3f}; compiles inside the window: "
          f"{h.window_compiles[0]} requests, {h.window_compiles[1]} served "
          f"by the persistent cache", flush=True)

    checks = out["checks"]
    correct = all(c["ok"] for c in checks) and out["failed"] == 0
    device = h.device()
    breakdown = None
    if args.trace:
        from bench import trace_reduce
        red = trace_reduce.reduce(trace_reduce.load(h.trace_path))
        shutil.rmtree(H.TRACE_DIR, ignore_errors=True)
        ctx = {"peaks": H.peaks_for(device["kind"]), "config": cell.config,
               "traffic": cell.traffic, "work": H.work,
               "roofline_share": H.roofline_share}
        metrics = H.read_per_layer(cell, red, out["record"], ctx)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
    else:
        metrics = {m["name"]: {"value": float(out["metrics"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": float(h.setup_s), "unit": "s"}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(H.result_line(correct, out["attempted"], out["failed"], metrics,
                        device, checks, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
