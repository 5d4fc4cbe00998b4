"""Run a cell's correctness comparison with its control in the program's
place, on several seeds in one process.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

The control is the plain reference computed in the precision below the one
the configuration states (bfloat16 for the simulator's float32, fp8 for the
KV cache's bfloat16).  Each seed runs the cell's set-up and a short window,
then compares the control against the reference as a run compares the
program; every compared number must come out above its limit.  The
benchmark's own runs do not run this.  Prints one JSON line per seed.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=int, default=3)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness as H
    cell = H.Cell(H.load_json(ROOT, "BENCHMARK.json"), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = H.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", H.CACHE_DIR)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    driver = H.load_module(cell.driver_path, "bench_driver_" + cell.kind)
    for seed in args.seeds:
        h = H.Harness(cell, seed, args.seconds, False, time.time())
        out = driver.run(h, control=driver.control_precision())
        print(json.dumps({"seed": seed, "control": True,
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
