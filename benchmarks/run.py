"""Run every paper-figure benchmark.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,fig2,...]

Prints a summary line per benchmark plus PASS/FAIL per paper claim, and
exits non-zero if any claim fails.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time

MODULES = [
    "fig1_grid",
    "fig2_best_vs_default",
    "fig3_migrations_timeline",
    "smac_efficiency",
    "table5_analysis",
    "fig6_pmem_small",
    "fig7_input_transfer",
    "fig9_threads_ratios",
    "fig10_numa",
    "fig11_hmsdk",
    "fig12_damon_gups",
    "fig13_memtis",
    "bo_overhead",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced budgets (~4x faster)")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated benchmark names")
    args = ap.parse_args(argv)

    names = [m for m in MODULES
             if not args.only or any(o in m for o in args.only.split(","))]
    all_claims = []
    t_start = time.time()
    for name in names:
        print(f"\n=== benchmarks.{name} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            payload = mod.run(quick=args.quick)
            claims = payload.get("claims", [])
        except Exception as e:  # keep the harness running
            import traceback
            traceback.print_exc()
            claims = [(f"{name}: completed without error", False, repr(e))]
        all_claims.extend(claims)
        print(f"--- {name}: {time.time() - t0:.1f}s", flush=True)

    n_pass = sum(ok for _, ok, _ in all_claims)
    print("\n================ SUMMARY ================")
    for cname, ok, detail in all_claims:
        print(f"[{'PASS' if ok else 'FAIL'}] {cname}")
    print(f"{n_pass}/{len(all_claims)} claims validated "
          f"in {time.time() - t_start:.0f}s")
    return 0 if n_pass == len(all_claims) else 1


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
