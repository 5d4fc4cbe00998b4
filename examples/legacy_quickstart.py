"""The pre-PR-2 call pattern, kept as the deprecation-shim demonstration.

    PYTHONPATH=src python examples/legacy_quickstart.py [--budget 8]

Runs the historical ``Scenario`` + ``tune_scenario`` path, asserts that the
shims emit ``DeprecationWarning`` pointing at the Study replacement, and
asserts the numbers match the typed API exactly.
"""
import argparse
import sys, os
import warnings
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gups")
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--scale", type=float, default=0.1)
    args = ap.parse_args()

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        from repro.core.simulator import Scenario
        from repro.core.bo.tuner import tune_scenario
        sc = Scenario(args.workload, scale=args.scale)
        legacy = tune_scenario("hemem", sc, budget=args.budget, seed=0)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)
           and str(w.message).startswith("repro.")]
    assert dep, "legacy path must emit DeprecationWarning"
    print("deprecation warnings emitted by the legacy path:")
    for w in {str(d.message).split(" is deprecated")[0] for d in dep}:
        print(f"  {w}")

    res = Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec(args.workload, scale=args.scale),
        options=SimOptions(sampler="elementwise"))).tune(
            budget=args.budget, seed=0)
    assert [o.value for o in res.history] == \
        [o.value for o in legacy.history], "shim numerics must match"
    print(f"\nlegacy best {legacy.best_value:.1f}s == Study best "
          f"{res.best_value:.1f}s (identical numerics, budget "
          f"{args.budget})")
    print("migrate: Scenario+tune_scenario -> "
          "Study(ExperimentSpec(...)).tune(...)")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
