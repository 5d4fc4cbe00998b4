"""Tune the TieredKVCache knobs against the REAL serving path through the
typed Study API: ``Study.tune(objective=...)`` drives the Table-2 HeMem
knob space while the objective replays an embedded, JSON-round-trippable
:class:`~repro.core.traffic.TrafficSpec` through the compiled decode loop
(fused append + paged-attention + read-recording jit) and scores
p99 latency / recall.

    PYTHONPATH=src python examples/tune_serving.py [--budget 20]
"""
import argparse
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import ExperimentSpec, Study
from repro.core.knobs import HEMEM_SPACE
from repro.core.traffic import TrafficSpec

from benchmarks.serving_tiered_kv import replay, serving_objective


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=20)
    ap.add_argument("--pattern", choices=("poisson", "bursty-diurnal"),
                    default="bursty-diurnal")
    args = ap.parse_args()

    traffic = TrafficSpec(pattern=args.pattern, arrival_rate=32 / 24,
                          steps=96)
    print(f"traffic: {traffic.to_json()}\n")

    def objective(config) -> float:
        stats = replay(config, traffic, batch=32, max_pages=8, seed=5)
        return serving_objective(stats)

    # the spec names the engine whose knob space is tuned; the serving
    # replay above replaces the simulator objective
    study = Study(ExperimentSpec(engine="kv-hemem", workload="kv-poisson"))
    res = study.tune(budget=args.budget, seed=0, n_init=8,
                     objective=objective, verbose=True)
    print(f"\ndefault objective: {res.default_value:.2f}")
    print(f"tuned   objective: {res.best_value:.2f} "
          f"({res.improvement:.2f}x better)")
    dflt = HEMEM_SPACE.default_config()
    for k, v in res.best.config.items():
        if v != dflt[k]:
            print(f"  {k:28s} {dflt[k]:>8} -> {v}")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
