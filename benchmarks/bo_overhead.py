"""BO hot-path overhead: ask/tell wall clock vs evaluation wall clock.

PR 5 acceptance receipts: a budget-64, q=8 ``Study.tune`` run under the
pre-PR-5 optimizer (``surrogate="reference"`` recursive forest fit +
``acquisition="legacy"`` per-tree descent / ``np.vectorize``'d erf / dict
candidate pools) vs the compiled default (level-synchronous array-native
fit + fused jitted EI acquisition + encoded pools).  The per-round
fit / acquisition / evaluation breakdown and the >= 3x ask/tell reduction
are recorded in ``BENCH_bo.json`` (repo root and benchmarks/results/).

Both runs use the same seeds; histories differ between acquisition modes
(different candidate-pool RNG protocols — see repro.core.bo.smac), so the
comparison is about optimizer cost, with best-values reported for context.
"""

from __future__ import annotations

import json
import os

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec

from .common import claim, print_claims, save


def _tune(budget: int, q: int, **kwargs) -> dict:
    study = Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec("gups", "8GiB-hot"),
        options=SimOptions(sampler="sparse")))
    res = study.tune(budget=budget, batch_size=q, seed=11, **kwargs)
    return {
        "spec": study.spec.to_dict(),
        "best_s": res.best_value,
        "improvement": res.improvement,
        "wall_s": res.wall_s,
        "ask_tell_s": res.optimizer_overhead_s,
        "evaluation_s": res.evaluation_s,
        "overhead_fraction_of_eval": res.overhead_fraction,
        "fit_s": float(sum(r["fit_s"] for r in res.round_times)),
        "acquisition_s": float(sum(r["ask_s"] - r["fit_s"]
                                   for r in res.round_times)),
        "rounds": res.round_times,
    }


def run(quick: bool = False) -> dict:
    budget = 24 if quick else 64
    q = 4 if quick else 8
    repeats = 1 if quick else 2
    print(f"  budget={budget} q={q} (gups:8GiB-hot, hemem)", flush=True)
    out = {"budget": budget, "q": q, "repeats": repeats}
    arms = {"before": dict(surrogate="reference", acquisition="legacy"),
            "after": {}}
    # interleaved min-of-N (same methodology as BENCH_backend): this box is
    # 2-core and throttles, so each arm keeps its least-noisy run
    runs = {label: [] for label in arms}
    for _ in range(repeats):
        for label, kwargs in arms.items():
            runs[label].append(_tune(budget, q, **kwargs))
    for label in arms:
        out[label] = min(runs[label], key=lambda r: r["ask_tell_s"])
    speedup = out["before"]["ask_tell_s"] / max(out["after"]["ask_tell_s"],
                                                1e-12)
    out["ask_tell_speedup_x"] = speedup
    for label in ("before", "after"):
        r = out[label]
        print(f"  {label:6s} ask+tell={r['ask_tell_s']:7.3f}s "
              f"(fit {r['fit_s']:.3f}s, acq {r['acquisition_s']:.3f}s)  "
              f"eval={r['evaluation_s']:7.3f}s  "
              f"overhead={100 * r['overhead_fraction_of_eval']:.1f}% of eval",
              flush=True)

    claims = [
        claim("bo: ask/tell overhead reduced >= 3x vs pre-PR-5 optimizer",
              speedup >= 3.0, f"{speedup:.1f}x "
              f"({out['before']['ask_tell_s']:.3f}s -> "
              f"{out['after']['ask_tell_s']:.3f}s)"),
        claim("bo: ask/tell is a small fraction of evaluation wall clock",
              out["after"]["overhead_fraction_of_eval"] <= 0.25,
              f"{100 * out['after']['overhead_fraction_of_eval']:.1f}% "
              "of evaluation"),
    ]
    out["claims"] = claims
    print_claims(claims)
    save("BENCH_bo", out)
    root = os.path.join(os.path.dirname(__file__), "..", "BENCH_bo.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)
