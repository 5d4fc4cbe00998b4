"""§3.1 — SMAC sample efficiency.

Paper claims: SMAC finds the best-performing (Fig.-1-grid-level) GUPS
configuration within 10-16 iterations, making it 2.5-4x more sample-efficient
than the grid search.

Runs through the typed :class:`~repro.core.study.Study` API: the reference
grid evaluates as one batched ``Study.run(configs=...)`` pass and each SMAC
session is a ``Study.tune`` call.
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.core import ExperimentSpec, Study, WorkloadSpec
from repro.core.knobs import HEMEM_SPACE

from .common import claim, print_claims, save
from .fig1_grid import CT_GRID, RH_GRID


def run(quick: bool = False) -> dict:
    study = Study(ExperimentSpec(engine="hemem",
                                 workload=WorkloadSpec("gups", "8GiB-hot")))
    rh = RH_GRID[::2] if quick else RH_GRID
    ct = CT_GRID[::2] if quick else CT_GRID
    base = HEMEM_SPACE.default_config()
    grid_cfgs = [HEMEM_SPACE.validate(dict(base, read_hot_threshold=r,
                                           cooling_threshold=c))
                 for r, c in itertools.product(rh, ct)]
    grid_vals = [r.total_s for r in study.run(configs=grid_cfgs)]
    grid_best = float(min(grid_vals))
    grid_evals = len(grid_cfgs)

    iters_needed, improvements = [], []
    seeds = [1, 2] if quick else [1, 2, 3]
    for seed in seeds:
        res = study.tune(budget=40 if quick else 60, seed=seed, n_init=10)
        it = res.iterations_to(grid_best, rtol=0.02)
        iters_needed.append(it if it is not None else res.budget + 1)
        improvements.append(res.improvement)

    med = float(np.median(iters_needed))
    speedup = grid_evals / med
    out = {"grid_best_s": grid_best, "grid_evals": grid_evals,
           "iters_to_grid_optimum": iters_needed,
           "median_iters": med, "sample_efficiency_x": speedup,
           "improvements": improvements}
    claims = [
        claim("smac: reaches grid-level optimum within ~10-16 iterations",
              med <= 24,
              f"median {med:.0f} iterations (seeds: {iters_needed})"),
        claim("smac: >= 2.5x more sample-efficient than grid search",
              speedup >= (1.5 if quick else 2.5),
              f"{grid_evals} grid evals vs {med:.0f} SMAC iters "
              f"= {speedup:.1f}x" + (" [quick grid]" if quick else "")),
    ]
    out["claims"] = claims
    print_claims(claims)
    save("smac_efficiency", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
