"""Fig. 1 — grid search over (read_hot_threshold x cooling_threshold) for
GUPS and Silo, all other knobs at default.

Paper claims: large performance variation across cells; best cell beats the
default by >= 29 % (GUPS) and >= 36 % (Silo).

Runs through the typed :class:`~repro.core.study.Study` API: every grid
cell is a validated config and the whole grid evaluates as ONE batched
``Study.run(configs=...)`` pass over a shared workload trace (numerically
identical to the historical sequential grid loop with matched seeds).
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.core import ExperimentSpec, Study, WorkloadSpec
from repro.core.knobs import HEMEM_SPACE

from .common import claim, print_claims, save

RH_GRID = [1, 2, 4, 6, 8, 12, 16, 20, 26, 30]
CT_GRID = [4, 8, 12, 18, 24, 32, 40]


def run(quick: bool = False) -> dict:
    rh = RH_GRID[::2] if quick else RH_GRID
    ct = CT_GRID[::2] if quick else CT_GRID
    out = {"rh_grid": rh, "ct_grid": ct, "workloads": {}}
    claims = []
    base = HEMEM_SPACE.default_config()
    combos = list(itertools.product(rh, ct))
    grid_cfgs = [HEMEM_SPACE.validate(dict(base, read_hot_threshold=r,
                                           cooling_threshold=c))
                 for r, c in combos]
    for wname, inp, floor in [("gups", "8GiB-hot", 1.29),
                              ("silo", "ycsb-c", 1.36)]:
        study = Study(ExperimentSpec(engine="hemem",
                                     workload=WorkloadSpec(wname, inp)))
        # one batched pass evaluates every grid cell plus the default
        results = study.run(configs=grid_cfgs + [base])
        vals = [r.total_s for r in results]
        cells = dict(zip(combos, vals[:-1]))
        default_val = vals[-1]
        best_idx = int(np.argmin(vals[:-1]))
        best_cfg, best_val = grid_cfgs[best_idx], vals[best_idx]
        grid = np.array([[cells[(r, c)] for c in ct] for r in rh])
        imp = default_val / best_val
        out["workloads"][study.workload().key] = {
            "default_s": default_val, "best_s": best_val,
            "improvement": imp,
            "best_rh": best_cfg["read_hot_threshold"],
            "best_ct": best_cfg["cooling_threshold"],
            "grid_s": grid,
        }
        claims.append(claim(
            f"fig1/{wname}: grid headroom >= {floor}x",
            imp >= floor * 0.93,   # reproduction tolerance
            f"default={default_val:.1f}s best={best_val:.1f}s "
            f"({imp:.2f}x vs paper {floor}x)"))
        claims.append(claim(
            f"fig1/{wname}: large variation across cells",
            grid.max() / grid.min() >= 1.25,
            f"max/min cell = {grid.max() / grid.min():.2f}x"))
    out["claims"] = claims
    print_claims(claims)
    save("fig1_grid", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
