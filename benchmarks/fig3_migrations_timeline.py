"""Figs. 3/4/5/8 — the time-series evidence behind Table 5.

* Fig 3 (BC): with the best config, migrations arrive in bursts at iteration
  boundaries (the frontier is promoted quickly), while the default migrates
  continuously and ends up doing more total work.
* Fig 4 (PR): streaming pattern — the default keeps migrating pages with no
  reuse; the best config's migration count flatlines.
* Fig 5 (XSBench): hot set stays fast-tier resident under the best config
  (placement stability), bulk churn eliminated.
* Fig 8 (BC kron vs twitter): twitter's popular-node pages concentrate
  traffic; the per-input heatmaps differ, which is why configs don't
  transfer (fig7).

Runs through the typed :class:`~repro.core.study.Study` API (tuning via
``Study.tune``, heatmap series via a ``SimOptions(record_heatmap=True)``
study).  Saves the raw time series + access heatmaps to
results/fig3_timelines.json.
"""

from __future__ import annotations

import numpy as np

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.workloads import make_workload

from .common import budget, claim, print_claims, save


def _tune(wname, inp, b):
    study = Study(ExperimentSpec(engine="hemem",
                                 workload=WorkloadSpec(wname, inp)))
    return study.tune(budget=b, seed=31)


def _series(wname, inp, cfg):
    spec = ExperimentSpec(
        engine="hemem" if cfg is None else {"name": "hemem", "config": cfg},
        workload=WorkloadSpec(wname, inp, threads=12, scale=0.25),
        machine="pmem-large",
        options=SimOptions(record_heatmap=True, heat_bins=64))
    return Study(spec).run()


def run(quick: bool = False) -> dict:
    b = budget(quick)
    out = {}
    claims = []

    # BC: default-vs-best migration timelines
    res = _tune("gapbs-bc", "kron", b)
    r_def = _series("gapbs-bc", "kron", None)
    r_best = _series("gapbs-bc", "kron", res.best.config)
    out["bc"] = {
        "cum_migrations_default": r_def.cum_migrations,
        "cum_migrations_best": r_best.cum_migrations,
        "wall_default_s": r_def.total_s, "wall_best_s": r_best.total_s,
    }
    # burstiness: fraction of best-config migrations inside iteration-start
    # windows (iterations are 15 epochs; window = first 5)
    mig_best = np.diff(r_best.cum_migrations, prepend=0)
    epochs = np.arange(len(mig_best))
    in_window = (epochs % 15) < 5
    burst_frac = float(mig_best[in_window].sum() /
                       max(mig_best.sum(), 1))
    out["bc"]["burst_frac_best"] = burst_frac
    claims.append(claim(
        "fig3/bc: best-config migrations concentrate at iteration starts",
        burst_frac > 0.5,
        f"{burst_frac:.0%} of migrations in the first third of iterations"))

    # PR: default churns, best flatlines
    res_pr = _tune("gapbs-pr", "kron", b)
    r_def = _series("gapbs-pr", "kron", None)
    r_best = _series("gapbs-pr", "kron", res_pr.best.config)
    out["pr"] = {
        "total_migrations_default": r_def.total_migrations,
        "total_migrations_best": r_best.total_migrations,
    }
    claims.append(claim(
        "fig4/pr: streaming pages keep default migrating; best flatlines",
        r_best.total_migrations < 0.2 * max(r_def.total_migrations, 1),
        f"{r_def.total_migrations} -> {r_best.total_migrations}"))

    # XSBench: hot rows of the heatmap stay fast-resident under best
    res_xs = _tune("xsbench", "", b)
    r_best = _series("xsbench", "", res_xs.best.config)
    hot_bins = 1   # first bin is entirely hot-set pages (first-touch layout)
    hot_resid = float(r_best.placement[10:, :hot_bins].mean())
    out["xsbench"] = {"hot_bin_residency_best": hot_resid}
    claims.append(claim(
        "fig5/xsbench: hot set stays fast-tier resident under best config",
        hot_resid > 0.9, f"hot-bin residency {hot_resid:.2f}"))

    # Fig 8: kron vs twitter page-level skew differs (popular-node pages)
    def top_page_share(inp, frac=0.005):
        wl = make_workload("gapbs-bc", inp, threads=12, scale=0.25, seed=0)
        reads, writes = wl.epoch_access(5)
        acc = np.sort(reads + writes)[::-1]
        k = max(1, int(len(acc) * frac))
        return float(acc[:k].sum() / max(acc.sum(), 1e-9))
    skew_kron = top_page_share("kron")
    skew_tw = top_page_share("twitter")
    out["fig8"] = {"top_half_pct_share_kron": skew_kron,
                   "top_half_pct_share_twitter": skew_tw}
    claims.append(claim(
        "fig8: twitter concentrates traffic on popular-node pages far more "
        "than kron",
        skew_tw > skew_kron * 1.3,
        f"top-0.5%-page share: twitter {skew_tw:.2f} vs kron {skew_kron:.2f}"))

    out["claims"] = claims
    print_claims(claims)
    save("fig3_timelines", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
