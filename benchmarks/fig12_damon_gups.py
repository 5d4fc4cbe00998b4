"""Fig. 12 — DAMON region-monitoring heatmaps for GUPS.

Paper claims: irrespective of monitoring parameters, DAMON cannot separate
GUPS's hot pages from cold ones, because the hot set is scattered uniformly
across the address space while DAMON assumes per-region homogeneity.

We quantify this as the correlation between DAMON's per-page hotness estimate
(region access rate) and the true page heat, under default and aggressive
scanning configs — and contrast it against HeMem's PEBS-style estimate, which
separates the sets easily.
"""

from __future__ import annotations

import numpy as np

from repro.core.engine import HMSDKEngine, HeMemEngine
from repro.core.knobs import HEMEM_SPACE, HMSDK_SPACE
from repro.core.pages import TierState
from repro.core.simulator import scale_config
from repro.core.workloads import make_workload

from .common import claim, print_claims, save


def _auc(score: np.ndarray, truth: np.ndarray) -> float:
    """Probability a random hot page outscores a random cold page."""
    hot, cold = score[truth], score[~truth]
    if len(hot) == 0 or len(cold) == 0:
        return 0.5
    # rank-based AUC with tie correction (average ranks)
    allv = np.concatenate([hot, cold])
    order = np.argsort(allv, kind="stable")
    ranks = np.empty(len(order))
    ranks[order] = np.arange(1, len(order) + 1)
    sorted_v = allv[order]
    # average ranks over tie groups
    i = 0
    while i < len(sorted_v):
        j = i
        while j + 1 < len(sorted_v) and sorted_v[j + 1] == sorted_v[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = 0.5 * (i + 1 + j + 1)
        i = j + 1
    r_hot = ranks[:len(hot)].sum()
    return float((r_hot - len(hot) * (len(hot) + 1) / 2)
                 / (len(hot) * len(cold)))


def _run_monitor(engine_cls, space, cfg, wl, epochs=30):
    tier = TierState(wl.n_pages, wl.n_pages)  # capacity irrelevant here
    eng = engine_cls(scale_config(
        "hmsdk" if engine_cls is HMSDKEngine else "hemem", cfg, wl.scale),
        tier, seed=0)
    for e in range(epochs):
        reads, writes = wl.epoch_access(e)
        tier.allocate_first_touch((reads + writes) > 0)
        eng.observe(reads, writes, wl.epoch_ms)
    if engine_cls is HMSDKEngine:
        return eng.nr_accesses[eng.region_of_page]
    return eng.read_counts + eng.write_counts


def run(quick: bool = False) -> dict:
    wl = make_workload("gups", "8GiB-hot", threads=12, scale=0.25, seed=0)
    reads0, writes0 = wl.epoch_access(0)
    truth = (reads0 + writes0) > np.median(reads0 + writes0) * 3

    damon_cfgs = {
        "default": HMSDK_SPACE.default_config(),
        "high-freq": HMSDK_SPACE.validate(
            dict(sample_us=100, aggr_us=10000, nr_regions=1000)),
    }
    out = {"auc": {}}
    for name, cfg in damon_cfgs.items():
        score = _run_monitor(HMSDKEngine, HMSDK_SPACE, cfg, wl)
        out["auc"][f"damon/{name}"] = _auc(score, truth)
    hemem_score = _run_monitor(HeMemEngine, HEMEM_SPACE,
                               HEMEM_SPACE.default_config(), wl)
    out["auc"]["hemem/default"] = _auc(hemem_score, truth)

    for k, v in out["auc"].items():
        print(f"  {k:18s} hot/cold separation AUC = {v:.3f}", flush=True)

    claims = [
        claim("fig12: DAMON cannot separate GUPS hot pages (any config)",
              all(v < 0.75 for k, v in out["auc"].items()
                  if k.startswith("damon/")),
              f"{ {k: round(v, 3) for k, v in out['auc'].items()} }"),
        claim("fig12: PEBS-style monitoring separates them easily",
              out["auc"]["hemem/default"] > 0.9,
              f"hemem AUC={out['auc']['hemem/default']:.3f}"),
    ]
    out["claims"] = claims
    print_claims(claims)
    save("fig12_damon_gups", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
