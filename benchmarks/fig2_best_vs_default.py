"""Fig. 2 — BO-tuned best HeMem configuration vs default, all 8 workloads
on pmem-large.

Paper claims: improvements of 1.07-2.09x for all workloads barring Graph500
(which shows ~no gain).

Ported to the typed Study API (PR 2): each tuning session evaluates whole
candidate batches per SMAC round (``batch_size=4``, process-pool sharded),
and the final default-vs-best bars come from one ``Study.sweep`` batched
pass per workload instead of sequential re-evaluations.  Result payloads
embed the replayable ``ExperimentSpec``.
"""

from __future__ import annotations

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.knobs import HEMEM_SPACE

from .common import SUITE, budget, claim, print_claims, save

# q=4 keeps enough adaptive SMAC rounds at quick budgets while still
# cutting wall-clock ~2-3x on this box (see fig9 note)
BATCH_SIZE = 4


def run(quick: bool = False) -> dict:
    out = {"workloads": {}}
    claims = []
    imps = {}
    for wname, inp in SUITE:
        study = Study(ExperimentSpec(
            engine="hemem", workload=WorkloadSpec(wname, inp),
            options=SimOptions(sampler="sparse", workers="auto")))
        res = study.tune(budget=budget(quick), batch_size=BATCH_SIZE, seed=3)
        # one batched pass re-scores {default, best} through a shared trace
        sweep = study.sweep(configs=[HEMEM_SPACE.default_config(),
                                     res.best.config])
        default_s, best_s = sweep.total_s()[("hemem", study.spec.workload.key)]
        imp = default_s / best_s
        imps[study.key] = imp
        out["workloads"][study.key] = {
            "spec": study.spec.to_dict(),
            "default_s": default_s,
            "best_s": best_s,
            "improvement": imp,
            "best_config": res.best.config,
            "incumbent": res.incumbent_trajectory(),
        }
        print(f"  {study.key:34s} default={default_s:8.1f}s "
              f"best={best_s:8.1f}s  {imp:.2f}x", flush=True)

    non_g500 = {k: v for k, v in imps.items() if "graph500" not in k}
    claims.append(claim(
        "fig2: non-graph500 improvements within ~[1.07, 2.09]x band",
        all(1.02 <= v <= 2.30 for v in non_g500.values()),
        ", ".join(f"{k}={v:.2f}x" for k, v in non_g500.items())))
    claims.append(claim(
        "fig2: most workloads show >= 1.07x gains",
        sum(v >= 1.07 for v in non_g500.values()) >= len(non_g500) - 1,
        f"{sum(v >= 1.07 for v in non_g500.values())}/{len(non_g500)}"))
    g500 = [v for k, v in imps.items() if "graph500" in k][0]
    claims.append(claim(
        "fig2: graph500 shows the least gain (~none)",
        g500 <= 1.10 and g500 <= min(non_g500.values()) + 0.05,
        f"graph500={g500:.2f}x vs min(others)={min(non_g500.values()):.2f}x"))
    out["claims"] = claims
    print_claims(claims)
    save("fig2_best_vs_default", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
