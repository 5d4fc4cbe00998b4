"""Fig. 6 — the same tuning experiments on pmem-small (fewer threads,
smaller DRAM bandwidth).

Paper claims: results are very similar to pmem-large — gains persist when
switching to different hardware.

Ported to the typed Study API (continuing the PR 3 migration): one
``ExperimentSpec`` per workload on the pmem-small machine profile, tuned
with batched SMAC rounds (``batch_size=4``, process-pool sharded) instead
of the deprecated ``Scenario``/``tune_scenario`` shims.  Result payloads
embed the replayable spec.
"""

from __future__ import annotations

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec

from .common import SUITE, budget, claim, print_claims, save

BATCH_SIZE = 4


def run(quick: bool = False) -> dict:
    out = {"workloads": {}}
    claims = []
    imps = {}
    suite = SUITE if not quick else SUITE[3:]
    for wname, inp in suite:
        study = Study(ExperimentSpec(
            engine="hemem",
            workload=WorkloadSpec(wname, inp, threads=4),
            machine="pmem-small",
            options=SimOptions(sampler="sparse", workers="auto")))
        res = study.tune(budget=budget(quick), batch_size=BATCH_SIZE, seed=7)
        imps[study.key] = res.improvement
        out["workloads"][study.key] = {
            "spec": study.spec.to_dict(),
            "default_s": res.default_value, "best_s": res.best_value,
            "improvement": res.improvement,
        }
        print(f"  {study.key:34s} {res.improvement:.2f}x", flush=True)
    non_g500 = {k: v for k, v in imps.items() if "graph500" not in k}
    claims.append(claim(
        "fig6: gains persist on pmem-small for most workloads",
        sum(v >= 1.05 for v in non_g500.values()) >= len(non_g500) - 1,
        ", ".join(f"{k.split('@')[0]}={v:.2f}x" for k, v in imps.items())))
    out["claims"] = claims
    print_claims(claims)
    save("fig6_pmem_small", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
