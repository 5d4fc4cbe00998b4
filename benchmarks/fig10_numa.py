"""Fig. 10 — tuning on the NUMA (CXL-emulation) machine + cross-machine
config transfer.

Paper claims: gains are mostly modest on NUMA (tiers are close in
latency/bandwidth, migrations nearly free) and pmem-large best configs
mostly perform well when transferred to NUMA.

Ported to the typed Study API (completing the PR 2 migration): one
``ExperimentSpec`` per (workload, machine), tuned with batched SMAC rounds
(``batch_size=4``, process-pool sharded) instead of the deprecated
``Scenario``/``tune_scenario`` shims; the transfer evaluation reuses the
NUMA study's cached workload trace.  Result payloads embed the replayable
spec.
"""

from __future__ import annotations

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec

from .common import SUITE, budget, claim, print_claims, save

BATCH_SIZE = 4


def _study(wname: str, inp: str, machine: str) -> Study:
    return Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec(wname, inp), machine=machine,
        options=SimOptions(sampler="sparse", workers="auto")))


def run(quick: bool = False) -> dict:
    b = budget(quick)
    out = {"workloads": {}}
    claims = []
    numa_imps, transfer_ok = {}, []
    suite = SUITE if not quick else [("silo", "ycsb-c"), ("xsbench", ""),
                                     ("gups", "8GiB-hot")]
    for wname, inp in suite:
        study_numa = _study(wname, inp, "numa")
        res_numa = study_numa.tune(budget=b, batch_size=BATCH_SIZE, seed=19)
        numa_imps[study_numa.key] = res_numa.improvement

        # transfer the pmem-large best config onto the NUMA machine
        res_pmem = _study(wname, inp, "pmem-large").tune(
            budget=b, batch_size=BATCH_SIZE, seed=19)
        transfer_s = study_numa.run(
            configs=[res_pmem.best.config])[0].total_s
        rel = transfer_s / res_numa.best_value
        transfer_ok.append(rel <= 1.15)
        out["workloads"][study_numa.key] = {
            "spec": study_numa.spec.to_dict(),
            "numa_improvement": res_numa.improvement,
            "pmem_config_on_numa_vs_numa_best": rel,
        }
        print(f"  {wname:12s} numa-gain={res_numa.improvement:.2f}x "
              f"pmem-cfg-transfer={rel:.2f}x of numa best", flush=True)

    claims.append(claim(
        "fig10: NUMA gains are mostly modest (smaller than pmem)",
        sorted(numa_imps.values())[len(numa_imps) // 2] <= 1.35,
        ", ".join(f"{k.split(':')[0]}={v:.2f}x" for k, v in numa_imps.items())))
    claims.append(claim(
        "fig10: pmem-large best configs mostly transfer to NUMA",
        sum(transfer_ok) >= max(1, int(0.6 * len(transfer_ok))),
        f"{sum(transfer_ok)}/{len(transfer_ok)} within 15% of NUMA-native best"))
    out["claims"] = claims
    print_claims(claims)
    save("fig10_numa", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
