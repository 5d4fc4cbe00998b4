"""Fig. 7 — tuning with different application inputs + cross-input transfer.

Paper claims: the best configuration for one input usually does NOT perform
well on the other input (often worse than default).

Ported to the typed Study API (continuing the PR 3 migration): one Study
per (workload, input), tuned with batched SMAC rounds (``batch_size=4``,
process-pool sharded); the transfer evaluations reuse the destination
input's Study so its cached workload trace serves both directions.
"""

from __future__ import annotations

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec

from .common import budget, claim, print_claims, save

PAIRS = [
    ("gapbs-bc", "kron", "twitter"),
    ("gapbs-pr", "kron", "twitter"),
    ("silo", "ycsb-c", "tpc-c"),
]

BATCH_SIZE = 4


def _study(wname: str, inp: str) -> Study:
    return Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec(wname, inp),
        options=SimOptions(sampler="sparse", workers="auto")))


def run(quick: bool = False) -> dict:
    out = {"pairs": {}}
    claims = []
    bad_transfers = 0
    total_transfers = 0
    for wname, in_a, in_b in PAIRS:
        entry = {}
        results = {}
        studies = {}
        for inp in (in_a, in_b):
            studies[inp] = _study(wname, inp)
            res = studies[inp].tune(budget=budget(quick),
                                    batch_size=BATCH_SIZE, seed=11)
            results[inp] = res
            entry[inp] = {"spec": studies[inp].spec.to_dict(),
                          "default_s": res.default_value,
                          "best_s": res.best_value,
                          "improvement": res.improvement}
        # transfer: run each best config on the OTHER input
        for src, dst in ((in_a, in_b), (in_b, in_a)):
            transfer_s = studies[dst].run(
                configs=[results[src].best.config])[0].total_s
            rel_to_best = transfer_s / results[dst].best_value
            rel_to_default = transfer_s / results[dst].default_value
            entry[f"{src}->{dst}"] = {
                "transfer_s": transfer_s,
                "vs_native_best": rel_to_best,
                "vs_default": rel_to_default,
            }
            total_transfers += 1
            if rel_to_best > 1.05:   # clearly worse than native tuning
                bad_transfers += 1
            print(f"  {wname}: {src}->{dst}  {rel_to_best:.2f}x of native best, "
                  f"{rel_to_default:.2f}x of default", flush=True)
        out["pairs"][wname] = entry

    claims.append(claim(
        "fig7: best configs usually do not transfer across inputs",
        bad_transfers * 2 >= total_transfers,   # "in most cases" (paper §4.3)
        f"{bad_transfers}/{total_transfers} transfers worse than native tuning"))
    out["claims"] = claims
    print_claims(claims)
    save("fig7_input_transfer", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
