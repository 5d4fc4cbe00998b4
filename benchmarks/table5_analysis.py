"""Table 5 — why the best configurations win: knob diffs, importance scores,
and the per-workload mechanism evidence (migration counts, hit rates).

Paper claims validated here:
  * PR/CC best configs eliminate (nearly all) migrations vs default.
  * XSBench best config eliminates warm/bulk-page migrations.
  * Btree best config reduces write-driven init-phase migrations.
  * Silo's important knobs include the *hidden* cooling_pages.
  * GUPS best config increases sampling accuracy (lower sampling_period)
    or otherwise stabilizes hot classification, reducing shuffling.

Ported to the typed Study API (completing the PR 2 migration): tuning runs
as batched SMAC rounds and the default-vs-best mechanism evidence comes
from ONE batched ``Study.run(configs=[default, best])`` pass over the
shared workload trace — no ``Scenario``/``tune_scenario``/
``run_simulation`` shims.  The knob-importance sweep rides the flat-forest
``predict_batch`` fast path (one descent over all knob sweeps).
"""

from __future__ import annotations

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.bo.importance import knob_importance
from repro.core.knobs import HEMEM_SPACE

from .common import budget, claim, print_claims, save

BATCH_SIZE = 4


def run(quick: bool = False) -> dict:
    out = {"workloads": {}}
    claims = []
    b = budget(quick)
    default_cfg = HEMEM_SPACE.default_config()

    for wname, inp in [("gapbs-pr", "kron"), ("xsbench", ""), ("btree", ""),
                       ("silo", "ycsb-c"), ("gups", "8GiB-hot")]:
        study = Study(ExperimentSpec(
            engine="hemem", workload=WorkloadSpec(wname, inp, threads=12),
            options=SimOptions(sampler="sparse", workers="auto")))
        res = study.tune(budget=b, batch_size=BATCH_SIZE, seed=5)
        best_cfg = res.best.config
        # default and best mechanisms from one shared-trace batched pass
        r_def, r_best = study.run(configs=[default_cfg, best_cfg])
        imp = knob_importance(HEMEM_SPACE, res.history)
        diff = {k: (default_cfg[k], best_cfg[k]) for k in best_cfg
                if best_cfg[k] != default_cfg[k]}
        out["workloads"][study.key] = {
            "spec": study.spec.to_dict(),
            "improvement": res.improvement,
            "migrations_default": r_def.total_migrations,
            "migrations_best": r_best.total_migrations,
            "hit_default": float(r_def.fast_hit_rate.mean()),
            "hit_best": float(r_best.fast_hit_rate.mean()),
            "knob_diff": diff,
            "importance": imp,
        }
        print(f"  {study.key:22s} {res.improvement:.2f}x  migs "
              f"{r_def.total_migrations} -> {r_best.total_migrations}  "
              f"top-knobs: {list(imp)[:3]}", flush=True)

        if wname in ("gapbs-pr", "xsbench"):
            claims.append(claim(
                f"table5/{wname}: best config eliminates unnecessary migrations",
                r_best.total_migrations <= max(0.25 * r_def.total_migrations, 50),
                f"{r_def.total_migrations} -> {r_best.total_migrations}"))
        if wname == "btree":
            claims.append(claim(
                "table5/btree: best config reduces init write migrations",
                r_best.total_migrations <= 0.7 * r_def.total_migrations,
                f"{r_def.total_migrations} -> {r_best.total_migrations}"))
        if wname == "silo":
            claims.append(claim(
                "table5/silo: hidden knob cooling_pages among important knobs",
                list(imp).index("cooling_pages") < 5
                if "cooling_pages" in imp else False,
                f"importance ranking: {list(imp)[:5]}"))
        if wname == "gups":
            claims.append(claim(
                "table5/gups: best config stabilizes hot classification "
                "(better hit rate, fewer wasteful migrations)",
                r_best.fast_hit_rate.mean() > r_def.fast_hit_rate.mean(),
                f"hit {r_def.fast_hit_rate.mean():.3f} -> "
                f"{r_best.fast_hit_rate.mean():.3f}"))

    out["claims"] = claims
    print_claims(claims)
    save("table5_analysis", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
