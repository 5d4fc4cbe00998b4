"""Asynchronous tuning-service benchmark -> BENCH_study.json.

Measures the wall-clock win of ``Study.tune(executor="async")`` — the
slot-saturating trial executor with ASHA successive halving — against the
synchronous ``batch_size=q`` round-barrier path at equal suggestion budget,
and records the receipts the tune-service PR gates on:

* **wall-clock speedup** of async slots=8 + ASHA over synchronous q=8 at
  budget 512 (target > 2x, acceptance gate >= 1.5x);
* **slot utilization** of the async executor (busy slot-time over
  slots x makespan — the round barrier is what the async path removes);
* **ASHA savings**, reported separately: the fraction of full-budget epoch
  work the scheduler skipped, and the async-without-scheduler arm that
  isolates executor overhead from early stopping.

On a single-core host the evaluation slots cannot overlap, so the async
win comes from ASHA epoch savings plus ask-ahead chunking (``window``
amortizes surrogate fits exactly like the sync path's ``ask_batch``); on
multi-core hosts slot overlap compounds with both.  The jax backend is
used for every arm (the compiled epoch loop checkpoints mid-run, so
promoted trials resume from their rung boundary instead of re-simulating);
all compiles are warmed outside the timed regions, matching the repo's
other benchmarks.

Determinism receipts ride along: the async arm journals every decision and
the resulting journal must validate against ``tools/journal_schema.py``.

Usage::

    PYTHONPATH=src python -m benchmarks.study_async [--quick]
        [--budget N] [--slots N] [--window N] [--scale S] [--seed S]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _default_xla_flags():
    ncpu = os.cpu_count() or 1
    if "XLA_FLAGS" not in os.environ and ncpu > 1:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={min(ncpu, 8)}"


_default_xla_flags()  # before any (transitive) jax import

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec  # noqa: E402
from repro.core.knobs import get_space  # noqa: E402
from repro.core.simulator import run_simulation_segment  # noqa: E402
from repro.core.tune_service.asha import ASHAScheduler  # noqa: E402

from .common import claim, print_claims, save  # noqa: E402


def _study(scale: float, seed: int) -> Study:
    return Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec("gups", scale=scale),
        machine="pmem-large",
        options=SimOptions(seed=seed, sampler="sparse", backend="jax")))


def _warm_compiles(study: Study, batch_size: int) -> float:
    """Compile every epoch-loop shape the arms will hit (B=q full run for
    the sync arm; B=1 full run + each ASHA rung segment length for the
    async arms) outside the timed regions."""
    t0 = time.time()
    wl = study.workload()
    cfg = get_space("hemem").default_config()
    study.run(configs=[cfg] * batch_size)          # sync arm: B=q, E=full
    rungs = ASHAScheduler(wl.n_epochs).rung_epochs
    lengths = sorted({hi - lo for lo, hi in
                      zip((0,) + rungs[:-1], rungs)} | {wl.n_epochs})
    for n in lengths:                              # async arms: B=1 segments
        run_simulation_segment(wl, "hemem", [cfg], study.machine,
                               seeds=study.spec.options.seed,
                               sampler="sparse", backend="jax",
                               epoch_start=0, epoch_stop=n)
    return time.time() - t0


def run(quick: bool = False, budget: int = None, slots: int = 8,
        window: int = None, scale: float = None, seed: int = 0) -> dict:
    budget = budget if budget is not None else (64 if quick else 512)
    scale = scale if scale is not None else (0.04 if quick else 0.1)
    window = window if window is not None else 4 * slots
    n_init = min(20, max(4, budget // 8))
    results_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(results_dir, exist_ok=True)
    journal = os.path.join(results_dir, "study_async_journal.jsonl")
    if os.path.exists(journal):
        os.remove(journal)

    study = _study(scale, seed)
    wl = study.workload()
    print(f"GUPS@{scale}/hemem (E={wl.n_epochs}, n_pages={wl.n_pages}), "
          f"budget={budget}, sync q=8 vs async slots={slots} "
          f"window={window}", flush=True)
    t_compile = _warm_compiles(study, batch_size=8)
    print(f"  compile warm-up: {t_compile:.1f}s (excluded from timings)",
          flush=True)

    kw = dict(budget=budget, seed=seed, n_init=n_init)

    t0 = time.time()
    r_sync = _study(scale, seed).tune(batch_size=8, **kw)
    t_sync = time.time() - t0
    print(f"  sync q=8:          {t_sync:7.2f}s  "
          f"best={r_sync.best_value:8.3f}s", flush=True)

    t0 = time.time()
    r_plain = _study(scale, seed).tune(executor="async", slots=slots,
                                       window=window, **kw)
    t_plain = time.time() - t0
    print(f"  async slots={slots}:     {t_plain:7.2f}s  "
          f"best={r_plain.best_value:8.3f}s  "
          f"util={r_plain.utilization:.2f}", flush=True)

    t0 = time.time()
    r_asha = _study(scale, seed).tune(executor="async", slots=slots,
                                      window=window, scheduler="asha",
                                      journal=journal, **kw)
    t_asha = time.time() - t0
    print(f"  async+asha:        {t_asha:7.2f}s  "
          f"best={r_asha.best_value:8.3f}s  "
          f"util={r_asha.utilization:.2f}  "
          f"epochs saved={r_asha.asha_epochs_saved_frac * 100:.0f}%",
          flush=True)

    speedup = t_sync / t_asha
    speedup_plain = t_sync / t_plain
    quality = abs(r_asha.best_value - r_sync.best_value) / r_sync.best_value

    # determinism receipt: the journal the timed run wrote must validate
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import journal_schema
    journal_problems = journal_schema.validate_file(journal)

    def _arm(r, wall):
        return {
            "wall_s": float(wall), "best_value_s": float(r.best_value),
            "default_value_s": float(r.default_value),
            "improvement_x": float(r.improvement),
        }

    out = {
        "engine": "hemem", "workload": f"gups:8GiB-hot@{scale}",
        "n_epochs": wl.n_epochs, "n_pages": wl.n_pages,
        "budget": budget, "n_init": n_init, "seed": seed,
        "slots": slots, "window": window,
        "cpu_count": os.cpu_count(),
        "compile_warmup_s": float(t_compile),
        "arms": {
            "sync_q8": _arm(r_sync, t_sync),
            "async_slots": dict(_arm(r_plain, t_plain),
                                utilization=float(r_plain.utilization),
                                makespan_s=float(r_plain.makespan_s),
                                busy_s=float(r_plain.busy_s)),
            "async_slots_asha": dict(
                _arm(r_asha, t_asha),
                utilization=float(r_asha.utilization),
                makespan_s=float(r_asha.makespan_s),
                busy_s=float(r_asha.busy_s),
                epochs_committed=int(r_asha.epochs_committed),
                epochs_full_budget=int(budget * wl.n_epochs),
                asha_epochs_saved_frac=float(r_asha.asha_epochs_saved_frac),
                n_stopped_early=int(r_asha.n_stopped_early),
                n_failed=int(r_asha.n_failed)),
        },
        "speedup_async_asha_x": float(speedup),
        "speedup_async_plain_x": float(speedup_plain),
        "best_value_delta_pct": float(quality * 100),
        "journal": os.path.relpath(journal,
                                   os.path.join(os.path.dirname(__file__),
                                                os.pardir)),
        "journal_valid": not journal_problems,
    }
    gate = 1.5 if not quick else 1.0  # quick mode checks wiring, not perf
    out["claims"] = [
        claim("async slots + ASHA beats synchronous q=8 wall-clock "
              f"(gate >= {gate}x, target > 2x)", speedup >= gate,
              f"{speedup:.2f}x at budget {budget} "
              f"({t_sync:.1f}s -> {t_asha:.1f}s, 1-core host: ASHA + "
              f"ask-chunking only, no slot overlap)"),
        claim("evaluation slots stay saturated (no round barrier)",
              r_asha.utilization >= 0.5,
              f"utilization {r_asha.utilization:.2f} over "
              f"{r_asha.makespan_s:.1f}s makespan"),
        claim("ASHA epoch savings reported separately",
              0.0 < r_asha.asha_epochs_saved_frac < 1.0,
              f"{r_asha.asha_epochs_saved_frac * 100:.0f}% of "
              f"{budget * wl.n_epochs} full-budget epochs skipped; "
              f"plain async (no scheduler) {speedup_plain:.2f}x"),
        claim("async incumbent tracks the synchronous one",
              quality <= 0.10,
              f"best_value delta {quality * 100:.2f}% at equal budget"),
        claim("study journal validates against the schema",
              not journal_problems,
              "tools/journal_schema.py: " +
              ("ok" if not journal_problems else
               "; ".join(journal_problems[:3]))),
    ]
    print_claims(out["claims"])
    save("BENCH_study", out)
    root = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_study.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=2)
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="tiny budget/scale: wiring check, not a perf gate")
    p.add_argument("--budget", type=int, default=None)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--window", type=int, default=None,
                   help="ask-ahead depth (default 4*slots)")
    p.add_argument("--scale", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    run(quick=args.quick, budget=args.budget, slots=args.slots,
        window=args.window, scale=args.scale, seed=args.seed)


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
