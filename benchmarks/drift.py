"""Online re-tuning under workload drift -> BENCH_drift.json.

The drift PR's receipts: on phase-shifting workloads
(:mod:`repro.core.drift`), does ``Study.tune(online=True)`` actually
re-adapt — beating the static default config, approaching the per-phase
static-best oracle, and NEVER thrashing?  Three arms per scenario, all on
the compiled backend with common random numbers so comparisons are paired:

* **default** — the engine's default config runs the whole drifting trace
  unchanged (what you get with no tuning at all);
* **online** — the sliding-window online tuner
  (:class:`~repro.core.tune_online.OnlineTuner`): windowed CRN candidate
  batches, histogram/residual phase-change detection, warm-restarted SMAC,
  hysteresis/dwell switch guard;
* **oracle** — per-phase static-best: at each TRUE phase boundary (the
  oracle knows the spec), a fresh SMAC searches that phase from the
  oracle's own system state and the single best config runs the phase.
  This is the information-unfair lower bound the online tuner is graded
  against.

Scenarios: ``hotspot`` (gups hot-set rotation, 3 phases x 20 epochs) and
``splice`` (gups -> silo/ycsb-c wholesale change at epoch 30) — the two
drift families the acceptance gates name.

Reported per scenario (written to ``BENCH_drift.json``, repo root and
``benchmarks/results/``):

* cumulative wall of each arm + the online/default and online/oracle
  ratios.  The oracle comparison is gated on the STEADY-STATE ratio
  (windows past the cold-start window 0): the oracle deploys a tuned
  config from epoch 0, which no online method can match before its first
  measurement, so the cold-start window is reported in the raw ratio but
  excluded from the gate (gates: online < default;
  steady-state online <= ``ORACLE_SLACK`` x oracle);
* **time-to-readapt**: per true switch, how many windows until the online
  arm's deployed window wall is back within 10% of the oracle's for the
  same window (gate: re-adapts within ``READAPT_WINDOWS`` windows);
* switch/detection/guard receipts with the zero-thrash assertion
  (``thrash_events == 0`` — the hysteresis/dwell guard makes config
  oscillation structurally impossible; this gate pins it).

Usage::

    PYTHONPATH=src python -m benchmarks.drift [--smoke|--quick]
        [--scale S] [--seed N] [--window W] [--batch Q]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import ExperimentSpec, SimOptions, Study  # noqa: E402
from repro.core import engine_jax  # noqa: E402
from repro.core.bo.smac import SMACOptimizer  # noqa: E402
from repro.core.drift import BUILTIN_DRIFTS  # noqa: E402
from repro.core.knobs import get_space  # noqa: E402
from repro.core.simulator import run_simulation_segment  # noqa: E402

from .common import claim, print_claims, save  # noqa: E402

#: acceptance slack: online cumulative wall vs the per-phase oracle's
ORACLE_SLACK = 1.6
#: acceptance bound on windows-to-readapt after a true phase switch
READAPT_WINDOWS = 3
#: "re-adapted" = deployed window wall within 10% of the oracle's window
READAPT_TOL = 1.10

SCENARIOS = {"hotspot": "drift-hotspot", "splice": "drift-splice"}


def _study(drift_name: str, scale: float, seed: int) -> Study:
    return Study(ExperimentSpec(
        engine="hemem",
        workload=dict(name=drift_name, scale=scale),
        options=SimOptions(seed=seed, backend="jax", crn=True,
                           sampler="sparse")))


def _segment(study: Study, configs, lo, hi, carry, return_carry=True):
    spec, opts = study.spec, study.spec.options
    seg_carry = None if carry is None else \
        engine_jax.broadcast_carry_row(carry, 0, len(configs))
    return run_simulation_segment(
        study.workload(), spec.engine.name, configs, study.machine,
        fast_slow_ratio=spec.fast_slow_ratio, seeds=opts.seed,
        sampler=opts.sampler, fast_capacity_pages=spec.fast_capacity_pages,
        backend="jax", crn=True, exact_select=opts.exact_select,
        epoch_start=lo, epoch_stop=hi, carry=seg_carry,
        return_carry=return_carry)


def default_arm(study: Study) -> np.ndarray:
    """Per-epoch walls of the default config over the whole trace."""
    out = _segment(study, [study.spec.engine.config], 0, None, None,
                   return_carry=False)
    return np.asarray(out["wall_ms"])[:, 0]


def oracle_arm(study: Study, dspec, q: int, rounds: int, seed: int):
    """Per-phase static-best with TRUE switch knowledge (lower bound).

    At each phase boundary the oracle runs ``rounds`` SMAC candidate
    batches of ``q`` over the phase — every batch a paired CRN
    counterfactual from the oracle's own system state — then deploys the
    single best config for the phase.  Returns the composed per-epoch
    walls and the per-phase configs.
    """
    space = get_space(study.spec.engine.name)
    bounds = list(dspec.phase_starts) + [dspec.n_epochs]
    carry, walls, configs = None, [], []
    for i in range(len(dspec.phases)):
        lo, hi = bounds[i], bounds[i + 1]
        opt = SMACOptimizer(space, seed=seed + 7 * i, n_init=q)
        best_cfg, best_val = study.spec.engine.config, float("inf")
        for _ in range(rounds):
            cands = opt.ask_batch(q)
            vals = np.asarray(
                _segment(study, cands, lo, hi, carry,
                         return_carry=False)["wall_ms"]).sum(axis=0)
            opt.tell_batch(cands, vals)
            j = int(np.argmin(vals))
            if float(vals[j]) < best_val:
                best_cfg, best_val = dict(cands[j]), float(vals[j])
        out = _segment(study, [best_cfg], lo, hi, carry)
        carry = out["carry"]
        walls.append(np.asarray(out["wall_ms"])[:, 0])
        configs.append(best_cfg)
    return np.concatenate(walls), configs


def _window_sums(per_epoch: np.ndarray, W: int) -> np.ndarray:
    return np.array([per_epoch[lo:lo + W].sum()
                     for lo in range(0, len(per_epoch), W)])


def readapt_times(online_w, oracle_w, switch_epochs, W):
    """Windows-to-readapt per true switch (None = never within the run)."""
    out = []
    for s in switch_epochs:
        k0 = -(-s // W)  # first window fully past the switch
        t = None
        for k in range(k0, len(online_w)):
            if online_w[k] <= READAPT_TOL * oracle_w[k]:
                t = k - k0
                break
        out.append(t)
    return out


def run_scenario(name: str, scale: float, seed: int, W: int, q: int,
                 budget: int, oracle_rounds: int, verbose: bool):
    dspec = BUILTIN_DRIFTS[SCENARIOS[name]]
    study = _study(dspec.name, scale, seed)
    print(f"== {name}: {dspec.name} n_epochs={dspec.n_epochs} "
          f"switches={list(dspec.switch_epochs)} scale={scale} "
          f"W={W} q={q} budget={budget}", flush=True)

    t0 = time.time()
    default_pe = default_arm(study)
    res = study.tune(online=True, window_epochs=W, batch_size=q,
                     budget=budget, seed=seed, verbose=verbose)
    oracle_pe, oracle_cfgs = oracle_arm(study, dspec, q, oracle_rounds,
                                        seed)
    wall_s = time.time() - t0

    online_w = res.deployed_walls
    oracle_w = _window_sums(oracle_pe, W)
    default_w = _window_sums(default_pe, W)
    readapt = readapt_times(online_w, oracle_w, dspec.switch_epochs, W)
    totals = {"default": float(default_pe.sum()),
              "online": float(res.total_wall_ms),
              "oracle": float(oracle_pe.sum())}
    out = {
        "scenario": name, "drift": dspec.name,
        "n_epochs": dspec.n_epochs,
        "switch_epochs": list(dspec.switch_epochs),
        "scale": scale, "seed": seed, "window_epochs": W, "q": q,
        "budget": budget, "oracle_rounds": oracle_rounds,
        "totals_ms": totals,
        "online_vs_default": totals["online"] / totals["default"],
        "online_vs_oracle": totals["online"] / totals["oracle"],
        # steady state: drop window 0 from both arms (cold start — the
        # oracle is pre-tuned at epoch 0, the online arm cannot be)
        "online_vs_oracle_steady":
            float(online_w[1:].sum() / oracle_w[1:].sum()),
        "readapt_windows": readapt,
        "switches": res.switches, "detections": res.detections,
        "guard_blocks": res.guard_blocks,
        "thrash_events": res.thrash_events,
        "evals_used": res.evals_used,
        "window_walls_ms": {"online": online_w.tolist(),
                            "oracle": oracle_w.tolist(),
                            "default": default_w.tolist()},
        "oracle_configs": oracle_cfgs,
        "final_config": res.final_config,
        "wall_s": wall_s,
    }
    print(f"   totals (ms): default={totals['default']:.0f} "
          f"online={totals['online']:.0f} oracle={totals['oracle']:.0f}  "
          f"readapt={readapt}  switches={res.switches} "
          f"thrash={res.thrash_events}  [{wall_s:.1f}s]", flush=True)
    return out


def run(smoke: bool = False, quick: bool = False, scale=None, seed: int = 0,
        window=None, batch=None, verbose: bool = False):
    if smoke:
        scale = scale or 0.03
        W, q, budget, rounds = window or 10, batch or 3, 18, 1
    elif quick:
        scale = scale or 0.04
        W, q, budget, rounds = window or 10, batch or 4, 24, 2
    else:
        scale = scale or 0.06
        W, q, budget, rounds = window or 10, batch or 6, 36, 4

    scenarios = [run_scenario(n, scale, seed, W, q, budget, rounds,
                              verbose) for n in SCENARIOS]

    claims = []
    for s in scenarios:
        nm = s["scenario"]
        claims.append(claim(
            f"{nm}: zero config thrashing",
            s["thrash_events"] == 0,
            f"thrash_events = {s['thrash_events']}, "
            f"guard_blocks = {s['guard_blocks']}"))
        claims.append(claim(
            f"{nm}: receipts complete",
            bool(s["window_walls_ms"]["online"])
            and s["detections"] >= len(s["switch_epochs"]),
            f"{len(s['window_walls_ms']['online'])} windows, "
            f"{s['detections']} detections for "
            f"{len(s['switch_epochs'])} true switches"))
        if not smoke:  # perf gates need the non-smoke budgets
            claims.append(claim(
                f"{nm}: online beats default",
                s["online_vs_default"] < 1.0,
                f"online/default = {s['online_vs_default']:.3f}"))
            claims.append(claim(
                f"{nm}: online approaches per-phase oracle (steady state)",
                s["online_vs_oracle_steady"] <= ORACLE_SLACK,
                f"steady online/oracle = "
                f"{s['online_vs_oracle_steady']:.3f} (slack {ORACLE_SLACK};"
                f" raw incl. cold start = {s['online_vs_oracle']:.3f})"))
            claims.append(claim(
                f"{nm}: re-adapts within {READAPT_WINDOWS} windows",
                all(t is not None and t <= READAPT_WINDOWS
                    for t in s["readapt_windows"]),
                f"readapt = {s['readapt_windows']}"))
    print_claims(claims)

    out = {"mode": "smoke" if smoke else ("quick" if quick else "full"),
           "scenarios": scenarios,
           "claims": claims,
           "ok": all(ok for _, ok, _ in claims)}
    save("BENCH_drift", out)
    root = os.path.join(os.path.dirname(__file__), "..", "BENCH_drift.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=2, default=float)
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny wiring check (CI): no perf gates")
    p.add_argument("--quick", action="store_true",
                   help="reduced budgets, perf gates active")
    p.add_argument("--scale", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--window", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--verbose", action="store_true")
    args = p.parse_args()
    out = run(smoke=args.smoke, quick=args.quick, scale=args.scale,
              seed=args.seed, window=args.window, batch=args.batch,
              verbose=args.verbose)
    raise SystemExit(0 if out["ok"] else 1)


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
