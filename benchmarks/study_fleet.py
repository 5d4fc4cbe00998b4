"""Fault-tolerant fleet-tuning benchmark -> BENCH_study.json["fleet"].

Exercises ``Study.tune(executor="fleet", workers=N)`` — the
lease-and-commit coordinator serving one shared work-unit queue to N
worker processes — and records the robustness receipts the fleet PR gates
on:

* **determinism across placements**: the fleet incumbent (1 worker, N
  workers, process transport) is bitwise identical to the local async
  executor's at equal study parameters;
* **determinism under faults**: a run with 1-in-8 injected worker kills
  (``FaultPlan(kill_every=8)`` — the worker process SIGKILLs itself
  mid-unit, the coordinator detects the death, respawns a replacement and
  re-issues the lease) still matches the fault-free incumbent bitwise;
* **slot utilization** stays near 1.0 as workers are added AND under the
  injected kills (lost leases cost re-issue overhead, not idle slots) —
  acceptance gate >= 0.8 at full size;
* **re-issue overhead + time-to-recover** columns: wall clock burned by
  duplicate/aborted executions, and the fault-to-reissue latency per
  expired lease;
* the faulty run's journal — including its ``lease``/``expire``/
  ``reissue`` lifecycle events — validates against
  ``tools/journal_schema.py``;
* **socket transport under latency** (hardened-fleet PR): the same study
  over the authenticated frame codec with injected per-frame link
  latency (``FaultPlan(net_delay_s=...)``) stays bitwise identical —
  slower frames, same decisions;
* **ASHA over the fleet** (ROADMAP 3a): ``scheduler="asha"`` on the
  socket fleet under combined kills + latency matches the local async
  ASHA incumbent bitwise, with early stopping actually saving epochs.

The numpy backend keeps worker processes fork-cheap (no per-respawn jax
import/compile), which is what makes a kill-every-8-units fault schedule
affordable; determinism is backend-independent, so the bitwise claims
carry over unchanged.

Usage::

    PYTHONPATH=src python -m benchmarks.study_fleet [--quick]
        [--budget N] [--workers N] [--scale S] [--seed S] [--kill-every K]
        [--net-delay S]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.tune_service import FaultPlan

from .common import claim, print_claims, save


def _study(scale: float, seed: int) -> Study:
    return Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec("gups", scale=scale),
        machine="pmem-large",
        options=SimOptions(seed=seed, sampler="sparse", backend="numpy")))


def run(quick: bool = False, budget: int = None, workers: int = 2,
        scale: float = None, seed: int = 0, kill_every: int = 8,
        net_delay: float = 0.002) -> dict:
    budget = budget if budget is not None else (48 if quick else 512)
    scale = scale if scale is not None else (0.1 if quick else 0.5)
    n_init = min(20, max(4, budget // 8))
    window = 4 * workers
    results_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(results_dir, exist_ok=True)
    journal = os.path.join(results_dir, "study_fleet_journal.jsonl")
    if os.path.exists(journal):
        os.remove(journal)

    wl = _study(scale, seed).workload()
    print(f"GUPS@{scale}/hemem (E={wl.n_epochs}, n_pages={wl.n_pages}), "
          f"budget={budget}, fleet workers={workers} window={window}, "
          f"1-in-{kill_every} injected worker kills", flush=True)

    kw = dict(budget=budget, seed=seed, n_init=n_init, window=window)

    t0 = time.time()
    r_async = _study(scale, seed).tune(executor="async", slots=workers, **kw)
    t_async = time.time() - t0
    print(f"  async slots={workers} (local):   {t_async:7.2f}s  "
          f"best={r_async.best_value:8.3f}s  "
          f"util={r_async.utilization:.2f}", flush=True)

    t0 = time.time()
    r_f1 = _study(scale, seed).tune(executor="fleet", workers=1,
                                    budget=budget, seed=seed, n_init=n_init,
                                    window=window)
    t_f1 = time.time() - t0
    print(f"  fleet workers=1:         {t_f1:7.2f}s  "
          f"best={r_f1.best_value:8.3f}s  util={r_f1.utilization:.2f}",
          flush=True)

    t0 = time.time()
    r_fw = _study(scale, seed).tune(executor="fleet", workers=workers, **kw)
    t_fw = time.time() - t0
    print(f"  fleet workers={workers}:         {t_fw:7.2f}s  "
          f"best={r_fw.best_value:8.3f}s  util={r_fw.utilization:.2f}",
          flush=True)

    plan = FaultPlan(kill_every=kill_every)
    t0 = time.time()
    r_fault = _study(scale, seed).tune(
        executor="fleet", workers=workers, faults=plan, journal=journal,
        max_respawns=budget, **kw)
    t_fault = time.time() - t0
    fs = r_fault.fleet
    recover = fs["time_to_recover_s"]
    print(f"  fleet workers={workers} +kills:  {t_fault:7.2f}s  "
          f"best={r_fault.best_value:8.3f}s  "
          f"util={r_fault.utilization:.2f}  "
          f"deaths={fs['n_worker_deaths']} respawns={fs['n_respawns']} "
          f"reissues={fs['n_reissues']}", flush=True)

    # socket transport + injected per-frame link latency: the hardened
    # codec (HMAC-signed, capped, replay-protected frames) under a slow
    # link — frames arrive late, decisions do not change
    t0 = time.time()
    r_sock = _study(scale, seed).tune(
        executor="fleet", workers=workers, pool="socket",
        faults=FaultPlan(net_delay_s=net_delay), **kw)
    t_sock = time.time() - t0
    sfs = r_sock.fleet
    print(f"  fleet workers={workers} socket+{net_delay * 1e3:.0f}ms: "
          f"{t_sock:7.2f}s  best={r_sock.best_value:8.3f}s  "
          f"util={r_sock.utilization:.2f}  "
          f"reconnects={sfs['n_reconnects']} "
          f"rejects={sfs['n_rejected_frames']}", flush=True)

    # ASHA over the fleet (ROADMAP 3a), under kills AND link latency at
    # once: rung segments re-derive [0, hi) from scratch, so promote/
    # early-stop composes with lease expiry + straggler re-issue
    t0 = time.time()
    r_asha_async = _study(scale, seed).tune(
        executor="async", slots=workers, scheduler="asha", **kw)
    t_asha_async = time.time() - t0
    t0 = time.time()
    r_asha_fleet = _study(scale, seed).tune(
        executor="fleet", workers=workers, pool="socket",
        scheduler="asha",
        faults=FaultPlan(kill_every=kill_every, net_delay_s=net_delay),
        max_respawns=budget, **kw)
    t_asha_fleet = time.time() - t0
    afs = r_asha_fleet.fleet
    print(f"  fleet workers={workers} asha+kills+lat: {t_asha_fleet:7.2f}s  "
          f"best={r_asha_fleet.best_value:8.3f}s  "
          f"util={r_asha_fleet.utilization:.2f}  "
          f"saved={r_asha_fleet.asha_epochs_saved_frac:.2f} "
          f"(async asha: {t_asha_async:.2f}s "
          f"best={r_asha_async.best_value:.3f}s)", flush=True)

    # determinism receipt: the faulty journal (with its lease lifecycle
    # events) must validate against the standalone schema checker
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import journal_schema
    journal_problems = journal_schema.validate_file(journal)
    with open(journal, "r", encoding="utf-8") as fh:
        kinds = [json.loads(line)["event"] for line in fh if line.strip()]
    n_expire = kinds.count("expire")
    n_reissue = kinds.count("reissue")

    def _arm(r, wall):
        out = {
            "wall_s": float(wall), "best_value_s": float(r.best_value),
            "utilization": float(r.utilization),
            "makespan_s": float(r.makespan_s), "busy_s": float(r.busy_s),
        }
        if r.fleet is not None:
            out["fleet"] = r.fleet
        return out

    util_gate = 0.8 if not quick else 0.4
    out = {
        "engine": "hemem", "workload": f"gups:8GiB-hot@{scale}",
        "n_epochs": wl.n_epochs, "n_pages": wl.n_pages,
        "budget": budget, "n_init": n_init, "seed": seed,
        "workers": workers, "window": window, "kill_every": kill_every,
        "net_delay_s": net_delay,
        "cpu_count": os.cpu_count(),
        "arms": {
            "async_local": _arm(r_async, t_async),
            "fleet_w1": _arm(r_f1, t_f1),
            f"fleet_w{workers}": _arm(r_fw, t_fw),
            f"fleet_w{workers}_kills": _arm(r_fault, t_fault),
            f"fleet_w{workers}_socket_latency": _arm(r_sock, t_sock),
            "asha_async": _arm(r_asha_async, t_asha_async),
            f"asha_fleet_w{workers}_kills_latency":
                _arm(r_asha_fleet, t_asha_fleet),
        },
        "reissue_overhead_s": float(fs["reissue_overhead_s"]),
        "time_to_recover_s": {
            "n": len(recover),
            "mean": float(sum(recover) / len(recover)) if recover else None,
            "max": float(max(recover)) if recover else None,
        },
        "journal": os.path.relpath(journal,
                                   os.path.join(os.path.dirname(__file__),
                                                os.pardir)),
        "journal_valid": not journal_problems,
        "journal_lease_events": {"expire": n_expire, "reissue": n_reissue},
    }
    out["claims"] = [
        claim("fleet incumbent is bitwise identical to the local async "
              "executor's at equal study shape",
              r_fw.best_value == r_async.best_value,
              f"async slots={workers} {r_async.best_value!r} == fleet "
              f"workers={workers} {r_fw.best_value!r} (w1 is a different "
              f"study shape: {r_f1.best_value!r})"),
        claim(f"1-in-{kill_every} injected worker kills do not change the "
              f"incumbent (bitwise)",
              r_fault.best_value == r_fw.best_value,
              f"{fs['n_worker_deaths']} worker deaths, "
              f"{fs['n_respawns']} respawns, {fs['n_reissues']} re-issues "
              f"-> best {r_fault.best_value!r}"),
        claim(f"slot utilization >= {util_gate} under injected kills",
              r_fault.utilization >= util_gate,
              f"{r_fault.utilization:.2f} with kills vs "
              f"{r_fw.utilization:.2f} fault-free at workers={workers}, "
              f"{r_f1.utilization:.2f} at workers=1"),
        claim("re-issue overhead and time-to-recover are reported",
              fs["n_worker_deaths"] > 0 and len(recover) > 0,
              f"reissue overhead {fs['reissue_overhead_s']:.2f}s; "
              f"recover mean "
              f"{(sum(recover) / max(len(recover), 1)):.3f}s over "
              f"{len(recover)} expiries"),
        claim("faulty-run journal validates (lease lifecycle included)",
              not journal_problems and n_expire > 0 and n_reissue > 0,
              f"tools/journal_schema.py: "
              f"{'ok' if not journal_problems else '; '.join(journal_problems[:3])}; "
              f"{n_expire} expire / {n_reissue} reissue events"),
        claim(f"authenticated socket transport under {net_delay * 1e3:.0f}ms "
              f"per-frame latency is bitwise identical",
              r_sock.best_value == r_fw.best_value,
              f"socket+latency best {r_sock.best_value!r} == process-pool "
              f"{r_fw.best_value!r}; {sfs['n_rejected_frames']} rejected "
              f"frames, {sfs['n_reconnects']} reconnects"),
        claim("ASHA over the fleet under kills + latency matches async "
              "ASHA bitwise, with real early stopping",
              r_asha_fleet.best_value == r_asha_async.best_value
              and r_asha_fleet.trials == r_asha_async.trials
              and r_asha_fleet.asha_epochs_saved_frac > 0,
              f"fleet asha best {r_asha_fleet.best_value!r} == async asha "
              f"{r_asha_async.best_value!r}; "
              f"{r_asha_fleet.asha_epochs_saved_frac:.1%} epochs saved, "
              f"{afs['n_worker_deaths']} deaths, "
              f"{afs['n_reissues']} re-issues"),
    ]
    print_claims(out["claims"])
    save("BENCH_study_fleet", out)
    # merge into the root BENCH_study.json next to the async receipts —
    # never clobber them
    root = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_study.json")
    payload = {}
    if os.path.exists(root):
        try:
            with open(root) as f:
                payload = json.load(f)
        except ValueError:
            payload = {}
    payload["fleet"] = out
    with open(root, "w") as f:
        json.dump(payload, f, indent=2)
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="tiny budget/scale: wiring check, not a perf gate")
    p.add_argument("--budget", type=int, default=None)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--scale", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kill-every", type=int, default=8,
                   help="kill the worker holding every K-th unit")
    p.add_argument("--net-delay", type=float, default=0.002,
                   help="injected per-frame link latency (socket arms)")
    args = p.parse_args()
    run(quick=args.quick, budget=args.budget, workers=args.workers,
        scale=args.scale, seed=args.seed, kill_every=args.kill_every,
        net_delay=args.net_delay)


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
