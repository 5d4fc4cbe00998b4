"""Quickstart: tune HeMem's knobs for a workload with SMAC-BO (the paper's
pipeline, §3.1) through the typed Study API, and print the before/after
table.

    PYTHONPATH=src python examples/quickstart.py [--workload gups] [--budget 40]

Pass ``--batch-size 8`` to evaluate whole candidate batches per tuning
iteration through the vectorized simulator, and ``--workers auto`` to
additionally shard each batch over a process pool.  With jax installed,
``--batch-size 8 --backend jax`` compiles the whole epoch loop (engines +
samplers + cost model) into one jitted ``lax.scan`` and adds ``--crn``
common-random-number evaluation, so every candidate batch is compared under
identical monitoring noise::

    PYTHONPATH=src python examples/quickstart.py --batch-size 8 \\
        --backend jax --crn

The jax backend plans migrations with the **exact** top-k selection kernel
(``repro.kernels.select_topk``; bit-identical page sets to the numpy
reference's stable sorts) — ``SimOptions(exact_select=False)`` keeps the
historical log-quantized approximation for ablations, and
``python -m benchmarks.batched_tuning --backend jax --select
{pallas,quantized,ref}`` measures what exactness costs.

The experiment is fully described by one JSON-round-trippable
``ExperimentSpec``; see ``examples/legacy_quickstart.py`` for the
deprecated pre-PR-2 call pattern.

The same Study API also drives the REAL serving path (PR 6): engines that
implement the lifted protocol (``repro.core.engine_jax.register_jax_engine``;
``kv-hemem`` ships) compile end-to-end under ``backend="jax"``, and
``TieredKVCache(compiled=True)`` runs decode as ONE fused jit (append +
paged attention + read recording) with batched ``page_migrate`` epochs —
bit-identically to the per-page Python loop.  ``Study.tune(objective=...)``
accepts a custom objective, e.g. a p99-latency/recall score over a
replayable ``TrafficSpec`` arrival trace; see ``examples/tune_serving.py``
and ``python -m benchmarks.serving_tiered_kv``.

**Async tuning & resume** (PR 7): ``--executor async`` hands the study to
the asynchronous trial-executor service — ``--slots N`` evaluation slots
stay saturated with trials (no per-round barrier), ``--scheduler asha``
adds successive-halving early stopping over ¼/½/full-epoch rungs (on the
jax backend promoted trials resume mid-run from the epoch-loop
checkpoint), and ``--journal study.jsonl`` records every ask/eval/rung/
tell decision as replayable JSON lines.  A killed study picks up exactly
where it died::

    PYTHONPATH=src python examples/quickstart.py --backend jax \\
        --executor async --slots 8 --scheduler asha --journal study.jsonl
    # ... SIGKILL it mid-run, then:
    PYTHONPATH=src python examples/quickstart.py --backend jax \\
        --executor async --slots 8 --scheduler asha --journal study.jsonl \\
        --resume

The control loop is deterministic (every decision happens at canonical
commit order, not wall-clock arrival), so the resumed journal, trial
table and incumbent are byte/bit-identical to an uninterrupted run —
and ``--executor async --slots 1`` reproduces the synchronous path's
incumbent bit-identically.  Receipts: ``python -m benchmarks.study_async``
-> ``BENCH_study.json``; journal schema: ``tools/journal_schema.py``.

**Fault-tolerant fleets** (PR 8): ``--executor fleet`` puts the same study
behind the lease-and-commit coordinator — ``--fleet-workers N`` worker
*processes* (or remote hosts via ``pool="socket"`` and ``python -m
repro.core.tune_service.worker --connect HOST:PORT``) drain one shared
work-unit queue.  Every dispatched unit carries a heartbeat-monitored
lease: a worker that dies, wedges or loses its result message has its
lease expired and the unit re-issued to another worker (duplicate
execution is safe — results are deterministic, the first commit wins and
any late twin is asserted bitwise equal), and at zero live workers the
coordinator degrades to its local slot rather than wedging.  The journal
gains ``lease``/``expire``/``reissue`` events, recorded at commit order,
so a SIGKILLed coordinator resumes byte-identically even mid-re-issue::

    PYTHONPATH=src python examples/quickstart.py --executor fleet \\
        --fleet-workers 4 --journal study.jsonl

Receipts (injected 1-in-8 worker kills, utilization, re-issue overhead):
``python -m benchmarks.study_fleet`` -> ``BENCH_study.json["fleet"]``;
fault injectors for tests live in ``repro.core.tune_service.faults``.

**Hardened multi-host fleets** (PR 10): ``--fleet-spec FLEET.json``
deploys the coordinator against a frozen
:class:`~repro.core.tune_service.FleetSpec` — ONE artifact holding the
bind address, worker count/hosts, heartbeat + lease parameters and the
shared ``auth_key`` that every socket frame is HMAC-signed with
(length-capped before allocation, replay-protected, bounded reads;
workers greet with a signed hello before any unit is leased, so
reachability no longer implies trust).  Mint a spec and bring up its
workers with the launcher, then point the study at it::

    python tools/fleet_launch.py --init fleet.json --workers 4
    python tools/fleet_launch.py fleet.json &      # or --print for the
                                                   # per-host commands
    PYTHONPATH=src python examples/quickstart.py --executor fleet \\
        --fleet-spec fleet.json --scheduler asha --journal study.jsonl

Workers re-dial with backoff when the link drops and the coordinator
re-attaches the live lease (``reconnect`` in the journal); invalid
frames are journaled as ``reject`` events and the connection is dropped.
``--scheduler asha`` now composes with the fleet: rung segments
re-derive their epoch prefix from scratch, so early stopping survives
lease expiry and re-issue bitwise.  The auth key is a secret — it rides
the spec file or the ``REPRO_FLEET_KEY`` environment variable, never
argv or the journal; keep spec files out of version control.

**Online re-tuning under drift** (PR 9): ``--drift`` swaps the workload
for a registered phase-shifting trace (:mod:`repro.core.drift`) and
``--online`` runs the sliding-window online tuner instead of a one-shot
search: every ``--window`` epochs ONE compiled CRN segment evaluates the
deployed config (row 0 — the system's actual trajectory) next to
``--batch-size`` SMAC candidates as paired what-if-we-switched
counterfactuals; a detected phase change (sampled-histogram divergence or
surrogate-residual blowup) warm-restarts the optimizer from the prior
elites, and switches apply only past a hysteresis margin + dwell period,
so the config can never thrash.  Worked hotspot-rotation example (the hot
set moves every 20 epochs; watch the tuner detect each rotation and
re-adapt)::

    PYTHONPATH=src python examples/quickstart.py --backend jax --crn \\
        --drift drift-hotspot --online --window 10 --batch-size 6 \\
        --budget 36

``--drift drift-splice`` replays a gups -> silo/ycsb-c wholesale change
instead, and custom drifts are one-liners (``DriftSpec.splice(...)``,
``.hotspot(...)``, ``.wset(...)`` — ``spec.register()`` makes them plain
workload names).  Receipts (time-to-readapt, cumulative slowdown vs the
default and per-phase-oracle arms, zero-thrash assertion):
``python -m benchmarks.drift`` -> ``BENCH_drift.json``.

The optimizer itself runs its compiled hot path by default (PR 5): the
random-forest surrogate is grown level-synchronously into flat arrays and
EI acquisition is one fused vectorized pass (jitted on TPU hosts) ending in
the exact ``select_topk`` top-q kernel — ask/tell costs a few percent of
evaluation wall clock (receipts: ``python -m benchmarks.bo_overhead`` ->
``BENCH_bo.json``).  ``Study.tune(surrogate="reference")`` pins the
recursive reference forest (bit-identical suggestions, for debugging) and
``acquisition="legacy"`` replays the pre-PR-5 scoring pipeline.
"""
import argparse
import json
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core.knobs import HEMEM_SPACE
from repro.core.bo.importance import knob_importance


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gups")
    ap.add_argument("--input", default="")
    ap.add_argument("--machine", default="pmem-large")
    ap.add_argument("--budget", type=int, default=40)
    ap.add_argument("--batch-size", type=int, default=1,
                    help="evaluate q candidates per iteration in one "
                         "vectorized simulator pass (1 = sequential)")
    ap.add_argument("--workers", default=1,
                    help="process-pool size for batch sharding (int or auto)")
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="'jax' compiles the whole epoch loop (one jitted "
                         "lax.scan per engine/workload shape)")
    ap.add_argument("--crn", action="store_true",
                    help="common random numbers: all candidates of a batch "
                         "see identical monitoring noise (requires "
                         "--backend jax)")
    ap.add_argument("--executor", choices=("sync", "async", "fleet"),
                    default="sync",
                    help="'async' = slot-saturating trial executor; "
                         "'fleet' = lease-and-commit coordinator over "
                         "worker processes (repro.core.tune_service)")
    ap.add_argument("--slots", type=int, default=1,
                    help="async evaluation slots (--executor async)")
    ap.add_argument("--fleet-workers", type=int, default=2,
                    help="fleet worker processes (--executor fleet)")
    ap.add_argument("--fleet-spec", metavar="SPEC.json", default=None,
                    help="frozen FleetSpec JSON from tools/fleet_launch.py "
                         "--init; switches the fleet to the authenticated "
                         "socket transport and supplies workers/heartbeat/"
                         "auth key (--executor fleet; overrides "
                         "--fleet-workers)")
    ap.add_argument("--scheduler", choices=("asha",), default=None,
                    help="ASHA successive-halving early stopping "
                         "(--executor async)")
    ap.add_argument("--journal", default=None,
                    help="JSON-lines study journal path (--executor async)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed study from --journal")
    ap.add_argument("--drift", default=None,
                    help="phase-shifting workload name (drift-hotspot, "
                         "drift-wset, drift-splice, or a registered "
                         "DriftSpec); overrides --workload")
    ap.add_argument("--online", action="store_true",
                    help="sliding-window online re-tuning (requires "
                         "--backend jax --crn; see repro.core.tune_online)")
    ap.add_argument("--window", type=int, default=10,
                    help="online re-tuning window length in epochs")
    args = ap.parse_args()
    workers = args.workers if args.workers == "auto" else int(args.workers)

    workload = WorkloadSpec(args.drift, scale=0.05) if args.drift \
        else WorkloadSpec(args.workload, args.input)
    spec = ExperimentSpec(
        engine="hemem",
        workload=workload,
        machine=args.machine,
        options=SimOptions(sampler="sparse" if args.batch_size > 1
                           else "elementwise", workers=workers,
                           backend=args.backend, crn=args.crn))
    study = Study(spec)
    if args.online:
        print(f"Online re-tuning of HeMem for {study.key} "
              f"(window {args.window} epochs, q={args.batch_size}, "
              f"budget {args.budget})...")
        print(f"spec: {json.dumps(spec.to_dict())}\n")
        res = study.tune(online=True, window_epochs=args.window,
                         batch_size=args.batch_size, budget=args.budget,
                         seed=0, journal=args.journal, resume=args.resume,
                         verbose=True)
        print(f"\ndeployed cumulative wall: {res.total_wall_ms:12.1f} ms "
              f"over {len(res.windows)} windows")
        print(f"switches: {res.switches} (windows {res.switch_windows}) | "
              f"detections: {res.detections} | guard-blocked: "
              f"{res.guard_blocks} | thrash: {res.thrash_events}")
        print("final config (changes vs default):")
        dflt = HEMEM_SPACE.default_config()
        for k, v in res.final_config.items():
            if v != dflt[k]:
                print(f"  {k:28s} {dflt[k]:>8} -> {v}")
        return
    if args.executor == "fleet":
        mode = f"fleet spec={args.fleet_spec}" if args.fleet_spec \
            else f"fleet workers={args.fleet_workers}"
    elif args.executor == "async":
        mode = f"async slots={args.slots}" + \
            (f" +{args.scheduler}" if args.scheduler else "")
    elif args.batch_size > 1:
        mode = f"batch q={args.batch_size}"
    else:
        mode = "sequential"
    print(f"Tuning HeMem for {study.key} (budget {args.budget}, {mode})...")
    print(f"spec: {json.dumps(spec.to_dict())}\n")
    if args.executor in ("async", "fleet"):
        fleet_kw = {}
        if args.executor == "fleet":
            if args.fleet_spec:
                from repro.core.tune_service import FleetSpec
                # the spec supplies workers/heartbeat/lease/auth key
                fleet_kw = {"fleet_spec": FleetSpec.load(args.fleet_spec)}
            else:
                fleet_kw = {"workers": args.fleet_workers}
        res = study.tune(budget=args.budget, seed=0, verbose=True,
                         executor=args.executor, slots=args.slots,
                         scheduler=args.scheduler, journal=args.journal,
                         resume=args.resume, **fleet_kw)
        print(f"\ntrials: {len(res.trials)} "
              f"({res.n_stopped_early} stopped early, "
              f"{res.n_failed} failed) | slot utilization "
              f"{res.utilization:.2f}"
              + (f" | journal: {args.journal}" if args.journal else ""))
        if res.fleet is not None:
            fs = res.fleet
            print(f"fleet: {fs['workers']} {fs['pool']} workers | "
                  f"{fs['n_worker_deaths']} deaths, "
                  f"{fs['n_respawns']} respawns, "
                  f"{fs['n_reissues']} re-issues"
                  + (" | degraded to local slot" if fs["degraded"] else ""))
    else:
        res = study.tune(budget=args.budget, batch_size=args.batch_size,
                         seed=0, verbose=True)
    print(f"\ndefault: {res.default_value:8.1f}s")
    print(f"best:    {res.best_value:8.1f}s   ({res.improvement:.2f}x)")
    print("\nbest config (changes vs default):")
    dflt = HEMEM_SPACE.default_config()
    for k, v in res.best.config.items():
        if v != dflt[k]:
            print(f"  {k:28s} {dflt[k]:>8} -> {v}")
    print("\nknob importance (surrogate-based, §3.1):")
    for k, v in list(knob_importance(HEMEM_SPACE, res.history).items())[:5]:
        print(f"  {k:28s} {v:.2f}")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
