"""Study: the unified typed front-end over simulate / tune / sweep.

One :class:`~repro.core.specs.ExperimentSpec` in, every call pattern out:

* ``Study(spec).run()`` — simulate the spec's engine config (a single
  :class:`~repro.core.simulator.SimResult`); ``run(configs=[...])`` pushes a
  whole candidate batch through ONE shared workload trace
  (:func:`~repro.core.simulator.run_simulation_batch`);
* ``Study(spec).tune(budget, batch_size)`` — SMAC-BO knob tuning
  (:class:`~repro.core.bo.tuner.TuningSession`), batched per iteration when
  ``batch_size > 1``;
* ``Study(spec).sweep(...)`` — multi-engine × multi-workload grids, each
  (engine, workload) cell evaluated as one batched simulator pass.

Workload traces are built once per Study and shared across evaluations
(builds are deterministic in the spec, so this never changes numerics — it
only removes redundant trace generation the legacy per-call path paid).

Migration table (old call -> new call):

======================================================  ======================================================
old                                                     new
======================================================  ======================================================
``evaluate(eng, cfg, wl, inp, machine, ...)``           ``Study(ExperimentSpec(engine=EngineSpec(eng, cfg),
                                                        workload=WorkloadSpec(wl, inp), ...)).run().total_s``
``evaluate_batch(eng, cfgs, wl, ...)``                  ``Study(spec).run(configs=cfgs)``
``run_simulation(workload, eng, cfg, machine)``         ``Study(spec).run()`` (full ``SimResult``)
``tune_scenario(eng, Scenario(...), budget, ...)``      ``Study(spec).tune(budget=..., batch_size=...)``
``Scenario(workload, inp, machine, ...)``               ``ExperimentSpec`` (+ ``SimOptions`` for seeds/
                                                        sampler/workers/backend)
``make_engine(name, cfg, tier)``                        ``@register_engine(name)`` + ``Study``; the registry
                                                        resolves dispatch
sequential fig-2/fig-9 sweep loops                      ``Study(spec).sweep(engines=..., workloads=...,
                                                        configs=...)``
======================================================  ======================================================
"""

from __future__ import annotations

import dataclasses
from typing import (Any, Dict, List, Mapping, Optional, Sequence, Tuple,
                    Union)

from . import spans
from .bo.tuner import TuningResult, TuningSession
from .knobs import Config, KnobSpace
from .simulator import (Machine, SimResult, get_machine,
                        run_simulation_batch, run_simulation_cells)
from .specs import EngineSpec, ExperimentSpec, SimOptions, WorkloadSpec
from .workloads import Workload, make_workload


@dataclasses.dataclass
class SweepResult:
    """Results of a multi-engine × multi-workload sweep.

    ``cells`` maps ``(engine_name, workload_label)`` to the list of
    :class:`~repro.core.simulator.SimResult` for that cell's config batch
    (one entry per config, in input order).  The workload label is
    ``WorkloadSpec.key``; when a sweep contains several variants of the same
    workload (different threads/scale) the label is extended with
    ``#t<threads>/s<scale>`` so no cell is overwritten.
    """

    cells: Dict[Tuple[str, str], List[SimResult]] = \
        dataclasses.field(default_factory=dict)

    def __getitem__(self, key: Tuple[str, str]) -> List[SimResult]:
        return self.cells[key]

    def __len__(self) -> int:
        return len(self.cells)

    def items(self):
        return self.cells.items()

    def total_s(self) -> Dict[Tuple[str, str], List[float]]:
        """Execution times per cell, one per config."""
        return {k: [r.total_s for r in v] for k, v in self.cells.items()}


class Study:
    """Unified front-end: one spec, three call patterns (run/tune/sweep)."""

    def __init__(self, spec: Optional[ExperimentSpec] = None, *,
                 machine: Optional[Machine] = None, **spec_kwargs):
        if spec is None:
            spec = ExperimentSpec(**spec_kwargs)
        elif spec_kwargs:
            raise TypeError("pass either a spec or spec kwargs, not both")
        if not isinstance(spec, ExperimentSpec):
            raise TypeError(f"expected ExperimentSpec, got {type(spec)!r}")
        if machine is not None and machine.name != spec.machine:
            raise ValueError(f"machine override {machine.name!r} does not "
                             f"match spec.machine {spec.machine!r}")
        self.spec = spec
        # an explicit Machine instance overrides the registry resolution —
        # this is how the legacy shims honour ad-hoc Machine objects whose
        # name collides with a registered profile
        self.machine: Machine = machine if machine is not None \
            else get_machine(spec.machine)
        self._workloads: Dict[Tuple, Workload] = {}

    @property
    def key(self) -> str:
        return self.spec.key

    # -- workload construction (cached; builds are deterministic) ----------
    def workload(self, wspec: Optional[WorkloadSpec] = None) -> Workload:
        wspec = wspec if wspec is not None else self.spec.workload
        threads = wspec.threads if wspec.threads is not None \
            else self.machine.default_threads
        cache_key = (wspec.name, wspec.input_name, threads, wspec.scale,
                     self.spec.options.seed)
        wl = self._workloads.get(cache_key)
        if wl is None:
            wl = make_workload(wspec.name, wspec.input_name, threads=threads,
                               scale=wspec.scale,
                               seed=self.spec.options.seed)
            self._workloads[cache_key] = wl
        return wl

    # -- simulate ----------------------------------------------------------
    def run(self, configs: Optional[Sequence[Mapping[str, Any]]] = None
            ) -> "SimResult | List[SimResult]":
        """Simulate the spec (one ``SimResult``), or a candidate batch.

        With ``configs`` (a sequence of knob configs), all B candidates run
        through one shared workload trace and a list of per-config results
        is returned; configs are used as-is (the optimizer and
        :class:`~repro.core.specs.EngineSpec` produce validated configs).
        """
        opts = self.spec.options
        batch = [self.spec.engine.config] if configs is None \
            else [dict(c) for c in configs]
        results = run_simulation_batch(
            self.workload(), self.spec.engine.name, batch, self.machine,
            fast_slow_ratio=self.spec.fast_slow_ratio, seeds=opts.seed,
            sampler=opts.sampler, record_heatmap=opts.record_heatmap,
            heat_bins=opts.heat_bins,
            fast_capacity_pages=self.spec.fast_capacity_pages,
            backend=opts.backend, crn=opts.crn, workers=opts.workers,
            exact_select=opts.exact_select)
        return results[0] if configs is None else results

    # -- tune --------------------------------------------------------------
    def tune(self, budget: int = 100, batch_size: int = 1, seed: int = 0,
             optimizer: str = "smac", n_init: int = 20,
             random_prob: float = 0.20, verbose: bool = False,
             space: Optional[KnobSpace] = None,
             surrogate: Optional[str] = None,
             acquisition: Optional[str] = None,
             objective: Optional[Any] = None,
             objective_batch: Optional[Any] = None,
             executor: str = "sync", slots: int = 1,
             scheduler: Optional[str] = None,
             journal: Optional[str] = None, resume: bool = False,
             pool: str = "thread", eta: int = 4,
             window: Optional[int] = None,
             workers: Optional[int] = None, retries: int = 1,
             timeout_s: Optional[float] = None,
             faults: Optional[Any] = None,
             heartbeat_s: Optional[float] = None,
             lease_deadline: Optional[int] = None,
             max_respawns: Optional[int] = None,
             fleet_spec: Optional[Any] = None,
             online: bool = False,
             window_epochs: Optional[int] = None,
             hysteresis: float = 0.05,
             dwell_windows: int = 2) -> TuningResult:
        """SMAC-BO tuning of the spec's engine knobs (§3.1).

        ``seed`` seeds the optimizer; the simulation seed stays
        ``spec.options.seed`` (matching how the legacy ``tune_scenario``
        reused one scenario seed across evaluations).  ``batch_size=q > 1``
        evaluates each optimizer round as one vectorized simulator pass
        honouring ``spec.options`` (sampler/workers/backend).  With
        ``spec.options.crn`` set, every candidate is evaluated under common
        random numbers — the compiled backend's counter-based noise is
        shared bitwise across the whole run, so all comparisons the
        optimizer makes are paired — and ``tell_batch(crn=True)`` debiases
        any re-evaluated config against its recorded value (see
        :meth:`~repro.core.bo.smac.SMACOptimizer.tell_batch`).

        ``surrogate``/``acquisition`` select the optimizer's internal
        paths (forest builder ``"reference"|"fast"``, scoring pipeline
        ``"fused"|"legacy"``); the defaults are the compiled hot path.
        The returned :class:`~repro.core.bo.tuner.TuningResult` records a
        per-round ask/fit/eval/tell wall-clock breakdown
        (``round_times``), which ``benchmarks/bo_overhead.py`` turns into
        the BENCH_bo.json before/after receipts.

        ``objective`` (``config -> float``, lower is better) replaces the
        default simulate-the-spec objective with a custom one — e.g. the
        serving benchmark's latency+recall score over a ``TieredKVCache``
        traffic replay — while the spec keeps recording *what* is tuned
        (engine name resolves the knob space, ``self.key`` the scenario).
        ``objective_batch`` (``[config] -> [float]``) is its vectorized
        counterpart, used when ``batch_size > 1``.

        **Async tuning & resume** (``executor="async"``). The study is
        handed to :class:`~repro.core.tune_service.TuneService`: ``slots``
        evaluation slots stay saturated with trials (no per-round
        barrier — a new trial is asked the moment the ask-ahead window
        has room), results are committed in canonical creation order, and
        every decision (ask, rung, tell) happens at commit time, so the
        whole study is a deterministic function of its parameters no
        matter how completions interleave.  At ``slots=1,
        scheduler=None`` this reproduces the synchronous path's incumbent
        bit-identically.  Knobs:

        * ``slots`` — evaluation-slot count; ``pool`` picks the slot
          backend (``"thread"`` default, ``"process"`` for the
          simulator's persistent worker pool).
        * ``window`` — ask-ahead depth (default ``slots``): a window
          larger than ``slots`` chunks several asks into one
          ``ask_batch`` call (one surrogate fit per chunk, amortized
          like the sync ``batch_size=q`` path) while the slots stay
          saturated.
        * ``scheduler="asha"`` — successive-halving early stopping over
          ¼/½/full-epoch rungs (``eta`` controls the promotion
          fraction).  Trials the scheduler stops early are told their
          value extrapolated to full budget; on the compiled backend
          promoted trials resume mid-run from the epoch-loop checkpoint
          (the scan carry) instead of re-simulating.  Incompatible with
          custom ``objective=`` (partial-epoch values come from the
          simulator).
        * ``journal=<path>`` — JSON-lines study journal recording every
          ask/eval/rung/tell/fail decision with the replayable spec
          (schema: :mod:`repro.core.tune_service.journal`;
          ``tools/journal_schema.py`` validates it standalone).  With
          ``resume=True`` a killed study re-runs the control loop using
          the journal as an evaluation cache and continues exactly where
          it died — the resumed journal is byte-identical to an
          uninterrupted run's.
        * failures in the objective or shard workers mark that trial
          ``FAILED`` (config + traceback journaled), skip its tell, and
          keep the executor saturated — one bad config cannot kill a
          study.

        The async path returns an
        :class:`~repro.core.tune_service.AsyncTuningResult` (a
        ``TuningResult`` plus the trial table, slot-utilization and
        ASHA-savings receipts); ``benchmarks/study_async.py`` turns those
        into the BENCH_study.json wall-clock receipts.

        **Fault-tolerant fleet tuning** (``executor="fleet",
        workers=N``).  The same deterministic control loop, but the
        evaluation slots are N *remote worker processes* driven by a
        lease-and-commit coordinator
        (:class:`~repro.core.tune_service.FleetExecutor`) that survives
        the fleet misbehaving.  Each dispatched work unit carries a
        lease; the worker heartbeats it every ``heartbeat_s`` while the
        segment runs.  A lease silent for ``lease_deadline`` heartbeats
        (a wedged host), a dead worker (crash/SIGKILL — detected
        immediately), or a lost result message expires the lease and the
        unit is **re-issued** to another worker with backoff.  Duplicate
        execution is safe *because* the study is deterministic: a unit is
        a pure function of its canonical coordinates (seed, batch offset,
        segment bounds), so both executions return the same bits — the
        first result to commit wins, and the late twin is asserted
        bitwise-equal (a free placement-invariance check on every
        straggler).  Lease lifecycle events
        (``lease``/``expire``/``reissue``) are journaled at the unit's
        *commit* point (wall-clock-free, no worker ids), so fleet
        journals — including kill/resume byte-identity — behave exactly
        like local ones.  Knobs:

        * ``workers`` — fleet size (defaults to ``slots``); ``pool``
          picks the transport: ``"process"`` (workers spawned on this
          box) or ``"socket"`` (workers connect over TCP via ``python -m
          repro.core.tune_service.worker --connect HOST:PORT``).
        * ``fleet_spec`` — a frozen
          :class:`~repro.core.tune_service.FleetSpec` (implies
          ``pool="socket"``): ONE JSON artifact carrying the bind
          address, the shared ``auth_key``, worker count/hosts and the
          transport caps.  ``tools/fleet_launch.py`` brings up the
          matching workers (local subprocesses, or printed per-host
          commands) and health-checks every greet.  The socket transport
          is authenticated end to end: every frame is HMAC-SHA256-signed
          with the spec's key, length-capped *before* allocation,
          replay-protected by per-connection sequence numbers, and
          bounded in read time — a worker must present a signed hello
          before any unit is leased, so the old "only connect workers to
          a coordinator you trust" caveat is replaced by key possession.
          Invalid frames are journaled as ``reject`` events and drop the
          connection; a worker whose link drops re-dials with backoff
          and has its in-flight lease re-attached (``reconnect``) or
          safely expired (first-commit-wins absorbs the duplicate).  The
          auth key is a secret: it never enters the journal — keep spec
          files out of version control.
        * ``scheduler="asha"`` composes with the fleet: rung units
          re-derive their epoch prefix by re-running ``[0, hi)`` from
          scratch (bitwise-identical to the checkpointed path — partial
          carries never travel over the wire), so promotion/early-stop
          decisions, heartbeat expiry, straggler re-issue and
          kill/resume all compose unchanged, and the incumbent matches
          the async-executor ASHA run bitwise.
        * ``timeout_s`` — per-unit evaluation bound: a hung objective
          becomes an ``{"error": "timeout..."}`` result (then a retry /
          FAILED trial) instead of wedging the study.  Also honoured by
          the local async executor.
        * ``retries`` — bounded per-trial retry budget (default 1): a
          transient fault (worker crash that exhausted its lease
          attempts, timeout, flaky objective) resubmits the trial's
          segment once before the trial is journaled FAILED, as a
          deterministic journaled ``retry`` event.  Also honoured by the
          local async executor.
        * ``heartbeat_s`` / ``lease_deadline`` / ``max_respawns`` —
          heartbeat cadence, lease deadline in *missed-heartbeat counts*
          (the journal stays wall-clock-free), and the respawn budget for
          dead process workers (a respawn promotes a booted hot-spare
          worker when one is up, keeping the interpreter boot off the
          slot critical path).  When the live fleet hits zero the
          coordinator degrades gracefully to a local slot — slower,
          never wedged.
        * ``faults`` — a
          :class:`~repro.core.tune_service.FaultPlan` of injected worker
          faults (kill/stall/hang/drop/dup/delay, plus the socket
          transport's corrupt/truncate/replay/partition frame faults and
          ``net_delay_s`` link latency, keyed by unit + attempt) for
          robustness testing; see :mod:`repro.core.tune_service.faults`.

        **Online re-tuning under drift** (``online=True,
        window_epochs=W``).  For phase-shifting workloads
        (:class:`~repro.core.drift.DriftSpec`) the study becomes a
        sliding-window control loop (:mod:`repro.core.tune_online`)
        instead of a one-shot search.  The contract:

        * *window*: every ``window_epochs`` epochs, ONE compiled CRN
          segment evaluates ``[deployed] + batch_size`` candidates from
          the deployed system's checkpoint — row 0 is the system's
          actual trajectory, the rest are paired what-if-we-switched
          counterfactuals.  ``budget`` caps total candidate evaluations.
        * *warm restart*: a detected phase change (sampled-histogram
          divergence or surrogate-residual blowup) REPLACES the
          optimizer with a fresh one seeded with the prior elites
          (``SMACOptimizer(seed_configs=...)``), so re-tuning starts
          from previously good configs, not from scratch — and stale
          observations cannot mislead the new phase's forest.
        * *hysteresis*: a config switch is applied only when the best
          candidate beats the deployed config by more than
          ``hysteresis`` (relative margin) AND ``dwell_windows`` windows
          have passed since the last switch — config thrashing is
          structurally impossible, not just unlikely.

        Requires ``SimOptions(backend='jax', crn=True)`` and
        ``executor='sync'``; ``journal=``/``resume=`` give the same
        byte-identical kill/resume contract as async studies.  Returns
        an :class:`~repro.core.tune_online.OnlineTuningResult` (window
        timeline + switch/detection/thrash receipts);
        ``benchmarks/drift.py`` turns those into the BENCH_drift.json
        time-to-readapt and cumulative-slowdown receipts.
        """
        if online:
            from .tune_online import OnlineTuner
            if executor != "sync":
                raise ValueError(
                    "online=True runs its own window loop; it is "
                    "incompatible with executor='async'/'fleet'")
            if window_epochs is None:
                raise ValueError(
                    "online=True requires window_epochs=W (the re-tuning "
                    "window length in epochs)")
            if scheduler is not None or objective is not None \
                    or objective_batch is not None:
                raise ValueError(
                    "online=True is incompatible with scheduler=/"
                    "objective=: the window loop needs the simulator's "
                    "segment checkpoints")
            tuner = OnlineTuner(
                self, window_epochs=window_epochs, batch_size=batch_size,
                budget=budget, seed=seed, n_init=n_init,
                hysteresis=hysteresis, dwell_windows=dwell_windows,
                space=space, journal=journal, resume=resume,
                verbose=verbose)
            return tuner.run()
        if window_epochs is not None:
            raise ValueError("window_epochs requires online=True")
        if executor in ("async", "fleet"):
            from .tune_service import TuneService
            if batch_size != 1 or objective_batch is not None:
                raise ValueError(
                    "executor='async' replaces per-round batching with "
                    "slot saturation; use slots=N instead of batch_size")
            service = TuneService(
                self, budget=budget, slots=slots, scheduler=scheduler,
                seed=seed, optimizer=optimizer, n_init=n_init,
                random_prob=random_prob, space=space, surrogate=surrogate,
                acquisition=acquisition, objective=objective,
                journal=journal, resume=resume, pool=pool, eta=eta,
                window=window, verbose=verbose,
                executor="fleet" if executor == "fleet" else "local",
                workers=workers, retries=retries, timeout_s=timeout_s,
                faults=faults, heartbeat_s=heartbeat_s,
                lease_deadline=lease_deadline, max_respawns=max_respawns,
                fleet_spec=fleet_spec)
            return service.run()
        if executor != "sync":
            raise ValueError(f"unknown executor {executor!r}; expected "
                             f"'sync', 'async' or 'fleet'")
        if scheduler is not None or slots != 1 or journal is not None \
                or resume or window is not None or workers is not None \
                or timeout_s is not None or faults is not None \
                or heartbeat_s is not None or lease_deadline is not None \
                or max_respawns is not None or fleet_spec is not None:
            raise ValueError(
                "slots/scheduler/journal/resume/window/workers/timeout_s/"
                "faults/heartbeat_s/lease_deadline/max_respawns/fleet_spec "
                "require executor='async' or 'fleet'")
        if objective is None:
            def objective(config: Config) -> float:
                return self.run(configs=[config])[0].total_s

            if objective_batch is None:
                def objective_batch(configs: Sequence[Config]
                                    ) -> List[float]:
                    return [r.total_s for r in self.run(configs=configs)]

        with spans.span("repro.study.tune", budget=budget,
                        batch_size=batch_size):
            session = TuningSession(
                self.spec.engine.name, objective, scenario_key=self.key,
                space=space, optimizer=optimizer, budget=budget, seed=seed,
                n_init=n_init, random_prob=random_prob,
                batch_size=batch_size,
                objective_batch=objective_batch if batch_size > 1 else None,
                crn=self.spec.options.crn, surrogate=surrogate,
                acquisition=acquisition)
            return session.run(verbose=verbose)

    # -- sweep -------------------------------------------------------------
    def sweep(self, grid: Optional[Mapping[str, Sequence[Any]]] = None, *,
              engines: Optional[Sequence[Union[EngineSpec, str]]] = None,
              workloads: Optional[Sequence[Union[WorkloadSpec, str]]] = None,
              configs: Optional[Sequence[Mapping[str, Any]]] = None,
              ) -> SweepResult:
        """Evaluate a multi-engine × multi-workload grid in batched passes.

        ``grid`` may bundle the axes as ``{"engines": [...], "workloads":
        [...], "configs": [...]}``; keyword arguments override.  Axes default
        to the spec's engine/workload; bare workload *names* inherit the
        spec's threads and scale (pass full ``WorkloadSpec``s to vary them).  ``configs`` (shared across engines)
        defaults to each engine spec's own config, so ``sweep(engines=[...],
        workloads=[...])`` compares engines at their spec'd settings.

        All (engine, workload) cells are submitted to ONE shared work queue
        (:func:`~repro.core.simulator.run_simulation_cells`): with
        ``workers > 1`` the process pool schedules config shards across
        cells, so it stays saturated even when individual cells are smaller
        than the worker count — nothing is evaluated sequentially per
        config and there is no per-cell barrier.
        """
        grid = dict(grid or {})
        engines = engines if engines is not None else grid.get("engines")
        workloads = workloads if workloads is not None \
            else grid.get("workloads")
        configs = configs if configs is not None else grid.get("configs")
        base_ws = self.spec.workload

        def _ws(w):
            if isinstance(w, str):  # same threads/scale, different workload
                return WorkloadSpec(w, threads=base_ws.threads,
                                    scale=base_ws.scale)
            return WorkloadSpec.coerce(w)

        espcs = [EngineSpec.coerce(e) for e in engines] \
            if engines is not None else [self.spec.engine]
        wspcs = [_ws(w) for w in workloads] \
            if workloads is not None else [base_ws]
        opts = self.spec.options
        # disambiguate same-name workload variants (threads/scale sweeps) so
        # cells never overwrite each other
        base_keys = [w.key for w in wspcs]
        labels = [w.key if base_keys.count(w.key) == 1
                  else f"{w.key}#t{w.threads}/s{w.scale}" for w in wspcs]
        if len(set(labels)) != len(labels):
            raise ValueError(f"duplicate workload specs in sweep: {labels}")
        cell_keys = []
        cells = []
        for ws, wlabel in zip(wspcs, labels):
            wl = self.workload(ws)
            for es in espcs:
                batch = [dict(c) for c in configs] if configs is not None \
                    else [es.config]
                cell_keys.append((es.name, wlabel))
                cells.append((wl, es.name, batch))
        results = run_simulation_cells(
            cells, self.machine, fast_slow_ratio=self.spec.fast_slow_ratio,
            seeds=opts.seed, sampler=opts.sampler,
            record_heatmap=opts.record_heatmap, heat_bins=opts.heat_bins,
            fast_capacity_pages=self.spec.fast_capacity_pages,
            backend=opts.backend, crn=opts.crn, workers=opts.workers,
            exact_select=opts.exact_select)
        out = SweepResult()
        for key, res in zip(cell_keys, results):
            out.cells[key] = res
        return out
