"""Driver of the tuning cells: back-to-back ``Study.tune`` studies.

Set-up builds the study (the GUPS trace at the configuration's scale) and
runs one whole study, which compiles the B=1 and B=q epoch loops and the
acquisition's shapes.  The window then runs studies back to back, study
``i`` under optimizer seed ``(seed, i)``, until ``--seconds`` have passed;
every evaluation's config, batch row, value and completion time is logged.

``tune_evals_per_s``, or the metric that the traffic mix names as its
``rate_metric``, counts the evaluations completed inside the window over the
window's seconds.  After the window the logged sample (each study's
default, the last incumbent and a seeded draw) is simulated again by the
plain reference: the sample's widest relative gap catches an answer gone
wrong, its median gap a path that computes every answer less exactly.  Each
study's incumbent must be its best observation.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from bench import generate
from bench.harness import check
from bench.reference import tune_ref
from bench.reference.hemem import Precision


#: study index of the set-up study, apart from the window's 0, 1, ...
WARMUP = 1 << 30


def make_study(cfg, seed: int):
    from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
    return Study(ExperimentSpec(
        engine=cfg["engine"],
        workload=WorkloadSpec(cfg["workload"], cfg["input"],
                              threads=cfg["threads"], scale=cfg["scale"]),
        machine=cfg["machine"]["name"],
        fast_slow_ratio=cfg["fast_slow_ratio"],
        options=SimOptions(backend=cfg["backend"], sampler=cfg["sampler"],
                           exact_select=cfg["exact_select"],
                           seed=generate.sim_seed(seed))))


class EvalLog:
    """The study's objective: ``Study.run`` on the batch, as ``Study.tune``
    evaluates by default, with each evaluation logged."""

    def __init__(self, study):
        self.study = study
        self.evals = []
        self.tag = None

    def batch(self, configs):
        vals = [r.total_s for r in self.study.run(configs=configs)]
        t = time.perf_counter()
        for row, (c, v) in enumerate(zip(configs, vals)):
            self.evals.append({"config": dict(c), "row": row,
                               "value": float(v), "t": t, "study": self.tag})
        return vals

    def one(self, config):
        return self.batch([config])[0]


def run(h, control: Precision = None):
    """One run of the cell.  ``control`` replaces the program's values by
    the reference's at that lower precision (the control of the
    comparison); the benchmark's own runs leave it None."""
    cfg, tr = h.cell.config, h.cell.traffic
    seed, seconds = h.seed, h.seconds
    study = make_study(cfg, seed)
    log = EvalLog(study)

    def tune(i):
        log.tag = i
        with h.annotate("bench.study"):
            return study.tune(budget=tr["budget"], batch_size=tr["batch_size"],
                              seed=generate.study_seed(seed, i),
                              objective=log.one, objective_batch=log.batch)

    with h.annotate("bench.warmup"):
        tune(WARMUP)
        if tr["batch_size"] > 1:
            # The acquisition scores a pool of 64 candidates per model-based
            # slot of a batch, at least 512, so a batch with many random
            # slots lands in a smaller shape bucket than the warm-up study
            # may have reached.  A study of half-size batches reaches it;
            # its objective replays the values just observed, so no epoch
            # loop runs at a batch size the cell never uses.
            replay = itertools.cycle([e["value"] for e in log.evals])
            study.tune(budget=tr["budget"], batch_size=tr["batch_size"] // 2,
                       seed=generate.study_seed(seed, WARMUP + 1),
                       objective=lambda c: next(replay),
                       objective_batch=lambda cs: [next(replay) for _ in cs])
    log.evals.clear()
    studies = []
    with h.window():
        t0 = time.perf_counter()
        while True:
            r = tune(len(studies))
            studies.append({"round_times": r.round_times,
                            "best": float(r.best_value),
                            "best_config": dict(r.best.config),
                            "default": float(r.default_value),
                            "end": time.perf_counter()})
            if studies[-1]["end"] - t0 >= seconds:
                break
    t_end = t0 + seconds
    window_evals = [e for e in log.evals if e["t"] <= t_end]
    h.read_peak()
    print(f"bench: {len(studies)} studies, {len(log.evals)} evaluations "
          f"({len(window_evals)} inside the window); last study default "
          f"total_s {studies[-1]['default']!r}, incumbent "
          f"{studies[-1]['best']!r}", flush=True)

    # the checked sample: a seeded draw of the window's evaluations, the
    # first study's default and the last study's incumbent
    picks = generate.sample(seed, 5, len(window_evals), cfg["check_sample"])
    chosen = {int(i) for i in picks}
    firsts = {}
    for i, e in enumerate(log.evals):
        firsts.setdefault(e["study"], i)
    chosen.add(firsts[0])
    last = studies[-1]
    chosen.add(next(i for i, e in enumerate(log.evals)
                    if e["study"] == len(studies) - 1
                    and e["config"] == last["best_config"]))
    sample = [log.evals[i] for i in sorted(chosen)]
    t = time.perf_counter()
    ref = tune_ref.check_sample(cfg, sample, generate.sim_seed(seed))
    got = np.array([e["value"] for e in sample]) if control is None else \
        tune_ref.check_sample(cfg, sample, generate.sim_seed(seed), control)
    gaps = np.abs(got - ref) / ref
    print(f"bench: reference over {len(sample)} evaluations in "
          f"{time.perf_counter() - t:.1f}s", flush=True)
    # each study's observations, after its default evaluation
    by_study = {}
    for i, e in enumerate(log.evals):
        if i != firsts[e["study"]]:
            by_study.setdefault(e["study"], []).append(e["value"])
    inc_gap = max(abs(s["best"] - min(by_study[i])) / min(by_study[i])
                  for i, s in enumerate(studies))
    lim = cfg["limits"]
    checks = [check("total_s_gap", gaps.max(), lim["total_s_gap"]),
              check("total_s_gap_median", np.median(gaps),
                    lim["total_s_gap_median"]),
              check("incumbent_gap", inc_gap, 0.0)]
    failed = sum(1 for e in window_evals if not np.isfinite(e["value"])
                 or e["value"] <= 0)
    record = {"studies": studies, "evals": log.evals, "t0": t0,
              "window_s": last["end"] - t0,
              "n_pages": study.workload().n_pages,
              "n_epochs": study.workload().n_epochs}
    return {"attempted": len(window_evals), "failed": failed,
            "metrics": {tr.get("rate_metric", "tune_evals_per_s"):
                        len(window_evals) / seconds},
            "checks": checks, "record": record}


def control_precision() -> Precision:
    """The precision below the configuration's float32: bfloat16."""
    import ml_dtypes
    return Precision(ml_dtypes.bfloat16)
