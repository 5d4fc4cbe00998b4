"""Batched evaluation pipeline benchmark (+ numpy-vs-jax backend report).

Measures the wall-clock win of the batched tuning stack
(``Study.tune(batch_size=q)`` -> ``SMACOptimizer.ask_batch`` ->
``run_simulation_batch``) against the paper-faithful sequential SMAC loop at
equal budget, and validates two correctness claims:

* **equivalence** — ``run_simulation_batch`` with B configs returns exactly
  the same per-config results as B single-config batches with matched
  seeds;
* **parity** — batched tuning reaches a best_value close to sequential
  SMAC's at equal budget (the search trajectories differ — top-q EI vs
  strictly sequential EI — so a small tolerance applies).

``--backend jax`` additionally benchmarks the **compiled epoch loop**
(:mod:`repro.core.engine_jax`) against the numpy reference for a batch-8
HeMem evaluation on GUPS — one-time compile excluded — and records the
numbers (plus a CRN bitwise check) in ``BENCH_backend.json`` (repo root and
``benchmarks/results/``).  The same backend is then used for the batched
tuning run.  ``--smoke`` runs only a tiny jitted HeMem evaluation + parity
check (the CI fail-fast job).

``--backend jax`` also runs the **selection ablation**: migration-plan
top-k selection via the exact Pallas kernel (``pallas``, interpret mode on
CPU), its pure-jnp ref (``ref``, the CPU default), and the historical
8-bit log-quantized approximation (``quantized``), recording per-call
selection wall-clock, end-to-end evaluation wall-clock and cross-mode
parity under ``select_ablation`` in ``BENCH_backend.json`` — the receipts
for what exact selection costs.  ``--select MODE`` additionally pins that
implementation for the batched tuning run itself.

Usage::

    PYTHONPATH=src python -m benchmarks.batched_tuning [--quick]
        [--budget N] [--batch-size Q] [--workers N|auto] [--seed S]
        [--backend numpy|jax] [--select pallas|ref|quantized] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _default_xla_flags():
    """Split the host into one XLA device per core (max 8) so the compiled
    jax epoch loop can shard a batch across cores.  Must run before jax
    initializes; an explicit XLA_FLAGS always wins."""
    ncpu = os.cpu_count() or 1
    if "XLA_FLAGS" not in os.environ and ncpu > 1:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={min(ncpu, 8)}"


_default_xla_flags()  # before any (transitive) jax import

import numpy as np  # noqa: E402

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec  # noqa: E402
from repro.core.knobs import get_space  # noqa: E402
from repro.core.simulator import run_simulation_batch  # noqa: E402
from repro.core.workloads import make_workload  # noqa: E402

from .common import claim, print_claims, save  # noqa: E402


def _check_equivalence(scale: float) -> bool:
    """Batch results must equal matched single-config runs, every engine."""
    wl = make_workload("gups", "8GiB-hot", threads=8, scale=scale, seed=3)
    rng = np.random.default_rng(5)
    for engine in ("hemem", "hmsdk", "memtis", "static", "oracle"):
        if engine in ("hemem", "hmsdk", "memtis"):
            cfgs = [get_space(engine).default_config(),
                    get_space(engine).sample(rng)]
        else:
            cfgs = [{}, {}]
        batch = run_simulation_batch(wl, engine, cfgs, "pmem-large", seeds=7)
        for cfg, b in zip(cfgs, batch):
            s = run_simulation_batch(wl, engine, [cfg], "pmem-large",
                                     seeds=7)[0]
            if b.total_s != s.total_s or \
                    not np.array_equal(b.epoch_wall_ms, s.epoch_wall_ms):
                return False
    return True


def _hemem_batch(n_configs: int, seed: int = 5):
    space = get_space("hemem")
    rng = np.random.default_rng(seed)
    return [space.default_config()] + [space.sample(rng)
                                       for _ in range(n_configs - 1)]


def _time_pair(wl, cfgs, reps: int):
    """Interleaved min wall times of numpy and jax batch evaluations: both
    backends sample the same throttle windows, and min-of-N is robust
    against noisy-neighbour slowdowns on shared hosts."""
    t_np, t_jx = [], []
    for _ in range(reps):
        t0 = time.time()
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=0,
                             sampler="sparse", backend="numpy")
        t_np.append(time.time() - t0)
        t0 = time.time()
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=0,
                             sampler="sparse", backend="jax")
        t_jx.append(time.time() - t0)
    return float(min(t_np)), float(min(t_jx))


def _select_mode_env(mode: str):
    """(kernels FORCE value, exact_select flag) pinning one selection
    implementation."""
    return (None, False) if mode == "quantized" else (mode, True)


def _select_microbench(modes, n_pages: int, reps: int, B: int = 8):
    """Per-call wall-clock (ms) of one jitted migration-plan selection at
    the evaluation's (B, n_pages) shape, per mode.  Reps are interleaved
    across modes (same throttle windows) and min-of-N (robust to noisy
    neighbours), like :func:`_time_pair`."""
    import functools
    import jax
    from repro.core import engine_jax
    engine_jax.have_jax()
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    heat = jnp.asarray(rng.gamma(0.3, 50.0, (B, n_pages)).astype(np.float32))
    p_mask = jnp.asarray(rng.uniform(size=(B, n_pages)) < 0.2)
    d_mask = jnp.asarray(rng.uniform(size=(B, n_pages)) < 0.5)
    k = jnp.asarray(np.full(B, n_pages // 20, np.float32))
    fns = {m: jax.jit(functools.partial(engine_jax.select_top, mode=m))
           for m in modes}
    for fn in fns.values():  # compile outside the timed region
        jax.block_until_ready(fn(p_mask, heat, d_mask, heat, k, k))
    times = {m: [] for m in modes}
    for _ in range(max(reps, 5)):
        for m, fn in fns.items():
            t0 = time.time()
            jax.block_until_ready(fn(p_mask, heat, d_mask, heat, k, k))
            times[m].append(time.time() - t0)
    return {m: float(min(t)) * 1e3 for m, t in times.items()}


def select_ablation(quick: bool = False) -> dict:
    """--select ablation: exact Pallas kernel vs its pure-jnp ref vs the
    log-quantized approximation, on the batch-8 HeMem/GUPS headline —
    selection-only and end-to-end wall clock plus cross-mode parity.
    All modes are timed interleaved rep by rep so they sample the same
    throttle windows (see :func:`_time_pair`)."""
    from repro.kernels import ops as kernel_ops
    cfgs = _hemem_batch(8)
    scale = 0.25
    wl = make_workload("gups", "8GiB-hot", threads=12, scale=scale, seed=0)
    reps = 3 if quick else 6
    modes = ("quantized", "ref", "pallas")
    totals = {}
    compiles = {}
    walls = {m: [] for m in modes}
    old_force = kernel_ops.FORCE

    def _eval(mode):
        force, exact = _select_mode_env(mode)
        kernel_ops.FORCE = force
        return run_simulation_batch(
            wl, "hemem", cfgs, "pmem-large", seeds=0, sampler="sparse",
            backend="jax", exact_select=exact)

    try:
        for mode in modes:  # compile each mode outside the timed region
            t0 = time.time()
            totals[mode] = np.array([r.total_s for r in _eval(mode)])
            compiles[mode] = time.time() - t0
        for _ in range(reps):  # interleaved: same throttle windows
            for mode in modes:
                t0 = time.time()
                _eval(mode)
                walls[mode].append(time.time() - t0)
        sel_ms = _select_microbench(modes, wl.n_pages, reps)
    finally:
        kernel_ops.FORCE = old_force
    rows = {mode: {"wall_s": float(min(walls[mode])),
                   "compile_s": float(compiles[mode]),
                   "select_ms_per_call": sel_ms[mode]}
            for mode in modes}
    for mode in modes:
        print(f"  select={mode:9s}: eval {rows[mode]['wall_s']:.3f}s | "
              f"selection {rows[mode]['select_ms_per_call']:.2f} ms/call",
              flush=True)
    exact_bitwise = bool(np.array_equal(totals["ref"], totals["pallas"]))
    quant_rel = float(np.max(np.abs(totals["quantized"] - totals["ref"])
                             / totals["ref"]))
    overhead = rows["ref"]["wall_s"] / rows["quantized"]["wall_s"] - 1.0
    out = {
        "scale": scale, "n_pages": wl.n_pages, "batch": len(cfgs),
        "modes": rows,
        "exact_pallas_vs_ref_bitwise": exact_bitwise,
        "quantized_vs_exact_total_s_rel": quant_rel,
        "exact_end_to_end_overhead_pct": overhead * 100.0,
        "claims": [
            claim("exact selection: pallas and ref dispatch agree bitwise "
                  "(batch-8 HeMem end-to-end)", exact_bitwise,
                  f"total_s identical across {len(cfgs)} configs"),
            claim("exact selection end-to-end cost is recorded and small",
                  overhead < 0.25,
                  f"exact(ref) is {overhead * 100:+.1f}% vs quantized; "
                  f"selection {rows['ref']['select_ms_per_call']:.2f} vs "
                  f"{rows['quantized']['select_ms_per_call']:.2f} ms/call "
                  f"(pallas-interpret: "
                  f"{rows['pallas']['select_ms_per_call']:.2f})"),
        ],
    }
    print_claims(out["claims"])
    return out


def backend_bench(quick: bool = False) -> dict:
    """Numpy-vs-jax wall clock for a batch-8 HeMem evaluation on GUPS,
    recorded in BENCH_backend.json (acceptance target: >= 3x post-compile).
    """
    cfgs = _hemem_batch(8)
    reps = 3 if quick else 6
    scales = (0.25,) if quick else (0.25, 0.5)
    rows = []
    for scale in scales:
        wl = make_workload("gups", "8GiB-hot", threads=12, scale=scale,
                           seed=0)
        t0 = time.time()
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=0,
                             sampler="sparse", backend="jax")
        t_compile = time.time() - t0
        t_np, t_jax = _time_pair(wl, cfgs, reps)
        rows.append({"scale": scale, "n_pages": wl.n_pages,
                     "batch": len(cfgs),
                     "wall_numpy_s": t_np, "wall_jax_s": t_jax,
                     "jax_compile_s": t_compile,
                     "speedup_x": t_np / t_jax})
        print(f"  GUPS@{scale} hemem batch-{len(cfgs)}: numpy {t_np:.3f}s | "
              f"jax {t_jax:.3f}s (compile {t_compile:.1f}s) | "
              f"{t_np / t_jax:.2f}x", flush=True)

    # CRN sanity: identical configs under crn=True draw identical noise
    wl_s = make_workload("gups", "8GiB-hot", threads=8, scale=0.04, seed=3)
    cfg = get_space("hemem").default_config()
    crn = run_simulation_batch(wl_s, "hemem", [cfg] * 3, "pmem-large",
                               seeds=0, backend="jax", crn=True)
    crn_ok = all(np.array_equal(crn[0].epoch_wall_ms, r.epoch_wall_ms)
                 for r in crn[1:])

    print("selection ablation (--select: pallas | ref | quantized):",
          flush=True)
    ablation = select_ablation(quick=quick)

    best = max(r["speedup_x"] for r in rows)
    out = {
        "engine": "hemem", "workload": "gups:8GiB-hot",
        "sampler": "sparse", "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "evaluations": rows,
        "best_speedup_x": best,
        "crn_bitwise_identical": bool(crn_ok),
        "select_ablation": ablation,
    }
    out["claims"] = [
        claim("jax backend >= 3x over numpy (batch-8 HeMem on GUPS, "
              "post-compile, exact selection)", best >= 3.0,
              f"best {best:.2f}x across scales "
              f"{[r['scale'] for r in rows]}"),
        claim("crn=True draws are bitwise-identical across the batch",
              crn_ok, "epoch walls equal across 3 identical configs"),
    ] + ablation["claims"]
    print_claims(out["claims"])
    save("BENCH_backend", out)
    # the acceptance artifact also lives at the repo root
    root = os.path.join(os.path.dirname(__file__), "..", "BENCH_backend.json")
    with open(root, "w") as f:
        json.dump(out, f, indent=2)
    return out


def smoke() -> dict:
    """CI fail-fast: one jitted HeMem evaluation on CPU + numpy parity."""
    wl = make_workload("gups", "8GiB-hot", threads=8, scale=0.04, seed=3)
    cfgs = _hemem_batch(2)
    t0 = time.time()
    jx = run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=0,
                              backend="jax")
    t_first = time.time() - t0
    npr = run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=0)
    rel = max(abs(a.total_s - b.total_s) / a.total_s
              for a, b in zip(npr, jx))
    ok = rel < 0.25 and all(np.isfinite(r.total_s) for r in jx)
    claims = [claim("jax smoke: jitted HeMem evaluation runs and tracks "
                    "numpy", ok,
                    f"compile+run {t_first:.1f}s, max rel diff {rel:.3f}")]
    print_claims(claims)
    if not ok:
        raise SystemExit("jax backend smoke failed")
    return {"rel": rel, "claims": claims}


def run(quick: bool = False, budget: int = None, batch_size: int = None,
        workers="auto", seed: int = 0, backend: str = "numpy",
        select: str = None) -> dict:
    budget = budget if budget is not None else (12 if quick else 32)
    batch_size = batch_size if batch_size is not None else (4 if quick else 8)
    exact_select = True
    if select is not None and backend != "jax":
        raise SystemExit("--select only applies to --backend jax "
                         "(the numpy reference is always exact)")

    print(f"GUPS/hemem, budget={budget}, batch_size={batch_size}, "
          f"workers={workers}, backend={backend}"
          + (f", select={select}" if select else ""), flush=True)

    out = {}
    if backend == "jax":
        print("backend benchmark (numpy vs compiled jax epoch loop):",
              flush=True)
        out["backend_bench"] = backend_bench(quick=quick)

    if select is not None:
        # pin the selection implementation for the TUNING run only — the
        # backend benchmark + ablation above always measure the default
        # dispatch and all three modes respectively
        force, exact_select = _select_mode_env(select)
        from repro.kernels import ops as kernel_ops
        kernel_ops.FORCE = force

    wspec = WorkloadSpec("gups", "8GiB-hot")

    def _study(sampler, wk, be):
        return Study(ExperimentSpec(
            engine="hemem", workload=wspec, machine="pmem-large",
            options=SimOptions(seed=seed, sampler=sampler, workers=wk,
                               backend=be, exact_select=exact_select)))

    # warm the persistent shard pool (one-time process spinup) so the timed
    # comparison measures steady-state throughput
    from repro.core.simulator import _get_pool, _resolve_workers
    n_workers = _resolve_workers(workers, batch_size)
    if backend == "numpy" and n_workers > 1:
        list(_get_pool(n_workers).map(int, range(n_workers)))
    if backend == "jax":
        # compile the epoch loops used by the tuning run (B=1 for the
        # default evaluation, B=batch_size + any partial final round)
        # outside the timed region, mirroring the pool warm-up above
        warm = _study("sparse", 1, "jax")
        cfg = get_space("hemem").default_config()
        for b in {1, batch_size, budget % batch_size or batch_size}:
            warm.run(configs=[cfg] * b)

    t0 = time.time()
    seq = _study("elementwise", 1, "numpy").tune(budget=budget, seed=seed)
    t_seq = time.time() - t0
    print(f"  sequential SMAC: {t_seq:6.2f}s  best={seq.best_value:8.3f}s  "
          f"improvement={seq.improvement:.2f}x", flush=True)

    # the jax backend parallelizes inside one process (XLA device
    # sharding); process-pool workers only apply to the numpy path
    eff_workers = workers if backend == "numpy" else 1
    t0 = time.time()
    bat = _study("sparse", eff_workers, backend).tune(
        budget=budget, seed=seed, batch_size=batch_size)
    t_bat = time.time() - t0
    speedup = t_seq / t_bat
    parity = abs(bat.best_value - seq.best_value) / seq.best_value
    print(f"  batched  q={batch_size}:   {t_bat:6.2f}s  "
          f"best={bat.best_value:8.3f}s  improvement={bat.improvement:.2f}x",
          flush=True)
    print(f"  speedup {speedup:.2f}x | best_value delta {parity * 100:.2f}%",
          flush=True)

    equiv = _check_equivalence(scale=0.04 if quick else 0.1)

    out.update({
        "budget": budget, "batch_size": batch_size,
        "workers": str(eff_workers), "backend": backend,
        "select": select or ("exact" if backend == "jax" else "n/a"),
        "wall_sequential_s": t_seq, "wall_batched_s": t_bat,
        "speedup_x": speedup,
        "best_sequential_s": seq.best_value, "best_batched_s": bat.best_value,
        "best_value_delta_pct": parity * 100,
        "improvement_sequential_x": seq.improvement,
        "improvement_batched_x": bat.improvement,
    })
    # the jax backend draws different (equal-in-distribution) monitoring
    # noise than the numpy reference, so best-value parity is statistical
    parity_tol = (0.05 if quick else 0.03) + (0.05 if backend == "jax" else 0)
    claims = [
        claim("batch == sequential (matched seeds, every engine)", equiv,
              "run_simulation_batch numerically equals per-config runs"),
        claim("batched tuning matches sequential best_value",
              parity <= parity_tol,
              f"delta {parity * 100:.2f}% at equal budget {budget}"),
        claim("batched tuning is faster than sequential SMAC",
              speedup >= 1.0,
              f"{speedup:.2f}x with {eff_workers} workers / {backend} "
              "backend"),
    ]
    # surface the backend-bench claims (if that section ran) at the top
    # level alongside the tuning claims
    out["claims"] = out.get("backend_bench", {}).get("claims", []) + claims
    print_claims(claims)
    save("batched_tuning", out)
    return out


def _workers_arg(value: str):
    if value == "auto":
        return value
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--workers must be an integer or 'auto', got {value!r}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--budget", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--workers", type=_workers_arg, default="auto",
                   help="process-pool size for batch sharding (int or auto)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                   help="evaluation backend for the batched tuning run; "
                   "'jax' also runs the backend comparison + selection "
                   "ablation and writes BENCH_backend.json")
    p.add_argument("--select", choices=("pallas", "ref", "quantized"),
                   default=None,
                   help="pin the migration-plan selection implementation "
                   "for the jax tuning run (the ablation section always "
                   "measures all three)")
    p.add_argument("--smoke", action="store_true",
                   help="CI fail-fast: one jitted HeMem evaluation only")
    args = p.parse_args()
    if args.smoke:
        smoke()
        return
    run(quick=args.quick, budget=args.budget, batch_size=args.batch_size,
        workers=args.workers, seed=args.seed, backend=args.backend,
        select=args.select)


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
