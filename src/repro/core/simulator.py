"""Epoch-based tiered-memory simulator: the black-box f(θ) the optimizer tunes.

The simulator executes a :class:`~repro.core.workloads.Workload` against a
tiering engine on a :class:`Machine` and returns the workload's execution
time.  It models, per epoch of fixed application work:

* **access cost** — bandwidth-bound and latency-bound components per tier,
  using the Table-3 machine characteristics (asymmetric NVM read/write
  bandwidth, per-tier load latencies, thread-level memory parallelism);
* **migration cost** — migrated bytes consume bandwidth on *both* tiers
  (promotions read from the far tier, demotions write to it), competing with
  application traffic; writes to in-flight pages stall on the write-protect
  barrier (HeMem §3.2);
* **monitoring cost** — PEBS-style sampling interrupts charge CPU time per
  sample (the paper's deployment fix #1 reduced, but did not eliminate, this);
  DAMON's page-table scans are far cheaper per probe;
* **engine cost** — extra kernel time some engines burn (Memtis page
  allocation/splitting, §4.6).

**Batched evaluation** is the primary entry point:
:func:`run_simulation_batch` carries a whole batch of B candidate
configurations through ONE shared workload trace — the engines keep
``(B, n_pages)`` state, and the batch can additionally be sharded over a
process pool (``workers=N``) or, with multi-cell work, scheduled through
one shared shard queue (:func:`run_simulation_cells`, used by
``Study.sweep``).  :func:`run_simulation` itself is the thin ``B=1``
wrapper kept for existing callers.

**Two-backend contract** (``backend=``):

* ``"numpy"`` (default) — the bit-exact reference.  Per-config random
  streams are independent and seeded exactly like the single-config path,
  so ``run_simulation_batch([c1..cB])`` returns the same numbers as B
  sequential :func:`run_simulation` calls with matched seeds and the same
  ``sampler``.
* ``"jax"`` — the compiled fast path: the WHOLE epoch loop (engine
  observe/plan, fused Poisson sampling kernels, tier update and this
  module's access-cost model) jit-compiles into one ``lax.scan`` per
  (engine, workload shape); see :mod:`repro.core.engine_jax`.  Draws are
  counter-based — equal in distribution to the reference but not
  stream-compatible, so cross-backend parity is statistical for the
  sampled engines; migration-plan selection itself is **exact** (the
  top-k selection kernel of :mod:`repro.kernels.select_topk` returns
  bit-identical index sets to the reference's stable sorts;
  ``exact_select=False`` restores the historical log-quantized
  approximation for ablations).  ``crn=True`` additionally shares the
  monitoring noise bitwise across the batch (common random numbers) for
  paired candidate comparisons during tuning; leave it off when
  estimating absolute performance from independent replicas.
  Engines/samplers outside the builtin set (and traces beyond the
  compiled path's page ceiling) fall back to the numpy epoch loop with
  the vmapped jax cost model — a one-line warning records the downgrade.

Scaling: ``workload.scale`` shrinks the page count and access volume while
*time semantics stay real*: effective bandwidth and memory-level parallelism
shrink by the same factor, so per-page access rates, thresholds, periods and
wall-clock times all match the full-size system.  Knobs with page-count
semantics (``cooling_pages``, ring sizes, ``nr_regions``) are scaled when the
engine is instantiated; see :func:`scale_config`.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence

import numpy as np

from ._deprecation import warn_deprecated
from . import engine_jax, spans
from .engine import make_batch_engine
from .knobs import get_space
from .pages import BatchTierState, PAGE_BYTES, migration_rate_pages
from .registry import (BACKENDS, MACHINES as MACHINE_REGISTRY,
                       register_backend, register_machine)
from .workloads import Workload, make_workload

CACHELINE = 64


# ---------------------------------------------------------------------------
# Machines — paper Table 3, plus a TPU-v5e host-offload profile for the
# beyond-paper serving substrate.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Machine:
    name: str
    cores: int
    near_bw_gbs: float          # fast-tier bandwidth (GB/s)
    far_bw_read_gbs: float      # slow-tier read bandwidth (GB/s)
    far_bw_write_gbs: float     # slow-tier write bandwidth (GB/s)
    near_lat_ns: float
    far_lat_ns: float
    sample_us: float            # CPU time per PEBS sample (post-fix #1)
    scan_us: float              # CPU time per DAMON page-table probe
    default_threads: int

    @property
    def far_symmetric(self) -> bool:
        return abs(self.far_bw_read_gbs - self.far_bw_write_gbs) < 1e-9


PMEM_LARGE = Machine("pmem-large", cores=24, near_bw_gbs=138.0,
                     far_bw_read_gbs=7.45, far_bw_write_gbs=2.25,
                     near_lat_ns=80.0, far_lat_ns=200.0,
                     sample_us=0.8, scan_us=0.05, default_threads=12)
PMEM_SMALL = Machine("pmem-small", cores=16, near_bw_gbs=46.0,
                     far_bw_read_gbs=6.8, far_bw_write_gbs=1.85,
                     near_lat_ns=80.0, far_lat_ns=200.0,
                     sample_us=0.8, scan_us=0.05, default_threads=4)
NUMA = Machine("numa", cores=20, near_bw_gbs=56.0,
               far_bw_read_gbs=36.0, far_bw_write_gbs=36.0,
               near_lat_ns=95.0, far_lat_ns=145.0,
               sample_us=0.8, scan_us=0.05, default_threads=12)
#: TPU v5e chip with host-DRAM offload over PCIe: the two-tier system the
#: production TieredKVCache manages.  "Threads" = the single decode stream;
#: MLP comes from DMA queue depth.
TPU_V5E_HOST = Machine("tpu-v5e-host", cores=1, near_bw_gbs=819.0,
                       far_bw_read_gbs=16.0, far_bw_write_gbs=16.0,
                       near_lat_ns=600.0, far_lat_ns=2500.0,
                       sample_us=0.05, scan_us=0.05, default_threads=1)

for _m in (PMEM_LARGE, PMEM_SMALL, NUMA, TPU_V5E_HOST):
    register_machine(_m)

#: machine profiles by name — now the shared registry (dict-like view)
MACHINES = MACHINE_REGISTRY


def get_machine(name: str) -> Machine:
    """Look up a registered machine profile (did-you-mean on unknown names)."""
    return MACHINE_REGISTRY.get(name)


def _as_machine(machine: "Machine | str") -> Machine:
    """Resolve a machine argument; ad-hoc Machine instances are registered on
    first use so specs referencing them by name stay replayable.  Reusing a
    registered name for a *different* profile keeps the instance for the
    current call but does NOT re-register it — replay-by-name resolves to
    the first profile; use ``register_machine(..., overwrite=True)`` (or a
    fresh name) to make a new profile the replay target."""
    if isinstance(machine, str):
        return get_machine(machine)
    if machine.name not in MACHINE_REGISTRY:
        register_machine(machine)
    return machine


# ---------------------------------------------------------------------------
# Config scaling (page-count-semantics knobs only; see module docstring).
# ---------------------------------------------------------------------------
_PAGE_SEMANTIC_KNOBS = {
    "hemem": ("cooling_pages", "hot_ring_reqs_threshold",
              "cold_ring_reqs_threshold"),
    "kv-hemem": ("cooling_pages", "hot_ring_reqs_threshold",
                 "cold_ring_reqs_threshold"),
    "hmsdk": ("nr_regions",),
    "memtis": (),
    "static": (),
    "oracle": (),
}


def scale_config(engine_name: str, config: Mapping[str, Any],
                 scale: float) -> Dict[str, Any]:
    out = dict(config)
    for k in _PAGE_SEMANTIC_KNOBS.get(engine_name, ()):
        if k in out:
            out[k] = max(1, int(round(out[k] * scale)))
    return out


# ---------------------------------------------------------------------------
# Simulation result
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SimResult:
    workload: str
    engine: str
    machine: str
    config: Dict[str, Any]
    total_s: float
    epoch_wall_ms: np.ndarray       # per-epoch wall time
    cum_migrations: np.ndarray      # cumulative migrated pages over epochs
    fast_hit_rate: np.ndarray       # fraction of accesses served by fast tier
    sampling_ms: np.ndarray
    stall_ms: np.ndarray
    heatmap: Optional[np.ndarray] = None   # (epochs, heat_bins) access heat
    placement: Optional[np.ndarray] = None  # (epochs, heat_bins) frac in fast

    @property
    def total_migrations(self) -> int:
        return int(self.cum_migrations[-1]) if len(self.cum_migrations) else 0


# ---------------------------------------------------------------------------
# Access-cost math — one scalar-config definition, reused by the vectorized
# numpy path and (vmapped) by the optional JAX backend.
# ---------------------------------------------------------------------------
def _access_cost(xp, acc_f, acc_s, reads_s, writes_s, promote_bytes,
                 demote_bytes, w_mig, est_wall_ms, samples, engine_ms,
                 const: Mapping[str, float]):
    """Per-config epoch wall-time model.  ``xp`` is numpy or jax.numpy; all
    per-config inputs are scalars (vmap/broadcast supplies the batch axis)."""
    bytes_f = acc_f * CACHELINE
    # bandwidth-bound terms (migration traffic shares the devices)
    t_near = (bytes_f + promote_bytes + demote_bytes) / const["near_bw"]
    t_far = ((reads_s * CACHELINE + promote_bytes) / const["far_bw_r"]
             + (writes_s * CACHELINE + demote_bytes) / const["far_bw_w"])
    # latency-bound term
    t_lat = (acc_f * const["near_lat_s"] + acc_s * const["far_lat_s"]) \
        / const["eff_par"]
    t_mem = xp.maximum(xp.maximum(t_near, t_far), t_lat)

    # write-protect stalls: HeMem write-protects in-flight pages, so only
    # the writes that land *during* a page's copy window stall, each for
    # half the copy time on average.  Expected stalled writes per page =
    # page_write_rate x copy_duration; a stalled thread cannot overlap, so
    # the app-level cost divides by thread count (scale-adjusted).
    page_copy_s = const["page_copy_s"]
    epoch_s_est = xp.maximum(est_wall_ms * 1e-3, page_copy_s)
    frac_in_flight = xp.minimum(page_copy_s / epoch_s_est, 1.0)
    stall_s = xp.where(
        (promote_bytes + demote_bytes) > 0,
        w_mig * frac_in_flight * (page_copy_s / 2.0) / const["stall_denom"],
        0.0)

    sampling_s = samples * const["probe_us"] * 1e-6 / const["threads_floor"]
    engine_s = engine_ms * 1e-3
    wall_ms = (xp.maximum(const["compute_ms"], t_mem * 1e3)
               + stall_s * 1e3 + sampling_s * 1e3 + engine_s * 1e3)
    hit_rate = acc_f / xp.maximum(acc_f + acc_s, 1e-12)
    return wall_ms, stall_s, sampling_s, hit_rate


_JAX_COST = None


def _jax_cost_fn():
    """Lazily build the jitted+vmapped JAX version of the access-cost math."""
    global _JAX_COST
    if _JAX_COST is None:
        try:
            import jax
            import jax.numpy as jnp
        except ImportError as e:  # pragma: no cover - env without jax
            raise RuntimeError(
                "backend='jax' requires jax; install it or use the default "
                "numpy backend") from e

        def scalar(acc_f, acc_s, reads_s, writes_s, pb, db, w_mig, est,
                   samples, engine_ms, const):
            return _access_cost(jnp, acc_f, acc_s, reads_s, writes_s, pb, db,
                                w_mig, est, samples, engine_ms, const)

        _JAX_COST = jax.jit(jax.vmap(scalar, in_axes=(0,) * 10 + (None,)))
    return _JAX_COST


def _numpy_cost_fn():
    return functools.partial(_access_cost, np)


# backends are zero-arg factories returning the vectorized cost callable;
# the numpy path broadcasts, the jax path jit+vmaps the same scalar math
register_backend("numpy", _numpy_cost_fn)
register_backend("jax", _jax_cost_fn)


# ---------------------------------------------------------------------------
# Core loop (batched)
# ---------------------------------------------------------------------------
def _epoch_consts(workload: Workload, engine_name: str, machine: Machine,
                  page_bytes: int) -> Dict[str, float]:
    """The scalar constants of the access-cost model (shared by both
    backends).  Effective parallel resources shrink with ``scale`` so time
    semantics stay real; see the module docstring."""
    threads = workload.threads
    scale = workload.scale
    eff_bw = scale
    eff_par = threads * workload.mlp * scale
    near_bw = machine.near_bw_gbs * 1e9 * eff_bw
    far_bw_r = machine.far_bw_read_gbs * 1e9 * eff_bw
    far_bw_w = machine.far_bw_write_gbs * 1e9 * eff_bw
    # probe-cost knob: engines that sample pay per-sample CPU; DAMON pays per
    # scan probe (engine reports its probes via samples_last_epoch).
    probe_us = machine.scan_us if engine_name == "hmsdk" else machine.sample_us
    return {
        "near_bw": near_bw, "far_bw_r": far_bw_r, "far_bw_w": far_bw_w,
        "near_lat_s": machine.near_lat_ns * 1e-9,
        "far_lat_s": machine.far_lat_ns * 1e-9,
        "eff_par": eff_par,
        "page_copy_s": page_bytes / max(min(far_bw_r, near_bw), 1.0),
        "stall_denom": max(threads * scale, 1e-9),
        "probe_us": probe_us, "threads_floor": max(threads, 1),
        "compute_ms": workload.compute_ms,
    }


def _fast_capacity(workload: Workload, fast_slow_ratio: float,
                   fast_capacity_pages: Optional[int]) -> int:
    if fast_capacity_pages is not None:
        return int(fast_capacity_pages)
    return max(1, int(round(workload.n_pages / (1.0 + fast_slow_ratio))))


def _run_batch_jax(workload: Workload, engine_name: str,
                   configs: Sequence[Mapping[str, Any]], machine: Machine,
                   fast_slow_ratio: float, seeds, sampler: str,
                   record_heatmap: bool, heat_bins: int,
                   fast_capacity_pages: Optional[int], crn: bool,
                   batch_offset: int,
                   exact_select: bool = True) -> List[SimResult]:
    """The compiled fast path: one ``lax.scan`` over epochs per batch (see
    :mod:`repro.core.engine_jax` for the backend contract)."""
    with spans.span("repro.sim.run", B=len(configs),
                    round=spans.current_round()):
        scale = workload.scale
        fast_cap = _fast_capacity(workload, fast_slow_ratio,
                                  fast_capacity_pages)
        sim_cfgs = [scale_config(engine_name, c, scale) for c in configs]
        const = _epoch_consts(workload, engine_name, machine, PAGE_BYTES)
        out = engine_jax.run_epochs(
            workload, engine_name, sim_cfgs, const, fast_cap, PAGE_BYTES,
            seeds, sampler, crn=crn, batch_offset=batch_offset,
            record_placement=record_heatmap, exact_select=exact_select)
        with spans.span("repro.sim.results"):
            return _batch_results(workload, engine_name, configs, machine,
                                  out, record_heatmap, heat_bins)


def _batch_results(workload: Workload, engine_name: str,
                   configs: Sequence[Mapping[str, Any]], machine: Machine,
                   out: Dict[str, np.ndarray], record_heatmap: bool,
                   heat_bins: int) -> List[SimResult]:
    """One ``SimResult`` per config from :func:`engine_jax.run_epochs`'
    per-epoch arrays."""
    B = len(configs)
    n = workload.n_pages
    wall = np.asarray(out["wall_ms"], dtype=np.float64)
    cum_mig = np.asarray(out["cum_migrations"], dtype=np.float64)
    hit_rate = np.asarray(out["hit_rate"], dtype=np.float64)
    sampling_ms = np.asarray(out["sampling_ms"], dtype=np.float64)
    stall_ms = np.asarray(out["stall_ms"], dtype=np.float64)
    n_epochs = workload.n_epochs
    heat = place = None
    if record_heatmap:
        bin_of = np.arange(n) * heat_bins // n
        bin_sizes = np.maximum(np.bincount(bin_of, minlength=heat_bins), 1)
        heat = np.zeros((n_epochs, heat_bins))
        place = np.zeros((B, n_epochs, heat_bins))
        in_fast = np.asarray(out["in_fast"])
        acc_t = (out["trace_reads"] + out["trace_writes"]).astype(np.float64)
        for e in range(n_epochs):
            heat[e] = np.bincount(bin_of, weights=acc_t[e],
                                  minlength=heat_bins)
            for b in range(B):
                place[b, e] = np.bincount(
                    bin_of, weights=in_fast[e, b].astype(np.float64),
                    minlength=heat_bins) / bin_sizes
    return [SimResult(
        workload=workload.key, engine=engine_name, machine=machine.name,
        config=dict(configs[b]), total_s=float(wall[:, b].sum() / 1e3),
        epoch_wall_ms=wall[:, b].copy(), cum_migrations=cum_mig[:, b].copy(),
        fast_hit_rate=hit_rate[:, b].copy(),
        sampling_ms=sampling_ms[:, b].copy(),
        stall_ms=stall_ms[:, b].copy(),
        heatmap=heat if record_heatmap else None,
        placement=place[b] if record_heatmap else None) for b in range(B)]


#: jax-fallback reasons already warned about (one line per distinct cause)
_JAX_FALLBACK_WARNED: set = set()


def _warn_jax_fallback(engine_name: str, sampler: str, n_pages: int) -> None:
    """One-line warning when ``backend="jax"`` silently cannot compile the
    requested combination and the numpy epoch loop runs instead (the
    vmapped jax cost model still applies)."""
    lifted = engine_jax.jax_engines()
    if engine_name not in lifted:
        reason = (f"engine {engine_name!r} has no lifted jax definition "
                  f"(compiled: {lifted}); register one with "
                  f"engine_jax.register_jax_engine to compile it")
    elif sampler not in engine_jax.JAX_SAMPLERS:
        reason = (f"sampler {sampler!r} is not one of the fused builtins "
                  f"{engine_jax.JAX_SAMPLERS}")
    elif n_pages > engine_jax.MAX_PAGES:
        reason = (f"trace has {n_pages} pages, above the compiled path's "
                  f"{engine_jax.MAX_PAGES}-page ceiling")
    else:
        reason = "jax is not installed"
    key = (engine_name, sampler, reason)
    if key in _JAX_FALLBACK_WARNED:
        return
    _JAX_FALLBACK_WARNED.add(key)
    import logging
    logging.getLogger(__name__).warning(
        "backend='jax': %s; falling back to the numpy epoch loop "
        "(vmapped jax cost model only)", reason)


def _run_batch_local(workload: Workload, engine_name: str,
                     configs: Sequence[Mapping[str, Any]],
                     machine: Machine, fast_slow_ratio: float,
                     seeds, sampler: str, record_heatmap: bool,
                     heat_bins: int, fast_capacity_pages: Optional[int],
                     backend: str, crn: bool = False,
                     batch_offset: int = 0,
                     exact_select: bool = True,
                     epoch_stop: Optional[int] = None) -> List[SimResult]:
    if backend == "jax":
        if engine_jax.supports(engine_name, sampler, workload.n_pages):
            # the compiled fast path: engines + samplers + cost model fused
            # into one jitted lax.scan over epochs
            return _run_batch_jax(workload, engine_name, configs, machine,
                                  fast_slow_ratio, seeds, sampler,
                                  record_heatmap, heat_bins,
                                  fast_capacity_pages, crn, batch_offset,
                                  exact_select)
        _warn_jax_fallback(engine_name, sampler, workload.n_pages)
    if crn:
        raise ValueError(
            "crn=True (common random numbers) requires the compiled jax "
            "path (backend='jax', builtin engine/sampler, trace within its "
            "page limit): the numpy engines consume sequential RNG streams "
            "that cannot be shared across configs (got "
            f"backend={backend!r}, engine={engine_name!r}, "
            f"sampler={sampler!r}, n_pages={workload.n_pages})")
    B = len(configs)
    n = workload.n_pages
    scale = workload.scale
    fast_capacity_pages = _fast_capacity(workload, fast_slow_ratio,
                                         fast_capacity_pages)
    tier = BatchTierState(B, n, fast_capacity_pages)
    sim_cfgs = [scale_config(engine_name, c, scale) for c in configs]
    engine = make_batch_engine(engine_name, sim_cfgs, tier, seeds=seeds,
                               sampler=sampler)

    page_bytes = tier.page_bytes
    const = _epoch_consts(workload, engine_name, machine, page_bytes)

    n_epochs = workload.n_epochs if epoch_stop is None \
        else min(int(epoch_stop), workload.n_epochs)
    wall = np.zeros((n_epochs, B))
    cum_mig = np.zeros((n_epochs, B))
    hit_rate = np.zeros((n_epochs, B))
    sampling_ms_a = np.zeros((n_epochs, B))
    stall_ms_a = np.zeros((n_epochs, B))
    heat = np.zeros((n_epochs, heat_bins)) if record_heatmap else None
    place = np.zeros((B, n_epochs, heat_bins)) if record_heatmap else None
    bin_of = (np.arange(n) * heat_bins // n) if record_heatmap else None
    bin_sizes = np.maximum(np.bincount(bin_of, minlength=heat_bins), 1) \
        if record_heatmap else None

    mig_cost_free = engine.zero_cost_migrations
    rates = engine.max_rates_gibs()
    est_wall_ms = np.full(B, workload.epoch_ms)  # running estimate
    total_mig = np.zeros(B)
    # per-config reduction buffers
    acc_f = np.zeros(B)
    reads_s = np.zeros(B)
    writes_s = np.zeros(B)
    w_mig = np.zeros(B)
    n_promote = np.zeros(B)
    n_demote = np.zeros(B)
    cost_fn = BACKENDS.get(backend)()

    for e in range(n_epochs):
        reads, writes = workload.epoch_access(e)
        touched = (reads + writes) > (1.0 / max(n, 1))
        tier.allocate_first_touch(touched)

        engine.observe(reads, writes, est_wall_ms)
        max_pages = migration_rate_pages(rates, est_wall_ms, page_bytes,
                                         scale)
        plans = engine.plan(est_wall_ms, max_pages)
        tier.apply(plans)

        acc = reads + writes
        acc_sum = float(acc.sum())
        # boolean-mask extraction sums, NOT matvecs: the float summation
        # order must match the historical scalar path bit-for-bit so that
        # batch results stay exactly equal to sequential runs
        for b, plan in enumerate(plans):
            in_fast_b = tier.in_fast[b]
            acc_f[b] = float(acc[in_fast_b].sum())
            slow = ~in_fast_b
            reads_s[b] = float(reads[slow].sum())
            writes_s[b] = float(writes[slow].sum())
            n_promote[b] = len(plan.promote)
            n_demote[b] = len(plan.demote)
            total_mig[b] += plan.n_pages
            if plan.n_pages and not mig_cost_free:
                w_mig[b] = float(writes[plan.promote].sum()
                                 + writes[plan.demote].sum())
            else:
                w_mig[b] = 0.0
        cum_mig[e] = total_mig
        acc_s = acc_sum - acc_f
        if mig_cost_free:
            promote_bytes = np.zeros(B)
            demote_bytes = np.zeros(B)
        else:
            promote_bytes = n_promote * page_bytes
            demote_bytes = n_demote * page_bytes

        wall_ms, stall_s, sampling_s, hr = cost_fn(
            acc_f, acc_s, reads_s, writes_s, promote_bytes, demote_bytes,
            w_mig, est_wall_ms, engine.samples_last_epoch,
            engine.overhead_ms_last_epoch, const)
        wall[e] = wall_ms
        est_wall_ms = np.asarray(wall_ms, dtype=np.float64)
        hit_rate[e] = hr
        sampling_ms_a[e] = np.asarray(sampling_s) * 1e3
        stall_ms_a[e] = np.asarray(stall_s) * 1e3

        if record_heatmap:
            heat[e] = np.bincount(bin_of, weights=acc, minlength=heat_bins)
            for b in range(B):
                place[b, e] = (np.bincount(
                    bin_of, weights=tier.in_fast[b].astype(np.float64),
                    minlength=heat_bins) / bin_sizes)

    return [SimResult(
        workload=workload.key, engine=engine_name, machine=machine.name,
        config=dict(configs[b]), total_s=float(wall[:, b].sum() / 1e3),
        epoch_wall_ms=wall[:, b].copy(), cum_migrations=cum_mig[:, b].copy(),
        fast_hit_rate=hit_rate[:, b].copy(),
        sampling_ms=sampling_ms_a[:, b].copy(),
        stall_ms=stall_ms_a[:, b].copy(),
        # the access heatmap comes from the shared trace, so all B results
        # reference one array; placement is per config
        heatmap=heat if record_heatmap else None,
        placement=place[b] if record_heatmap else None) for b in range(B)]


# ---------------------------------------------------------------------------
# Process-pool sharding for batch evaluation
# ---------------------------------------------------------------------------
_POOL = None
_POOL_SIZE = 0


#: the checkout this package runs from (``<checkout>/src/repro/core``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """The XLA persistent-compilation-cache directory: the parent's (see
    :func:`enable_compile_cache`) and the one shipped to worker shards.

    ``JAX_COMPILATION_CACHE_DIR`` overrides; the default is the fixed
    ``<checkout>/.jax_cache``, so successive pools and successive
    processes in one checkout warm-start instead of re-jitting the epoch
    loop.  The path is part of the cache key, so it holds no temp-dir, pid
    or time component."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")
    os.makedirs(d, exist_ok=True)
    return d


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process at
    :func:`compile_cache_dir`; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the variable is set to the
    default, for a later import of jax and for child processes, and an
    already-imported jax is pointed there too.  Call it at a program's
    entry, before the first compilation.  It does not import jax."""
    d = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
        if "jax" in sys.modules:
            sys.modules["jax"].config.update("jax_compilation_cache_dir", d)
    return d


def jax_backend_is_tpu() -> bool:
    """True when JAX's backend in this process is a TPU.  Where
    ``JAX_PLATFORMS`` rules a TPU out, answers without importing jax."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    try:
        import jax  # noqa: F401
    except ImportError:  # pragma: no cover - env without jax
        return False
    from ..kernels.ops import on_tpu
    return on_tpu()


def refuse_children_on_tpu(what: str) -> None:
    """Raise instead of starting child processes that would import JAX
    while this process's backend is a TPU: a chip belongs to one process
    at a time, so the children would fail or hang."""
    if jax_backend_is_tpu():
        raise RuntimeError(
            f"{what} would start child processes that import JAX, but the "
            "JAX backend here is a TPU, which one process holds at a time; "
            "run it in this process (workers=1)")


def _worker_init(cache_dir: str) -> None:
    """Pool initializer: point the worker's (not-yet-imported) jax at the
    shared XLA compile cache.  Runs before any shard work, so the env is in
    place when the worker first imports jax and every compilation it would
    repeat lands as a disk hit instead."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def _get_pool(workers: int):
    global _POOL, _POOL_SIZE
    # a larger warm pool serves smaller requests (e.g. a tuning run's partial
    # final batch) — only grow, never tear down and respawn mid-run
    if _POOL is None or workers > _POOL_SIZE:
        import concurrent.futures
        import multiprocessing as mp
        if _POOL is not None:
            _POOL.shutdown(wait=False, cancel_futures=True)
        # forking a parent whose XLA runtime is already initialized is
        # unsupported (threads are not inherited) and can hang the workers;
        # fall back to spawn once jax has been imported
        use_fork = "fork" in mp.get_all_start_methods() and \
            "jax" not in sys.modules
        ctx = mp.get_context("fork" if use_fork else "spawn")
        _POOL = concurrent.futures.ProcessPoolExecutor(
            max_workers=workers, mp_context=ctx,
            initializer=_worker_init, initargs=(compile_cache_dir(),))
        _POOL_SIZE = workers
    return _POOL


def _discard_pool(pool) -> None:
    """Forget (and shut down) a broken shared pool so the next
    :func:`_get_pool` call builds a fresh one — the tuning executor's
    BrokenProcessPool self-heal path."""
    global _POOL, _POOL_SIZE
    try:
        pool.shutdown(wait=False, cancel_futures=True)
    except Exception:
        pass
    if pool is _POOL:
        _POOL = None
        _POOL_SIZE = 0


def _shard_worker(args):
    (wl_spec, components, engine_name, configs, machine, fast_slow_ratio,
     seeds, sampler, record_heatmap, heat_bins, fast_capacity_pages,
     backend, crn, batch_offset, exact_select) = args
    # spawn-context workers start from a fresh interpreter that only imported
    # this module, so components registered (or overridden) by user code are
    # unknown there; the parent's resolved objects shipped in the payload are
    # authoritative — register them unconditionally so the worker dispatches
    # to exactly what the parent resolved
    from .registry import BACKENDS as _B, ENGINES as _E, SAMPLERS as _S, \
        WORKLOADS as _W
    for reg, name, obj in ((_E, engine_name, components[0]),
                           (_W, wl_spec[0], components[1]),
                           (_S, sampler, components[2]),
                           (_B, backend, components[3])):
        reg.register(name, obj, overwrite=True)
    wl = make_workload(*wl_spec)
    return _run_batch_local(wl, engine_name, configs, machine,
                            fast_slow_ratio, seeds, sampler, record_heatmap,
                            heat_bins, fast_capacity_pages, backend,
                            crn=crn, batch_offset=batch_offset,
                            exact_select=exact_select)


def _resolve_workers(workers, batch: int, backend: str = "numpy") -> int:
    """Worker-process count for a batch.  ``"auto"`` is one per core,
    except for the jax backend on a TPU, where it is 1; an explicit count
    above 1 there raises (:func:`refuse_children_on_tpu`)."""
    auto = workers in ("auto", 0, None)
    if auto:
        workers = os.cpu_count() or 1
    workers = max(1, min(int(workers), batch))
    if workers > 1 and backend == "jax":
        if auto and jax_backend_is_tpu():
            return 1
        refuse_children_on_tpu(f"backend='jax' with workers={workers}")
    return workers


def run_simulation_cells(cells,
                         machine: Machine | str = PMEM_LARGE,
                         fast_slow_ratio: float = 8.0,
                         seeds=0,
                         sampler: str = "sparse",
                         record_heatmap: bool = False,
                         heat_bins: int = 128,
                         fast_capacity_pages: Optional[int] = None,
                         backend: str = "numpy",
                         crn: bool = False,
                         workers: int = 1,
                         exact_select: bool = True) -> List[List[SimResult]]:
    """Evaluate many (workload, engine, config-batch) *cells* through one
    shared work queue.

    ``cells`` is a sequence of ``(workload, engine_name, configs)`` tuples;
    the return value is one ``List[SimResult]`` per cell, in input order.
    With ``workers > 1`` every cell is split into config shards and ALL
    shards across ALL cells are submitted to the process pool at once, so
    the pool stays saturated even when individual cells are smaller than
    the worker count (previously each cell was a sequential barrier).
    Scheduling never changes results — each shard computes exactly what the
    sequential path would (the jax backend keys its counter-based draws by
    the GLOBAL batch index, shipped to each shard as ``batch_offset``).

    ``seeds`` is an int (shared by every config of every cell) or one seed
    sequence per cell (one seed per config).
    """
    machine = _as_machine(machine)
    cells = [(wl, eng, [dict(c) for c in cfgs]) for wl, eng, cfgs in cells]
    n_cells = len(cells)
    if n_cells == 0:
        return []
    if np.ndim(seeds) == 0:
        cell_seeds = [[int(seeds)] * len(cfgs) for _, _, cfgs in cells]
    else:
        rows = list(seeds)
        if any(np.ndim(r) == 0 for r in rows):
            raise ValueError("seeds must be an int or one seed sequence "
                             "per cell (one seed per config); got a flat "
                             "sequence — wrap it per cell")
        cell_seeds = [[int(s) for s in row] for row in rows]
        if len(cell_seeds) != n_cells or any(
                len(row) != len(cells[i][2])
                for i, row in enumerate(cell_seeds)):
            raise ValueError("seeds must be an int or one seed sequence "
                             "per cell (one seed per config)")
    if crn:
        # the CRN contract is per cell: every row shares the CELL's first
        # seed.  Collapsing here (before sharding) keeps the shared stream
        # anchored to the global row 0 even when the batch is split over
        # workers — otherwise a shard would key off ITS first seed and both
        # the bitwise-CRN and sharding-invariance guarantees would break.
        cell_seeds = [[row[0]] * len(row) for row in cell_seeds]
    total = sum(len(cfgs) for _, _, cfgs in cells)
    if total == 0:
        return [[] for _ in range(n_cells)]
    workers = _resolve_workers(workers, total, backend)
    if workers > 1 and backend == "jax":
        # results are identical either way; worker processes share the XLA
        # persistent compile cache (see _worker_init), so only the first
        # pool ever compiles a given shard shape — later workers and later
        # pools warm-start from disk
        import logging
        logging.getLogger(__name__).info(
            "sharding a jax-backend batch over %d worker processes; shards "
            "warm-start from the shared XLA compile cache at %s "
            "(first-ever run per shape still compiles once per worker)",
            workers, compile_cache_dir())
    if workers == 1:
        return [_run_batch_local(wl, eng, cfgs, machine, fast_slow_ratio,
                                 cell_seeds[i], sampler, record_heatmap,
                                 heat_bins, fast_capacity_pages, backend,
                                 crn=crn, exact_select=exact_select)
                for i, (wl, eng, cfgs) in enumerate(cells)]

    from .registry import ENGINES as _ENGINES, SAMPLERS as _SAMPLERS, \
        WORKLOADS as _WORKLOADS
    # one flat shard queue across all cells: shard size targets `workers`
    # equal slices of the TOTAL config count (never crossing a cell), so the
    # pool saturates even when every cell is smaller than the worker count
    shard_size = max(1, -(-total // workers))
    pool = _get_pool(workers)
    futures = []
    for ci, (wl, eng, cfgs) in enumerate(cells):
        wl_spec = (wl.name, wl.input_name, wl.threads, wl.scale, wl.seed)
        # resolved components travel with the shard so spawn-start workers
        # can serve names registered outside this module (see _shard_worker)
        components = (_ENGINES.get(eng), _WORKLOADS.get(wl.name),
                      _SAMPLERS.get(sampler), BACKENDS.get(backend))
        for lo in range(0, len(cfgs), shard_size):
            hi = min(lo + shard_size, len(cfgs))
            fut = pool.submit(_shard_worker, (
                wl_spec, components, eng, cfgs[lo:hi], machine,
                fast_slow_ratio, cell_seeds[ci][lo:hi], sampler,
                record_heatmap, heat_bins, fast_capacity_pages, backend,
                crn, lo, exact_select))
            futures.append((ci, fut))
    out: List[List[SimResult]] = [[] for _ in range(n_cells)]
    for ci, fut in futures:  # shards were submitted in config order per cell
        out[ci].extend(fut.result())
    return out


def run_simulation_batch(workload: Workload, engine_name: str,
                         configs: Sequence[Mapping[str, Any]],
                         machine: Machine | str = PMEM_LARGE,
                         fast_slow_ratio: float = 8.0,
                         seeds=0,
                         sampler: str = "sparse",
                         record_heatmap: bool = False,
                         heat_bins: int = 128,
                         fast_capacity_pages: Optional[int] = None,
                         backend: str = "numpy",
                         crn: bool = False,
                         workers: int = 1,
                         exact_select: bool = True) -> List[SimResult]:
    """Simulate ``workload`` under B candidate configs in one pass.

    The workload trace is generated once and shared; engine state carries a
    leading batch axis.  With the default ``backend="numpy"``, per-config
    RNG streams are seeded from ``seeds`` (an int, applied to every config —
    matching how sequential tuning reuses one scenario seed — or a
    per-config sequence), so results are numerically identical to B
    sequential :func:`run_simulation` calls with matched ``seed`` and
    ``sampler`` — the numpy path is the bit-exact reference.
    ``backend="jax"`` compiles the whole epoch loop (engines + samplers +
    cost model) into one jitted ``lax.scan`` with counter-based monitoring
    draws — equal in distribution, not stream-compatible; see
    :mod:`repro.core.engine_jax`.  Its migration-plan selection is exact
    by default (bit-identical index sets to the reference's stable sorts;
    ``exact_select=False`` restores the log-quantized ablation path).
    ``crn=True`` (jax only) shares the monitoring noise bitwise across
    all B configs (common random numbers) so within-batch comparisons see
    identical noise.

    ``sampler="sparse"`` (default) draws the exact Poisson sampling
    distribution at cost ∝ events; ``"elementwise"`` reproduces the
    historical per-page draws bit-for-bit.  ``workers > 1`` (or ``"auto"``)
    shards the batch over a persistent process pool; sharding never changes
    results, only wall time.
    """
    configs = list(configs)
    B = len(configs)
    if B == 0:
        return []
    if np.ndim(seeds) == 0:
        seeds = [int(seeds)] * B
    seeds = [int(s) for s in seeds]
    if len(seeds) != B:
        raise ValueError("seeds must be an int or one seed per config")
    return run_simulation_cells(
        [(workload, engine_name, configs)], machine, fast_slow_ratio,
        [seeds], sampler, record_heatmap, heat_bins, fast_capacity_pages,
        backend, crn, workers, exact_select)[0]


def run_simulation_segment(workload: Workload, engine_name: str,
                           configs: Sequence[Mapping[str, Any]],
                           machine: Machine | str = PMEM_LARGE,
                           fast_slow_ratio: float = 8.0,
                           seeds=0,
                           sampler: str = "sparse",
                           fast_capacity_pages: Optional[int] = None,
                           backend: str = "numpy",
                           crn: bool = False,
                           batch_offset: int = 0,
                           exact_select: bool = True,
                           epoch_start: int = 0,
                           epoch_stop: Optional[int] = None,
                           carry: Any = None,
                           return_carry: bool = False
                           ) -> Dict[str, Any]:
    """Partial-epoch evaluation — the tune service's checkpoint/restore hook.

    Evaluates epochs ``[epoch_start, epoch_stop)`` of the workload (defaults
    to the full range) and returns ``{"wall_ms": (seg, B) float64 array,
    "carry": <scan-carry pytree or None>}``.  Per-epoch walls are bitwise
    identical to the corresponding rows of a full :func:`run_simulation_batch`
    pass — segmentation is invisible to the numerics.

    ``backend="jax"`` (compiled-path combinations) supports true mid-run
    checkpointing: pass ``return_carry=True`` to get the scan carry back
    (numpy-ified, picklable) and feed it to the next segment via ``carry`` +
    ``epoch_start``.  The numpy reference path has sequential RNG state that
    cannot be checkpointed, so it only supports prefixes
    (``epoch_start=0``): a partial-budget re-evaluation re-runs from epoch 0
    to ``epoch_stop`` — exact (the prefix of a full run is bit-identical),
    just without the resume shortcut.
    """
    configs = [dict(c) for c in configs]
    B = len(configs)
    machine = _as_machine(machine)
    if np.ndim(seeds) == 0:
        seeds = [int(seeds)] * B
    seeds = [int(s) for s in seeds]
    if len(seeds) != B:
        raise ValueError("seeds must be an int or one seed per config")
    if crn:
        seeds = [seeds[0]] * len(seeds)
    use_jax = backend == "jax" and engine_jax.supports(
        engine_name, sampler, workload.n_pages)
    if backend == "jax" and not use_jax:
        _warn_jax_fallback(engine_name, sampler, workload.n_pages)
    if use_jax:
        fast_cap = _fast_capacity(workload, fast_slow_ratio,
                                  fast_capacity_pages)
        sim_cfgs = [scale_config(engine_name, c, workload.scale)
                    for c in configs]
        const = _epoch_consts(workload, engine_name, machine, PAGE_BYTES)
        out = engine_jax.run_epochs(
            workload, engine_name, sim_cfgs, const, fast_cap, PAGE_BYTES,
            seeds, sampler, crn=crn, batch_offset=batch_offset,
            exact_select=exact_select, epoch_start=epoch_start,
            epoch_stop=epoch_stop, carry=carry, return_carry=return_carry)
        # the materialized segment trace rides along (compiled path only):
        # the online tuner's sampled-histogram drift detector consumes it
        # without regenerating the procedural workload epochs
        return {"wall_ms": np.asarray(out["wall_ms"], dtype=np.float64),
                "carry": out.get("carry"),
                "trace_reads": out.get("trace_reads"),
                "trace_writes": out.get("trace_writes")}
    if crn:
        raise ValueError(
            "crn=True requires the compiled jax path; see run_simulation_batch")
    if epoch_start != 0 or carry is not None or return_carry:
        raise ValueError(
            "the numpy epoch loop has sequential RNG state and cannot be "
            "checkpointed mid-run: only prefix segments (epoch_start=0, no "
            "carry) are supported; use backend='jax' for resumable trials")
    results = _run_batch_local(
        workload, engine_name, configs, machine, fast_slow_ratio, seeds,
        sampler, False, 128, fast_capacity_pages, backend,
        batch_offset=batch_offset, exact_select=exact_select,
        epoch_stop=epoch_stop)
    wall = np.stack([np.asarray(r.epoch_wall_ms, dtype=np.float64)
                     for r in results], axis=1)
    return {"wall_ms": wall, "carry": None}


def run_simulation(workload: Workload, engine_name: str,
                   config: Optional[Mapping[str, Any]] = None,
                   machine: Machine | str = PMEM_LARGE,
                   fast_slow_ratio: float = 8.0,
                   seed: int = 0,
                   record_heatmap: bool = False,
                   heat_bins: int = 128,
                   fast_capacity_pages: Optional[int] = None,
                   sampler: str = "elementwise") -> SimResult:
    """Deprecated ``B=1`` wrapper over :func:`run_simulation_batch`.

    Use :class:`repro.core.study.Study` (``Study(spec).run()``) instead.
    ``fast_slow_ratio`` r sets fast-tier capacity = RSS/(1+r) (the
    paper's "1:r memory size ratio"; default 1:8, §4.1).
    """
    warn_deprecated("repro.core.simulator.run_simulation",
                    "Study(ExperimentSpec(...)).run()")
    machine = _as_machine(machine)
    if config is None:
        config = get_space(engine_name).default_config() \
            if engine_name in ("hemem", "hmsdk", "memtis") else {}
    return _run_batch_local(workload, engine_name, [config], machine,
                            fast_slow_ratio, [seed], sampler, record_heatmap,
                            heat_bins, fast_capacity_pages, "numpy")[0]


# ---------------------------------------------------------------------------
# f(θ) for the tuner — deprecated shims over the typed Study API.
# ---------------------------------------------------------------------------
def _legacy_study(engine_name: str, workload_name: str, input_name: str,
                  machine: "Machine | str", threads: Optional[int],
                  scale: float, fast_slow_ratio: float, seed: int,
                  sampler: str, workers="auto-off", backend: str = "numpy"):
    """Build the Study equivalent of the historical loose-kwargs call."""
    from .specs import EngineSpec, ExperimentSpec, SimOptions, WorkloadSpec
    from .study import Study
    machine = _as_machine(machine)
    spec = ExperimentSpec(
        engine=EngineSpec(engine_name),
        workload=WorkloadSpec(workload_name, input_name, threads=threads,
                              scale=scale),
        machine=machine.name, fast_slow_ratio=fast_slow_ratio,
        options=SimOptions(seed=seed, sampler=sampler,
                           workers=1 if workers == "auto-off" else workers,
                           backend=backend))
    # pass the resolved Machine through: an ad-hoc instance whose name
    # collides with a registered profile must win, as it did pre-shim
    return Study(spec, machine=machine)


def evaluate(engine_name: str, config: Mapping[str, Any], workload_name: str,
             input_name: str = "", machine: Machine | str = PMEM_LARGE,
             threads: Optional[int] = None, scale: float = 0.25,
             fast_slow_ratio: float = 8.0, seed: int = 0,
             sampler: str = "elementwise") -> float:
    """Execution time (seconds) of one workload run — the objective of §3.

    Deprecated: use ``Study(ExperimentSpec(...)).run().total_s``.
    """
    warn_deprecated("repro.core.simulator.evaluate",
                    "Study(ExperimentSpec(...)).run().total_s")
    study = _legacy_study(engine_name, workload_name, input_name, machine,
                          threads, scale, fast_slow_ratio, seed, sampler)
    if config is None:
        return study.run().total_s
    return study.run(configs=[config])[0].total_s


def evaluate_batch(engine_name: str, configs: Sequence[Mapping[str, Any]],
                   workload_name: str, input_name: str = "",
                   machine: Machine | str = PMEM_LARGE,
                   threads: Optional[int] = None, scale: float = 0.25,
                   fast_slow_ratio: float = 8.0, seed: int = 0,
                   sampler: str = "sparse", workers: int = 1,
                   backend: str = "numpy") -> List[float]:
    """Batched objective: execution times of all B candidate configs.

    Deprecated: use ``Study(ExperimentSpec(...)).run(configs=...)``.
    """
    warn_deprecated("repro.core.simulator.evaluate_batch",
                    "Study(ExperimentSpec(...)).run(configs=...)")
    study = _legacy_study(engine_name, workload_name, input_name, machine,
                          threads, scale, fast_slow_ratio, seed, sampler,
                          workers=workers, backend=backend)
    return [r.total_s for r in study.run(configs=configs)]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A fully-specified tuning target: workload × input × machine × setting.

    Deprecated: :class:`repro.core.specs.ExperimentSpec` composes the same
    information as typed sub-specs (plus a :class:`~repro.core.specs.
    SimOptions` for evaluation-mode options) and round-trips through JSON.
    """
    workload: str
    input_name: str = ""
    machine: str = "pmem-large"
    threads: Optional[int] = None
    scale: float = 0.25
    fast_slow_ratio: float = 8.0
    seed: int = 0

    def __post_init__(self):
        warn_deprecated("repro.core.simulator.Scenario",
                        "repro.core.specs.ExperimentSpec", stacklevel=4)

    def _study(self, engine_name: str, sampler: str = "elementwise",
               workers: int = 1, backend: str = "numpy"):
        return _legacy_study(engine_name, self.workload, self.input_name,
                             self.machine, self.threads, self.scale,
                             self.fast_slow_ratio, self.seed, sampler,
                             workers=workers, backend=backend)

    def objective(self, engine_name: str):
        study = self._study(engine_name)

        def f(config: Mapping[str, Any]) -> float:
            return study.run(configs=[config])[0].total_s
        return f

    def objective_batch(self, engine_name: str, sampler: str = "sparse",
                        workers: int = 1, backend: str = "numpy"):
        study = self._study(engine_name, sampler=sampler, workers=workers,
                            backend=backend)

        def f(configs: Sequence[Mapping[str, Any]]) -> List[float]:
            return [r.total_s for r in study.run(configs=configs)]
        return f

    @property
    def key(self) -> str:
        inp = f":{self.input_name}" if self.input_name else ""
        return f"{self.workload}{inp}@{self.machine}"
