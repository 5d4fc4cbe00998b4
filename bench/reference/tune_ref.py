"""Plain reference of one tuning evaluation: HeMem on GUPS, simulated.

An evaluation runs a candidate HeMem configuration over the GUPS access
trace for the workload's epochs and returns the simulated run time
(``total_s``).  This module builds the trace from the configuration file's
published sizes and the simulation seed, and runs the epoch loop in numpy:
first-touch allocation into the fast tier, HeMem's monitoring and plan
(:mod:`hemem`), the tier update, and the access-cost model of the paper's
machine (bandwidth-, latency- and compute-bound terms, write-protect stalls,
sampling cost).  It imports nothing of the system under test.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np

from . import hemem
from .hemem import F32, FP32, Precision

CACHELINE = 64
#: accesses per second one thread issues at fast-tier speed
BASE_RATE_PER_THREAD = 40e6
#: knobs counted in pages, which scale with the simulated trace
PAGE_COUNT_KNOBS = ("cooling_pages", "hot_ring_reqs_threshold",
                    "cold_ring_reqs_threshold")


def gups_trace(cfg: Mapping, seed: int):
    """Per-epoch (reads, writes) of GUPS with a moving hot set: a
    ``hot_gib / span_gib`` share of the pages, scattered over the address
    space, takes 90 % of the updates and moves once, half way through."""
    n, E = cfg["n_pages"], cfg["n_epochs"]
    rng = np.random.default_rng(seed + 17)
    n_hot = max(8, int(n * cfg["hot_gib"] / cfg["span_gib"]))
    hot1 = rng.choice(n, size=n_hot, replace=False)
    hot2 = rng.choice(n, size=n_hot, replace=False)
    A = cfg["threads"] * BASE_RATE_PER_THREAD * (cfg["epoch_ms"] / 1e3) \
        * cfg["scale"]
    base = np.full(n, cfg["cold_share"] / n)
    w1 = base.copy()
    w1[hot1] += (1.0 - cfg["cold_share"]) / n_hot
    w2 = base.copy()
    w2[hot2] += (1.0 - cfg["cold_share"]) / n_hot
    half = (0.5 * A * w1).astype(F32), (0.5 * A * w2).astype(F32)
    return [half[0] if e < E // 2 else half[1] for e in range(E)]


def cost_consts(cfg: Mapping) -> Dict[str, F32]:
    m, s = cfg["machine"], cfg["scale"]
    threads = cfg["threads"]
    near = m["dram_gbs"] * 1e9 * s
    far_r = m["pmem_read_gbs"] * 1e9 * s
    far_w = m["pmem_write_gbs"] * 1e9 * s
    c = {"near_bw": near, "far_bw_r": far_r, "far_bw_w": far_w,
         "near_lat_s": m["dram_lat_ns"] * 1e-9,
         "far_lat_s": m["pmem_lat_ns"] * 1e-9,
         "eff_par": threads * cfg["mlp"] * s,
         "page_copy_s": cfg["page_bytes"] / max(min(far_r, near), 1.0),
         "stall_denom": max(threads * s, 1e-9),
         "probe_us": m["sample_us"], "threads_floor": max(threads, 1),
         "compute_ms": cfg["compute_ms"]}
    return {k: F32(v) for k, v in c.items()}


def epoch_wall(c, acc_f, acc_s, reads_s, writes_s, pb, db, w_mig, est,
               samples, P: Precision = FP32):
    """Simulated wall (ms) of one epoch per row."""
    t_near = P((P(acc_f * F32(CACHELINE)) + pb + db) / c["near_bw"])
    t_far = P(P((reads_s * F32(CACHELINE) + pb) / c["far_bw_r"])
              + P((writes_s * F32(CACHELINE) + db) / c["far_bw_w"]))
    t_lat = P((acc_f * c["near_lat_s"] + acc_s * c["far_lat_s"])
              / c["eff_par"])
    t_mem = np.maximum(np.maximum(t_near, t_far), t_lat)
    copy_s = c["page_copy_s"]
    epoch_s = np.maximum(est * F32(1e-3), copy_s)
    in_flight = np.minimum(copy_s / epoch_s, F32(1.0))
    stall_s = np.where((pb + db) > 0, P(w_mig * in_flight * (copy_s / F32(2))
                                        / c["stall_denom"]), F32(0))
    sampling_s = P(samples * c["probe_us"] * F32(1e-6) / c["threads_floor"])
    return P(np.maximum(c["compute_ms"], t_mem * F32(1e3))
             + stall_s * F32(1e3) + sampling_s * F32(1e3))


def simulate(cfg: Mapping, configs: Sequence[Mapping], seed: int,
             rows: Sequence[int], P: Precision = FP32,
             trace=None) -> np.ndarray:
    """``total_s`` of each candidate: ``configs[i]`` evaluated as row
    ``rows[i]`` of a batch under simulation seed ``seed``."""
    trace = gups_trace(cfg, seed) if trace is None else trace
    n = cfg["n_pages"]
    B = len(configs)
    fast_cap = max(1, int(round(n / (1.0 + cfg["fast_slow_ratio"]))))
    page_bytes = F32(cfg["page_bytes"])
    c = cost_consts(cfg)
    scaled = [dict(cand, **{k: max(1, int(round(cand[k] * cfg["scale"])))
                            for k in PAGE_COUNT_KNOBS}) for cand in configs]
    kv = hemem.knobs(scaled, n)
    keys = hemem.row_keys(seed, rows)
    st = hemem.init_state(B, n)
    in_fast = np.zeros((B, n), bool)
    allocated = np.zeros(n, bool)
    est = np.full(B, F32(cfg["epoch_ms"]), F32)
    floor = F32(1.0 / n)
    total = np.zeros(B)
    for e, reads in enumerate(trace):
        writes = reads
        acc = reads + writes
        new = (acc > floor) & ~allocated
        room = fast_cap - in_fast.sum(axis=1)
        rank = np.cumsum(new)
        in_fast = in_fast | (new[None, :] & (rank[None, :] <= room[:, None]))
        allocated = allocated | new
        sr = hemem.monitor(keys, e, hemem.SITE_READ, reads, kv["sp"], P)
        sw = hemem.monitor(keys, e, hemem.SITE_WRITE, writes, kv["wsp"], P)
        st, samples = hemem.observe(st, kv, sr, sw, P)
        max_pages = np.floor(kv["rate"] * F32(2 ** 30) * (est / F32(1e3))
                             / page_bytes * F32(cfg["scale"]))
        st, pm, dm = hemem.plan(st, kv, in_fast, allocated, est, max_pages,
                                fast_cap, page_bytes, P)
        n_prom = pm.sum(axis=1).astype(F32)
        n_dem = dm.sum(axis=1).astype(F32)
        in_fast = (in_fast & ~dm) | pm
        reads_f = np.where(in_fast, reads, F32(0)).sum(axis=1, dtype=F32)
        acc_f = P(reads_f + reads_f)
        acc_s = P(acc.sum(dtype=F32) - acc_f)
        reads_s = P(reads.sum(dtype=F32) - reads_f)
        w_mig = np.where(pm | dm, writes, F32(0)).sum(axis=1, dtype=F32)
        est = epoch_wall(c, acc_f, acc_s, reads_s, reads_s,
                         n_prom * page_bytes, n_dem * page_bytes, w_mig,
                         est, samples, P)
        total += est.astype(np.float64)
    return total / 1e3


def check_sample(cfg: Mapping, evals: List[Mapping], seed: int,
                 P: Precision = FP32, chunk: int = 8) -> np.ndarray:
    """Reference ``total_s`` of each logged evaluation (``config``,
    ``row``), in chunks of rows that share one trace."""
    trace = gups_trace(cfg, seed)
    out = []
    for i in range(0, len(evals), chunk):
        part = evals[i:i + chunk]
        out.append(simulate(cfg, [ev["config"] for ev in part], seed,
                            [ev["row"] for ev in part], P, trace))
    return np.concatenate(out) if out else np.zeros(0)
