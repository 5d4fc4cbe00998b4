"""Tune the TieredKVCache knobs against the REAL serving path through the
typed Study API: ``Study.tune(objective=...)`` drives the Table-2 HeMem
knob space while the objective replays an embedded, JSON-round-trippable
:class:`~repro.core.traffic.TrafficSpec` through the compiled decode loop
(fused append + paged-attention + read-recording jit) and scores
p99 latency / recall.

    PYTHONPATH=src python examples/tune_serving.py [--budget 20]
"""
import argparse
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax.numpy as jnp

from repro.core import ExperimentSpec, Study
from repro.core.knobs import HEMEM_SPACE
from repro.core.tiered_kv import KVSpec, TieredKVCache
from repro.core.traffic import TrafficSpec, replay_schedule

SPEC = KVSpec(n_layers=2, kv_heads=2, head_dim=16, page_tokens=4,
              dtype=jnp.float32)

#: modeled serving machine: HBM vs PCIe-host bandwidth + per-step compute.
#: Latency is modeled at the paper's production page granule (2 MiB), not
#: the miniature spec's page size, so residency actually moves the tail: a
#: non-resident page costs ~65us of PCIe reads vs ~2.6us from HBM.
NEAR_GBS, FAR_GBS, COMPUTE_MS = 800.0, 32.0, 0.2
MODEL_PAGE_BYTES = 2 << 20


def _page_ms(pages, gbs: float):
    return pages * MODEL_PAGE_BYTES * 1e3 / (gbs * 1e9)


def replay(config, traffic: TrafficSpec, *, batch: int, max_pages: int,
           hbm_frac: float = 0.25, seed: int = 0, engine_every: int = 8,
           dt_ms: float = 50.0) -> dict:
    """Replay one arrival trace through a TieredKVCache; returns the p99
    modeled decode latency and the recall.  Deterministic in
    (config, traffic, seed)."""
    hbm_pages = max(2, int(batch * max_pages * hbm_frac))
    sched = replay_schedule(traffic, batch, max_pages * SPEC.page_tokens,
                            seed)
    cache = TieredKVCache(SPEC, batch, max_pages, hbm_pages, config=config)
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(batch, SPEC.n_layers, SPEC.kv_heads,
                         SPEC.head_dim)).astype(np.float32)
    q = rng.normal(size=(batch, SPEC.kv_heads,
                         SPEC.head_dim)).astype(np.float32)
    lats = []
    for t in range(traffic.steps):
        active = sched["active"][t]
        if not active.any():
            continue
        cache.decode_step(k, k, q, active=active)
        moved = 0
        if t % engine_every == engine_every - 1:
            m0 = cache.migrations
            cache.step_engine(dt_ms)
            moved = cache.migrations - m0
        res, tot = (np.asarray(a, np.float64) for a in cache.last_step_pages)
        # modeled per-sequence decode latency: compute floor + resident
        # pages over HBM + non-resident over PCIe + migration stall
        lat = (COMPUTE_MS + _page_ms(res, NEAR_GBS)
               + _page_ms(tot - res, FAR_GBS) + _page_ms(moved, FAR_GBS))
        lats.append(lat[active])
        cache.reset_seqs(sched["done"][t])
    lat = np.concatenate(lats) if lats else np.zeros(1)
    return {"p99_ms": float(np.percentile(lat, 99)),
            "recall": cache.recall()}


def serving_objective(stats: dict) -> float:
    """Lower-is-better serving score: tail latency penalized by recall."""
    return stats["p99_ms"] / max(stats["recall"], 1e-3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=20)
    ap.add_argument("--pattern", choices=("poisson", "bursty-diurnal"),
                    default="bursty-diurnal")
    args = ap.parse_args()

    traffic = TrafficSpec(pattern=args.pattern, arrival_rate=32 / 24,
                          steps=96)
    print(f"traffic: {traffic.to_json()}\n")

    def objective(config) -> float:
        stats = replay(config, traffic, batch=32, max_pages=8, seed=5)
        return serving_objective(stats)

    # the spec names the engine whose knob space is tuned; the serving
    # replay above replaces the simulator objective
    study = Study(ExperimentSpec(engine="kv-hemem", workload="kv-poisson"))
    res = study.tune(budget=args.budget, seed=0, n_init=8,
                     objective=objective, verbose=True)
    print(f"\ndefault objective: {res.default_value:.2f}")
    print(f"tuned   objective: {res.best_value:.2f} "
          f"({res.improvement:.2f}x better)")
    dflt = HEMEM_SPACE.default_config()
    for k, v in res.best.config.items():
        if v != dflt[k]:
            print(f"  {k:28s} {dflt[k]:>8} -> {v}")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
