"""Serve a small model with batched requests over a TieredKVCache, with the
tiering engine migrating KV pages between HBM and host tiers — the paper's
technique running in the real decode path.

By default this runs the COMPILED serving path: ``decode_step`` is one
jitted call (append + paged-attention + read-recording fused over the whole
batch) and engine epochs batch their page moves through ``page_migrate``.
``--python-loop`` runs the per-page reference loop instead — same residency
decisions (both modes share one jitted engine executable), ~100x slower.

    PYTHONPATH=src python examples/serve_tiered.py [--steps 128] [--tuned]
"""
import argparse
import sys, os, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.tiered_kv import KVSpec, TieredKVCache

TUNED = dict(read_hot_threshold=2, sampling_period=500,
             cooling_pages=65536, migration_period=10)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--hbm-pages", type=int, default=24)
    ap.add_argument("--tuned", action="store_true")
    ap.add_argument("--python-loop", action="store_true",
                    help="use the per-page reference loop instead of the "
                         "fused compiled step")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    spec = KVSpec(n_layers=4, kv_heads=2, head_dim=32, page_tokens=8)
    cache = TieredKVCache(spec, batch=args.batch, max_pages_per_seq=64,
                          hbm_pages=args.hbm_pages,
                          config=TUNED if args.tuned else None,
                          compiled=not args.python_loop)
    t0 = time.time()
    for step in range(args.steps):
        k = rng.normal(size=(args.batch, spec.n_layers, spec.kv_heads,
                             spec.head_dim))
        q = rng.normal(size=(args.batch, spec.kv_heads, spec.head_dim))
        out = cache.decode_step(k, k, q)   # fused append+attend+record
        if step % 8 == 7:
            cache.step_engine(50.0)
        if step % 32 == 31:
            print(f"step {step+1:4d}  recall={cache.recall():.3f}  "
                  f"migrations={cache.migrations:4d}  "
                  f"hbm_util={cache.hbm_utilization():.2f}")
    mode = "python-loop" if args.python_loop else "compiled"
    print(f"\n{'tuned' if args.tuned else 'default'} config [{mode}]: "
          f"recall={cache.recall():.3f} migrations={cache.migrations} "
          f"({(time.time()-t0)/args.steps*1e3:.1f} ms/step)")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
