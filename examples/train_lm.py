"""End-to-end training driver: train a language model with the full
production stack (sharded train_step, grad accumulation, async checkpoints,
straggler detection, deterministic resume).

    PYTHONPATH=src python examples/train_lm.py --preset tiny --steps 200
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is a ~100M-param llama-style config (takes a while on CPU;
it is the TPU-ready path).
"""
import argparse
import dataclasses
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.mesh import make_local_mesh
from repro.models.config import ModelConfig
from repro.train.trainer import Trainer

PRESETS = {
    "tiny": ModelConfig(arch="tiny-lm", family="lm", n_layers=4, d_model=128,
                        n_heads=4, n_kv_heads=2, d_ff=512, vocab=2048,
                        remat=False),
    "100m": ModelConfig(arch="lm-100m", family="lm", n_layers=12,
                        d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                        vocab=32000, remat=False),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    print(f"{cfg.arch}: {cfg.param_count()/1e6:.1f}M params")
    mesh = make_local_mesh()
    tr = Trainer(cfg, mesh, args.workdir, global_batch=args.batch,
                 seq_len=args.seq, total_steps=args.steps, ckpt_every=50,
                 lr=3e-4)
    out = tr.run()
    for m in out["metrics"]:
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.2f}  {m['dt']*1e3:.0f}ms")
    print(f"stragglers: {len(out['stragglers'])}")


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    main()
