"""Roofline analysis from the dry-run artifacts (§Roofline of EXPERIMENTS.md).

Per (arch x shape) cell on the single-pod mesh (256 chips), derive:

  compute term    = HLO_FLOPs_dev / peak_FLOPs          (197 TFLOP/s bf16)
  memory term     = HLO_bytes_dev / HBM_bw              (819 GB/s)
  collective term = collective_bytes_dev / link_bw      (~50 GB/s ICI)

Sources: ``compiled.cost_analysis()`` flops / bytes-accessed and the
collective operand bytes parsed from ``compiled.as_text()`` — all recorded by
``repro.launch.dryrun``.  Two methodology notes (validated in
``test_roofline.py`` and EXPERIMENTS.md §Dry-run):

  1. The SPMD module is the per-device program, so cost_analysis numbers are
     per-chip already.
  2. XLA's HloCostAnalysis counts while-loop bodies ONCE.  The layer stack
     and the gradient-accumulation loop are lax.scans, so we correct by the
     known static trip counts: K = n_micro x n_layer_groups (train),
     n_layer_groups (prefill/decode).  The correction is exact for the
     scan-resident work, which dominates every cell; out-of-loop work
     (embedding, final loss reduction) is over-counted by K but is orders of
     magnitude smaller.

MODEL_FLOPS uses the 6·N_active·D convention (train) / 2·N_active·D
(inference) — the "useful"-compute yardstick; its ratio against HLO FLOPs
exposes remat/dispatch overheads.
"""

from __future__ import annotations

import glob
import json
import os

from .common import claim, print_claims, save

PEAK_FLOPS = 197e12          # bf16 per chip (v5e)
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def _n_groups(arch: str) -> int:
    from repro.configs import get_config
    from repro.models.transformer import pattern_period
    cfg = get_config(arch)
    return cfg.n_layers // pattern_period(cfg)


def _tokens(shape: str, res: dict) -> float:
    from repro.models.config import SHAPES
    sh = SHAPES[shape]
    if sh.kind == "decode":
        return sh.global_batch           # one token per sequence
    return sh.global_batch * sh.seq_len


def _analytic_hbm_bytes(res: dict) -> float:
    """Per-chip HBM traffic model (the fused-TPU counterpart of the CPU
    backend's unfused bytes-accessed): weight streaming + activation traffic
    + KV-cache reads, all bf16.

      train:   3 reads of the (sharded) params per microbatch (fwd, remat
               re-fwd, bwd) + grad/optimizer write traffic + activations
      prefill: 1 read of params + activations
      decode:  1 read of params + full KV-cache read
    """
    from repro.models.config import SHAPES
    from repro.configs import get_config
    cfg = get_config(res["arch"])
    sh = SHAPES[res["shape"]]
    chips = res["n_chips"]
    P_dev = 2.0 * res["param_count"] / chips        # bf16 shard (FSDP+TP)
    act_frac = res["active_param_count"] / res["param_count"]
    tokens_dev = _tokens(res["shape"], res) / chips
    act_bytes = 2.0 * tokens_dev * cfg.d_model * cfg.n_layers * 6

    if res.get("step_kind") == "train_step":
        n_micro = res.get("n_micro", 1)
        # dense weights stream 3x per microbatch; MoE experts only the
        # active fraction after the first touch
        w_traffic = P_dev * (1 + 2 * act_frac) * n_micro
        opt = 3.0 * P_dev * 2                       # grads + moments (fp32)
        return w_traffic + opt + 3 * act_bytes
    if res.get("step_kind") == "prefill_step":
        return P_dev * act_frac + act_bytes
    # decode: params (active) + KV cache for this step
    kv_bytes = 2.0 * 2.0 * sh.global_batch * min(sh.seq_len,
                                                 cfg.window or sh.seq_len) \
        * cfg.n_kv_heads * cfg.hd * cfg.n_layers / chips
    return P_dev * act_frac + kv_bytes + act_bytes


def analyze_cell(res: dict) -> dict:
    arch, shape = res["arch"], res["shape"]
    chips = res["n_chips"]
    k_groups = _n_groups(arch)
    n_micro = res.get("n_micro", 1)
    K = (n_micro * k_groups) if res.get("step_kind") == "train_step" \
        else k_groups

    flops_dev = res["cost_analysis"].get("flops", 0.0) * K
    bytes_dev_raw = res["cost_analysis"].get("bytes accessed", 0.0) * K
    # the CPU backend's bytes-accessed is an UNFUSED upper bound; the fused
    # HBM traffic model below is the roofline memory term (both reported)
    bytes_dev = _analytic_hbm_bytes(res)
    coll_dev = res["collective_bytes_total"] * K

    t_c = flops_dev / PEAK_FLOPS
    t_m = bytes_dev / HBM_BW
    t_n = coll_dev / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_n}
    dominant = max(terms, key=terms.get)

    mult = 6.0 if res.get("step_kind") == "train_step" else 2.0
    model_flops = mult * res["active_param_count"] * _tokens(shape, res)
    model_flops_dev = model_flops / chips
    ratio = model_flops_dev / max(flops_dev, 1.0)
    bound = max(t_c, t_m, t_n)
    frac = (model_flops_dev / PEAK_FLOPS) / max(bound, 1e-12)

    return {
        "arch": arch, "shape": shape, "mesh": res["mesh"],
        "step_kind": res.get("step_kind"), "K": K,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "unfused_bytes_s": bytes_dev_raw / HBM_BW,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": ratio,
        "roofline_fraction": frac,
    }


def load_cells(mesh: str = "single"):
    cells = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR,
                                              f"*__{mesh}.json"))):
        with open(path) as f:
            res = json.load(f)
        if "error" in res or "skipped" in res:
            cells.append(res)
            continue
        cells.append(analyze_cell(res))
    return cells


def format_table(cells) -> str:
    hdr = (f"{'arch':24s} {'shape':12s} {'step':12s} "
           f"{'T_comp(s)':>10s} {'T_mem(s)':>10s} {'T_coll(s)':>10s} "
           f"{'bound':>10s} {'useful':>7s} {'roofline':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for c in cells:
        if "skipped" in c:
            lines.append(f"{c['arch']:24s} {c['shape']:12s} SKIP "
                         f"({c['skipped'][:60]}...)")
            continue
        if "error" in c:
            lines.append(f"{c['arch']:24s} {c['shape']:12s} ERROR")
            continue
        lines.append(
            f"{c['arch']:24s} {c['shape']:12s} {c['step_kind'] or '':12s} "
            f"{c['compute_s']:10.4f} {c['memory_s']:10.4f} "
            f"{c['collective_s']:10.4f} {c['dominant']:>10s} "
            f"{c['useful_flops_ratio']:7.2f} {c['roofline_fraction']:9.3f}")
    return "\n".join(lines)


def run(quick: bool = False) -> dict:
    cells = load_cells("single")
    ok = [c for c in cells if "dominant" in c]
    skipped = [c for c in cells if "skipped" in c]
    failed = [c for c in cells if "error" in c]

    table = format_table(cells)
    print(table, flush=True)

    multi = load_cells("multi")
    multi_ok = [c for c in multi if "dominant" in c]

    n_expected_skips = 7 * 1   # 7 full-attention archs skip long_500k
    claims = [
        claim("dryrun: every applicable (arch x shape) cell lowered+compiled "
              "on the single-pod mesh",
              len(failed) == 0 and len(ok) + len(skipped) == 40,
              f"{len(ok)} ok, {len(skipped)} skipped, {len(failed)} failed"),
        claim("dryrun: multi-pod (2x16x16) mesh compiles every cell too",
              len([c for c in multi if 'error' in c]) == 0,
              f"{len(multi_ok)} ok / {len(multi)} total"),
        claim("roofline: every compiled cell has a dominant term identified",
              all(c.get("dominant") for c in ok), "see table"),
    ]
    out = {"cells": cells, "multi_cells": multi, "table": table,
           "claims": claims}
    print_claims(claims)
    save("roofline", out)
    return out


if __name__ == "__main__":
    from repro.core.simulator import enable_compile_cache
    enable_compile_cache()
    run()
