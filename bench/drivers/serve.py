"""Driver of the serving cells: a closed decode loop over a tiered KV cache.

Every slot always decodes.  Each slot's sequence runs to a target length
drawn from the mix, then the slot resets and starts again from 0.  Step
inputs (K and V of every layer, q) come from a pool made on the device from
the seed at set-up, so the host does no random generation in the window.
Every ``engine_every`` steps one engine epoch runs.  Set-up decodes
``max_tokens`` steps, so every slot has reset at least once and every shape
is compiled, before the window opens.

In the window each step's attention output is copied to the host; the gap
between two such arrivals is the token gap a user sees (engine epochs and
resets fall into the gaps they delay).  ``decode_tok_per_s`` is tokens
decoded over the window's seconds, ``decode_step_p95_ms`` the 95th
percentile of the gaps.

After the window the plain reference replays every step from the start:
attention outputs of a seeded sample of window steps, the final residency,
lengths and migration count, and the K/V bytes of a seeded sample of pages.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import generate
from bench.harness import check
from bench.reference.hemem import Precision
from bench.reference.serve_ref import TieredKVReference


def geometry(cfg, tr):
    pt = cfg["page_tokens"]
    return dict(B=cfg["batch"], mp=cfg["max_position_embeddings"] // pt,
                pt=pt, H=tr["hbm_pages"], L=cfg["num_hidden_layers"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                heads=cfg["num_attention_heads"])


def run(h, control: Precision = None):
    """One run of the cell.  ``control`` replaces the served attention
    outputs by the reference's at that lower precision (the control of the
    comparison); the benchmark's own runs leave it None."""
    import jax
    import jax.numpy as jnp
    from repro.core.tiered_kv import KVSpec, TieredKVCache

    cfg, tr, seed = h.cell.config, h.cell.traffic, h.seed
    g = geometry(cfg, tr)
    B, pt, P = g["B"], g["pt"], tr["inputs"]
    dtype = jnp.dtype(cfg["dtype"])
    spec = KVSpec(n_layers=g["L"], kv_heads=g["KV"], head_dim=g["D"],
                  page_tokens=pt, dtype=dtype)
    cache = TieredKVCache(spec, batch=B, max_pages_per_seq=g["mp"],
                          hbm_pages=g["H"], compiled=True)
    kp, vp, qp = generate.input_pool(seed, P, B, g["L"], g["KV"],
                                     g["heads"], g["D"], dtype)
    ks, vs, qs = ([a[i] for i in range(P)] for a in (kp, vp, qp))
    jax.block_until_ready((ks, vs, qs))

    targets = generate.TargetLengths(tr["target_len"], B, seed)
    target = np.array([targets.draw(b) for b in range(B)])
    lengths = np.zeros(B, np.int64)
    every, dt_ms = cfg["engine_every"], cfg["dt_ms"]
    keep = generate.rng(seed, 4)
    events = []          # the replay log: ("s", src) / ("e",) / ("r", mask)
    kept = {}            # window step -> served output on the host
    engine_s = []
    state = {"step": 0}

    def step(in_window: bool):
        t = state["step"]
        src = t % P
        out = cache.decode_step(ks[src], vs[src], qs[src])
        host = np.asarray(out)
        stamp = time.perf_counter()
        events.append(("s", src))
        si = len(events) - 1
        lengths[:] += 1
        if in_window and keep.random() < tr["check_share"]:
            kept[si] = host
        if t % every == every - 1:
            t0 = time.perf_counter()
            with h.annotate("bench.engine"):
                cache.step_engine(dt_ms)
            if in_window:
                engine_s.append(time.perf_counter() - t0)
            events.append(("e",))
        done = lengths >= target
        if done.any():
            with h.annotate("bench.reset"):
                cache.reset_seqs(done)
            events.append(("r", done.copy()))
            lengths[done] = 0
            for b in np.flatnonzero(done):
                target[b] = targets.draw(b)
        state["step"] = t + 1
        return stamp, host, si

    with h.annotate("bench.warmup"):
        for _ in range(cfg["max_position_embeddings"]):
            step(False)
    w0 = len(events)
    mig0 = cache.migrations
    stamps = []
    with h.window():
        t_start = time.perf_counter()
        last = None
        while True:
            with h.annotate("bench.step"):
                stamp, host, pos = step(True)
            stamps.append(stamp)
            last = (pos, host)
            if stamp - t_start >= h.seconds:
                break
    h.read_peak()
    kept[last[0]] = last[1]
    window_s = stamps[-1] - t_start
    gaps = np.diff(np.concatenate([[t_start], stamps]))
    n_steps = len(stamps)
    print(f"bench: {n_steps} window steps, {len(engine_s)} engine epochs, "
          f"migrations {cache.migrations - mig0} in the window", flush=True)

    # the program's state, then the program is freed
    prog = {"slot_of": cache.slot_of, "lengths": cache.lengths,
            "migrations": cache.migrations}
    cand = [b * g["mp"] + j for b in range(B)
            for j in range(-(-int(lengths[b]) // pt))]
    pages = [cand[i] for i in generate.sample(seed, 6, len(cand),
                                              tr["check_pages"])]
    st = cache._st
    page_data = {}
    for pid in pages:
        s = int(prog["slot_of"][pid])
        pk, pv = (st["hbm_k"][s], st["hbm_v"][s]) if s >= 0 else \
            (st["host_k"][pid], st["host_v"][pid])
        page_data[pid] = (np.asarray(pk), np.asarray(pv))
    k_all = np.asarray(kp).reshape(P * B, g["L"], g["KV"], g["D"])
    v_all = np.asarray(vp).reshape(P * B, g["L"], g["KV"], g["D"])
    q_all = np.asarray(qp).astype(np.float32)
    del cache, st, ks, vs, qs, kp, vp, qp
    gc.collect()

    # the reference replays every step
    t_ref = time.perf_counter()
    k0 = k_all[:, 0].astype(np.float32)
    v0 = v_all[:, 0].astype(np.float32)
    ref = TieredKVReference(B, g["mp"], pt, g["H"], g["L"], g["KV"],
                            cfg["engine_knobs"],
                            g["L"] * pt * g["KV"] * g["D"]
                            * dtype.itemsize)
    attn_gap = 0.0
    tokens, moved = [], []
    for i, ev in enumerate(events):
        if ev[0] == "s":
            ref.append(ev[1])
            n_tok = ref.record()
            if i >= w0:
                tokens.append(n_tok)
            if i in kept:
                want = ref.attend(q_all[ev[1]], k0, v0)
                got = kept[i].astype(np.float32) if control is None else \
                    ref.attend(q_all[ev[1]], k0, v0, control)
                attn_gap = max(attn_gap, float(np.abs(got - want).max()
                                                / np.abs(want).max()))
        elif ev[0] == "e":
            m = ref.engine(dt_ms)
            if i >= w0:
                moved.append(m)
        else:
            ref.reset(ev[1])
    mism = int((prog["slot_of"] != ref.slot_of).sum()
               + (prog["lengths"] != ref.lengths).sum()
               + abs(prog["migrations"] - ref.migrations))
    bad = 0
    for pid, (pk, pv) in page_data.items():
        src = ref.page_writers(pid)
        held = src >= 0
        want_k = k_all[src[held]].transpose(1, 0, 2, 3)   # (L, T, KV, D)
        want_v = v_all[src[held]].transpose(1, 0, 2, 3)
        bad += int((pk[:, held].view(np.uint16)
                    != want_k.view(np.uint16)).sum())
        bad += int((pv[:, held].view(np.uint16)
                    != want_v.view(np.uint16)).sum())
    print(f"bench: reference replay of {len(events)} events, "
          f"{len(kept)} attention outputs and {len(page_data)} pages in "
          f"{time.perf_counter() - t_ref:.1f}s", flush=True)
    checks = [check("attn_gap", attn_gap, cfg["limits"]["attn_gap"]),
              check("state_mismatch", mism, 0),
              check("page_bytes_mismatch", bad, 0)]
    record = {"window_s": window_s, "steps": n_steps, "gaps": gaps.tolist(),
              "engine_s": engine_s, "tokens": tokens, "moved": moved,
              "geometry": g, "itemsize": dtype.itemsize}
    return {"attempted": n_steps, "failed": 0,
            "metrics": {"decode_tok_per_s": B * n_steps / window_s,
                        "decode_step_p95_ms": 1e3 * float(
                            np.percentile(gaps, 95))},
            "checks": checks, "record": record}


def control_precision() -> Precision:
    """The precision below the configuration's bfloat16: fp8 (e4m3)."""
    import ml_dtypes
    return Precision(ml_dtypes.float8_e4m3fn)
