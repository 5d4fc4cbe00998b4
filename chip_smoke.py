#!/usr/bin/env python3
"""Run the tuning loop and tiered-KV decode once on a TPU, and check them.

    python chip_smoke.py                # one chip: tuning + serving phases
    python chip_smoke.py --four-chips   # four chips: the pmapped epoch loop

Everything runs in this one process: a chip belongs to one process at a
time.

* **tuning** — ``Study.tune(budget=16, batch_size=8)`` of HeMem on GUPS
  8GiB-hot at the paper's size (scale 1.0: 64.03 GiB RSS, 32,783 2 MiB
  pages, 60 epochs) on the jax backend, so the compiled epoch loop runs at
  B=8 through the Pallas selection kernel.  Then parity with the numpy
  reference on the same workload: static and oracle plan bit-identical
  migrations with walls within 1e-4; sampled HeMem lands within the 5 %
  that ``tests/test_jax_backend.py`` allows at scale >= 0.25.
* **serving** — ``TieredKVCache(compiled=True)`` at Gemma 2 9B's KV
  geometry (42 layers, 8 KV heads, head_dim 256, 16-token pages, bf16),
  8 sequences of up to 1,024 tokens over 192 HBM pages, replayed until
  engine epochs migrate pages, sequences finish and their slots are
  reused.  The same trace through the ``compiled=False`` reference loop
  must give equal residency sets and migration counts at every engine
  epoch, and the Pallas decode attention must match
  ``kernels.ref.paged_attention_ref`` over the reference's pools.
* **four chips** (``--four-chips`` only) — the B=8 epoch loop, which
  ``engine_jax`` pmaps over the local devices, against the same batch
  jitted on one device: equal bitwise.

Each phase prints the device kind, the selection path, compile and run
times (to ``block_until_ready``), its checks and the device's peak memory.
The last line of standard output is ``{"ok": true, "device": {...}}``.  The
script exits non-zero without that line when JAX finds no TPU, when a
kernel would run in interpret mode, or when a check fails.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: hemem's numpy-vs-jax tolerance on total_s at scale >= 0.25 (the sampled
#: monitoring noise differs in stream, not in distribution)
HEMEM_REL_TOL = 0.05
#: static/oracle walls agree to float32 cost-model rounding
EXACT_REL_TOL = 1e-4
#: decode attention vs the f32 reference, outputs in bf16 (a few bf16 ulps
#: at unit magnitude)
ATTN_ATOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[{'PASS' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> list:
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def report_phase(name: str) -> None:
    from repro.kernels import ops
    info = device_info()
    print(f"{name}: device {info['kind']} x{info['count']}, "
          f"select_path={ops.select_path()}, "
          f"interpret={ops.interpret()}", flush=True)


def timed(fn, *args, **kw):
    """(result, seconds); results are host values or blocked on."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# tuning
# ---------------------------------------------------------------------------
def _study(engine: str, backend: str, scale: float):
    from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
    return Study(ExperimentSpec(
        engine=engine, workload=WorkloadSpec("gups", "8GiB-hot", scale=scale),
        options=SimOptions(backend=backend)))


def _batch_configs(batch: int, seed: int = 0):
    from repro.core.knobs import get_space
    space = get_space("hemem")
    rng = np.random.default_rng(seed)
    return [space.default_config()] + [space.sample(rng)
                                       for _ in range(batch - 1)]


def tuning_phase(scale: float = 1.0, budget: int = 16, batch: int = 8):
    report_phase("tuning")
    st = _study("hemem", "jax", scale)
    wl = st.workload()
    print(f"tuning: gups 8GiB-hot scale={scale}: {wl.n_pages} pages x "
          f"{wl.n_epochs} epochs, B={batch}", flush=True)
    cfgs = _batch_configs(batch)
    _, t_cold = timed(st.run, configs=cfgs)
    res, t_warm = timed(st.run, configs=cfgs)
    print(f"tuning: B={batch} epoch loop compile+run {t_cold:.3f}s, run "
          f"{t_warm:.3f}s (compile ~{t_cold - t_warm:.3f}s)", flush=True)
    check(all(np.isfinite(r.total_s) and r.total_s > 0 for r in res),
          f"B={batch} epoch loop gives finite positive walls")
    tune, t_tune = timed(st.tune, budget=budget, batch_size=batch)
    print(f"tuning: tune(budget={budget}, batch_size={batch}) {t_tune:.3f}s "
          f"default {tune.default_value:.6f}s best {tune.best_value:.6f}s",
          flush=True)
    check(len(tune.history) == budget and np.isfinite(tune.best_value),
          f"tune returns {budget} finite observations")

    for engine in ("static", "oracle"):
        ref = _study(engine, "numpy", scale).run()
        (jx, t) = timed(_study(engine, "jax", scale).run)
        rel = abs(ref.total_s - jx.total_s) / ref.total_s
        rel_e = float(np.max(np.abs(ref.epoch_wall_ms - jx.epoch_wall_ms)
                             / np.maximum(ref.epoch_wall_ms, 1e-9)))
        same = np.array_equal(ref.cum_migrations, jx.cum_migrations)
        print(f"tuning: {engine} numpy total_s {ref.total_s!r} jax "
              f"{jx.total_s!r} rel {rel:.3e} max epoch rel {rel_e:.3e} "
              f"migrations {int(ref.cum_migrations[-1])}/"
              f"{int(jx.cum_migrations[-1])} ({t:.3f}s)", flush=True)
        check(same and rel < EXACT_REL_TOL,
              f"{engine}: migrations bitwise, total_s within "
              f"{EXACT_REL_TOL:g}")
    ref = _study("hemem", "numpy", scale).run()
    jx = st.run()
    rel = abs(ref.total_s - jx.total_s) / ref.total_s
    print(f"tuning: hemem numpy total_s {ref.total_s!r} jax {jx.total_s!r} "
          f"rel {rel:.3e}", flush=True)
    check(rel < HEMEM_REL_TOL, f"hemem: total_s within {HEMEM_REL_TOL:g}")
    print(f"tuning: peak_bytes_in_use {peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def gemma2_kv_spec(page_tokens: int = 16):
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.tiered_kv import KVSpec
    cfg = get_config("gemma2-9b")
    return KVSpec(n_layers=cfg.n_layers, kv_heads=cfg.n_kv_heads,
                  head_dim=cfg.head_dim, page_tokens=page_tokens,
                  dtype=jnp.bfloat16), cfg.n_heads


def _step_inputs(t: int, batch: int, spec, n_heads: int, seed: int):
    rng = np.random.default_rng([seed, t])
    kv_shape = (batch, spec.n_layers, spec.kv_heads, spec.head_dim)
    k = rng.standard_normal(kv_shape, dtype=np.float32)
    v = rng.standard_normal(kv_shape, dtype=np.float32)
    q = rng.standard_normal((batch, n_heads, spec.head_dim),
                            dtype=np.float32)
    return k, v, q


def replay(cache, limits, steps: int, engine_every: int, n_heads: int,
           seed: int, on_epoch):
    """Decode ``steps`` tokens per sequence; a sequence finishes after
    ``limits[b]`` tokens and restarts in its slot.  Every
    ``engine_every`` steps ``on_epoch(t, q, out)`` runs before the engine
    epoch.  Returns per-epoch ``(residency, migrations)`` and the count
    of finished sequences, with the HBM slots they freed and whether a
    later page reused one."""
    snaps, finished, freed, reused = [], 0, set(), False
    for t in range(steps):
        k, v, q = _step_inputs(t, cache.batch, cache.spec, n_heads, seed)
        out = cache.decode_step(k, v, q)
        if t % engine_every == engine_every - 1:
            on_epoch(t, q, out)
            cache.step_engine(50.0)
            snaps.append((cache.slot_of >= 0, cache.migrations))
        if freed and not reused:
            pos = cache.page_of_slot
            reused = bool((pos[sorted(freed)] >= 0).any())
        done = cache.lengths >= limits
        if done.any():
            slots = cache.slot_of.reshape(cache.batch, cache.max_pages)
            freed |= set(int(s) for s in slots[done].ravel() if s >= 0)
            finished += int(done.sum())
            cache.reset_seqs(done)
    return snaps, finished, reused


def _reference_batch(batch: int, max_pages: int, spec) -> int:
    """Largest batch <= ``batch`` whose float32 host pools (K and V) take
    at most half of this host's available memory."""
    page_bytes = spec.n_layers * spec.page_tokens * spec.kv_heads \
        * spec.head_dim * 4
    avail = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    while batch > 1 and 2 * batch * max_pages * page_bytes > avail // 2:
        batch //= 2
    return batch


def serving_phase(spec=None, n_heads: int = None, batch: int = 8,
                  max_pages: int = 64, hbm_pages: int = 192,
                  steps: int = 640, engine_every: int = 16, seed: int = 0):
    import jax
    from repro.core.tiered_kv import TieredKVCache
    from repro.kernels.ref import paged_attention_ref
    report_phase("serving")
    if spec is None:
        spec, n_heads = gemma2_kv_spec()
    rb = _reference_batch(batch, max_pages, spec)
    if rb != batch:
        print(f"serving: reference host pools do not fit this host; batch "
              f"cut from {batch} to {rb}", flush=True)
        batch = rb
    tokens = max_pages * spec.page_tokens
    # staggered completions: sequence b finishes after 512 + 64 b tokens
    # (scaled to the context), so the first ones restart mid-run
    limits = tokens // 2 + (tokens // 16) * np.arange(batch)
    page_mib = spec.n_layers * spec.page_tokens * spec.kv_heads \
        * spec.head_dim * np.dtype(spec.dtype).itemsize / 2 ** 20
    print(f"serving: KV {spec.n_layers}L x {spec.kv_heads}KV x "
          f"{spec.head_dim}D, page {spec.page_tokens} tokens "
          f"({page_mib:.2f} MiB), batch {batch} x {tokens} tokens, "
          f"{hbm_pages} HBM pages, {steps} steps, engine every "
          f"{engine_every}", flush=True)
    kw = dict(batch=batch, max_pages_per_seq=max_pages, hbm_pages=hbm_pages)

    # compiled path; a first step, epoch and reset on a scratch cache
    # compile what the replay runs
    k, v, q = _step_inputs(0, batch, spec, n_heads, seed + 1)
    warm = TieredKVCache(spec, compiled=True, **kw)
    _, t_dec = timed(warm.decode_step, k, v, q)
    _, t_eng = timed(lambda: (warm.step_engine(50.0), warm.migrations))
    _, t_rst = timed(lambda: (warm.reset_seqs(np.ones(batch, bool)),
                              warm.lengths))
    del warm
    gc.collect()
    print(f"serving: compile+run first decode {t_dec:.3f}s, engine epoch "
          f"{t_eng:.3f}s, reset {t_rst:.3f}s", flush=True)
    cache = TieredKVCache(spec, compiled=True, **kw)
    outs = {}

    def keep(t, q, out):
        outs[t] = np.asarray(out, np.float32)

    (snaps_c, fin_c, reused), t_run = timed(
        replay, cache, limits, steps, engine_every, n_heads, seed, keep)
    print(f"serving: compiled replay {steps} steps {t_run:.3f}s "
          f"({t_run / steps * 1e3:.3f} ms/step incl. input generation), "
          f"migrations {snaps_c[-1][1]}, finished {fin_c}, recall "
          f"{cache.recall():.4f}", flush=True)
    print(f"serving: peak_bytes_in_use {peak_bytes()}", flush=True)
    del cache
    gc.collect()

    # the same trace through the reference loop
    ref = TieredKVCache(spec, compiled=False, **kw)
    errs = []

    def compare(t, q, out):
        want = paged_attention_ref(
            jax.numpy.asarray(q, spec.dtype), ref.hbm_k[:, 0],
            ref.hbm_v[:, 0], ref.block_table(),
            jax.numpy.asarray(ref.lengths, jax.numpy.int32))
        errs.append(float(np.max(np.abs(
            outs[t] - np.asarray(want, np.float32)))))

    (snaps_r, fin_r, _), t_ref = timed(
        replay, ref, limits, steps, engine_every, n_heads, seed, compare)
    print(f"serving: reference replay {t_ref:.3f}s, migrations "
          f"{snaps_r[-1][1]}", flush=True)
    same = len(snaps_c) == len(snaps_r) and all(
        mc == mr and np.array_equal(rc, rr)
        for (rc, mc), (rr, mr) in zip(snaps_c, snaps_r))
    moving = int(np.count_nonzero(np.diff([0] + [m for _, m in snaps_c])))
    check(same, f"compiled == reference: residency sets and migration "
          f"counts at all {len(snaps_c)} engine epochs")
    check(moving >= 2, f"{moving} engine epochs migrated pages")
    check(fin_c >= 1 and fin_c == fin_r, f"{fin_c} sequences finished")
    check(reused, "a finished sequence's HBM slot was reused")
    check(max(errs) <= ATTN_ATOL,
          f"decode attention vs paged_attention_ref: max |err| "
          f"{max(errs):.3e} <= {ATTN_ATOL:g} over {len(errs)} steps")
    print(f"serving: peak_bytes_in_use {peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def one_device():
    """Run the compiled epoch loop jitted on one device: the pmap
    decision reads ``engine_jax._n_devices``."""
    from repro.core import engine_jax
    saved = engine_jax._n_devices
    engine_jax._n_devices = lambda: 1
    try:
        yield
    finally:
        engine_jax._n_devices = saved


def four_chip_phase(scale: float = 1.0, batch: int = 8, chips: int = 4):
    import jax
    from repro.core import engine_jax
    report_phase("four-chips")
    check(jax.local_device_count() == chips,
          f"{jax.local_device_count()} local devices (want {chips})")
    st = _study("hemem", "jax", scale)
    cfgs = _batch_configs(batch)
    pm, t_pm = timed(st.run, configs=cfgs)
    pmapped = [k for k in engine_jax.compiled_cache_info() if k[9]]
    check(bool(pmapped), f"B={batch} epoch loop pmapped over {chips} devices")
    _, t_pm2 = timed(st.run, configs=cfgs)
    with one_device():
        one, t_one = timed(st.run, configs=cfgs)
        _, t_one2 = timed(st.run, configs=cfgs)
    print(f"four-chips: pmap compile+run {t_pm:.3f}s run {t_pm2:.3f}s; "
          f"one-device jit compile+run {t_one:.3f}s run {t_one2:.3f}s",
          flush=True)
    same = all(np.array_equal(a.cum_migrations, b.cum_migrations)
               and np.array_equal(a.epoch_wall_ms, b.epoch_wall_ms)
               and np.array_equal(a.fast_hit_rate, b.fast_hit_rate)
               for a, b in zip(pm, one))
    check(same, f"pmapped B={batch} == one-device jit, bitwise "
          "(migrations, walls, hit rates)")
    print(f"four-chips: peak_bytes_in_use {peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the pmapped epoch loop on four chips")
    args = p.parse_args(argv)
    try:
        from repro.core.simulator import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's source is missing ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    entries0 = sum(len(fs) for _, _, fs in os.walk(cache_dir))

    import jax
    from repro.kernels import ops
    counts = {"requests": 0, "hits": 0}

    def listen(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1

    jax.monitoring.register_event_listener(listen)
    try:
        info = device_info()
        print(f"device: {info}", flush=True)
        check(info["platform"] == "tpu",
              f"JAX platform is tpu (got {info['platform']})")
        check(ops.select_path() == "pallas", "selection runs the Pallas "
              f"kernel (select_path={ops.select_path()})")
        check(not ops.interpret(), "Pallas kernels compile for the chip "
              "(not interpret mode)")
        if args.four_chips:
            four_chip_phase()
        else:
            tuning_phase()
            serving_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    entries1 = sum(len(fs) for _, _, fs in os.walk(cache_dir))
    print(f"compile cache {cache_dir}: {counts['requests']} requests, "
          f"{counts['hits']} hits, entries {entries0} -> {entries1}",
          flush=True)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
