"""Compiled tiered-KV serving: conformance, traffic, deprecations, cache.

The contract under test (see ``repro.core.serving_jax``):

* the fused compiled decode/engine step is conformant **by construction**
  with the per-page Python reference loop — identical HBM residency sets
  and migration counts, because both modes share ONE jitted engine-decision
  executable and feed it bit-identical integer access counts;
* traffic replay (``repro.core.traffic``) is deterministic in
  ``(spec, seed)`` and JSON-round-trippable;
* the lifted ``kv-hemem`` engine dispatches through the compiled jax
  backend (no numpy-fallback warning);
* ``record_reads`` is the public name (``_record_reads`` is a deprecated
  shim) and is fused — hence unavailable — on the compiled path.
"""

import logging
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import ExperimentSpec, SimOptions, Study
from repro.core.workloads import make_workload
from repro.core.knobs import HEMEM_SPACE
from repro.core.tiered_kv import KVSpec, TieredKVCache
from repro.core.traffic import (TrafficSpec, arrival_trace, replay_schedule,
                                step_read_counts)

SPEC = KVSpec(n_layers=1, kv_heads=2, head_dim=8, page_tokens=8)

#: corner configs for the conformance sweep: default; hair-trigger
#: promotion with fast epochs; sluggish cooling with slow epochs; tiny
#: thresholds with aggressive cooling
CORNERS = [
    None,
    dict(read_hot_threshold=1, sampling_period=100, migration_period=10),
    dict(read_hot_threshold=24, cooling_threshold=40,
         migration_period=2000, sampling_period=8000),
    dict(read_hot_threshold=2, write_hot_threshold=1, cooling_threshold=4,
         cooling_pages=1024, migration_period=10),
]


def _drive(cache: TieredKVCache, steps: int, *, engine_every=5, seed=0):
    """Deterministic decode loop with completion-style resets (a sequence
    finishes after 16 + 3*b tokens — staggered per slot); returns per-epoch
    (slot_of, migrations) snapshots."""
    B = cache.batch
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(B, cache.spec.n_layers, cache.spec.kv_heads,
                         cache.spec.head_dim)).astype(np.float32)
    q = rng.normal(size=(B, cache.spec.kv_heads,
                         cache.spec.head_dim)).astype(np.float32)
    limit = 16 + 3 * np.arange(B)
    snaps = []
    for t in range(steps):
        cache.decode_step(k, k, q)
        if t % engine_every == engine_every - 1:
            cache.step_engine(50.0)
            snaps.append((cache.slot_of.copy(), cache.migrations))
        done = cache.lengths >= limit
        if done.any():
            cache.reset_seqs(done)
    return snaps


@pytest.mark.parametrize("config", CORNERS)
def test_compiled_matches_reference_residency(config):
    """Compiled and reference modes agree on HBM residency (which logical
    pages sit in fast memory) and on total migration counts at every
    engine epoch — bitwise, not approximately."""
    kw = dict(batch=3, max_pages_per_seq=4, hbm_pages=5, config=config)
    ref = TieredKVCache(SPEC, compiled=False, **kw)
    com = TieredKVCache(SPEC, compiled=True, **kw)
    sr = _drive(ref, 60)
    sc = _drive(com, 60)
    assert len(sr) == len(sc) == 12
    for e, ((slot_r, mig_r), (slot_c, mig_c)) in enumerate(zip(sr, sc)):
        assert mig_r == mig_c, f"epoch {e}: migration counts diverge"
        np.testing.assert_array_equal(
            slot_r >= 0, slot_c >= 0,
            err_msg=f"epoch {e}: HBM residency sets diverge")
    assert ref.recall() == pytest.approx(com.recall(), abs=1e-9)
    if config is None or config.get("migration_period", 10) <= 10:
        assert sr[-1][1] > 0, "sweep produced no migrations (test too weak)"


def test_compiled_decode_equals_append_attend():
    """decode_step is the fusion of append + attend (same state, output)."""
    kw = dict(batch=2, max_pages_per_seq=3, hbm_pages=4)
    a = TieredKVCache(SPEC, compiled=True, **kw)
    b = TieredKVCache(SPEC, compiled=True, **kw)
    rng = np.random.default_rng(1)
    k = rng.normal(size=(2, 1, 2, 8)).astype(np.float32)
    q = rng.normal(size=(2, 2, 8)).astype(np.float32)
    for _ in range(6):
        out_a = a.decode_step(k, k, q)
        b.append(k, k)
        out_b = b.attend(q)
        np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    np.testing.assert_array_equal(a.lengths, b.lengths)
    np.testing.assert_array_equal(a.slot_of, b.slot_of)
    res, tot = a.last_step_pages
    assert res.shape == tot.shape == (2,)
    assert (res <= tot).all() and (tot >= 1).all()


def test_step_read_counts_numpy_jax_bitwise():
    """The integer access profile both paths feed the engine is identical
    under numpy and jitted jax (pure int32 arithmetic, the conformance
    anchor)."""
    import jax.numpy as jnp
    lengths = np.array([0, 1, 7, 8, 9, 64], np.int32)
    c_np, a_np = step_read_counts(lengths, 8, 8, 4096, xp=np)
    f = jax.jit(lambda ln: step_read_counts(ln, 8, 8, 4096, xp=jnp))
    c_j, a_j = f(jnp.asarray(lengths))
    np.testing.assert_array_equal(c_np, np.asarray(c_j))
    np.testing.assert_array_equal(a_np, np.asarray(a_j))


# -- traffic ----------------------------------------------------------------

def test_arrival_trace_deterministic():
    spec = TrafficSpec(pattern="bursty-diurnal", arrival_rate=3.0, steps=64)
    a1, l1 = arrival_trace(spec, seed=7)
    a2, l2 = arrival_trace(spec, seed=7)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(l1, l2)
    a3, _ = arrival_trace(spec, seed=8)
    assert not np.array_equal(a1, a3)


def test_traffic_spec_json_roundtrip():
    spec = TrafficSpec(pattern="bursty-diurnal", arrival_rate=2.5,
                       steps=96, burst_factor=4.0)
    assert TrafficSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="unknown traffic pattern"):
        TrafficSpec(pattern="sawtooth")


def test_replay_schedule_accounting():
    spec = TrafficSpec(pattern="poisson", arrival_rate=2.0, steps=80)
    sched = replay_schedule(spec, batch=8, max_tokens=24, seed=3)
    active, done = sched["active"], sched["done"]
    assert done[active].size and not done[~active].any(), \
        "done must imply active"
    assert sched["completed"] == done.sum()
    # a completed request decoded exactly its (clamped) length
    runs = active.sum(0)
    assert runs.sum() > 0


def test_kv_workloads_registered():
    for name in ("kv-poisson", "kv-diurnal"):
        wl = make_workload(name, scale=1.0)
        assert wl.n_pages == 8 * 32
        r, w = wl.epoch_access(0)
        assert r.shape == (wl.n_pages,) and r.sum() > 0


# -- lifted engine dispatch -------------------------------------------------

def test_kv_hemem_jax_dispatch_no_fallback(caplog):
    with caplog.at_level(logging.WARNING, logger="repro.core.simulator"):
        res = Study(ExperimentSpec(
            engine="kv-hemem", workload="kv-poisson",
            options=SimOptions(backend="jax"))).run()
    assert res.total_s > 0
    assert not any("falling back" in r.getMessage() for r in caplog.records)


# -- deprecations / API edges ----------------------------------------------

def test_record_reads_public_and_shim():
    cache = TieredKVCache(SPEC, batch=2, max_pages_per_seq=3, hbm_pages=4)
    rng = np.random.default_rng(0)
    k = rng.normal(size=(2, 1, 2, 8))
    cache.append(k, k)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # public name: no warning
        cache.record_reads()
    with pytest.deprecated_call():
        cache._record_reads()


def test_compiled_record_reads_is_fused():
    cache = TieredKVCache(SPEC, batch=2, max_pages_per_seq=3, hbm_pages=4,
                          compiled=True)
    with pytest.raises(RuntimeError, match="fuses read recording"):
        cache.record_reads()


# -- XLA compile cache plumbing --------------------------------------------

def test_compile_cache_dir_respects_env(tmp_path, monkeypatch):
    from repro.core import simulator
    target = tmp_path / "xla-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    assert simulator.compile_cache_dir() == str(target)
    assert target.is_dir()                      # created eagerly


def test_worker_init_points_jax_at_cache(tmp_path, monkeypatch):
    from repro.core import simulator
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    simulator._worker_init(str(tmp_path))
    import os
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    """Without the variable the cache sits at a fixed path in the checkout
    (no temp-dir, pid or time component: the path is part of the key)."""
    import os
    from repro.core import simulator
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert simulator.compile_cache_dir() == os.path.join(root, ".jax_cache")


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_enable_compile_cache_honours_env(tmp_path, monkeypatch,
                                          restore_cache_config):
    from repro.core import simulator
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert simulator.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # nothing set


def test_enable_compile_cache_defaults_to_checkout(monkeypatch,
                                                   restore_cache_config):
    import os
    from repro.core import simulator
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # unset, restored
    d = simulator.enable_compile_cache()
    assert d.endswith(os.sep + ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == d
    assert jax.config.jax_compilation_cache_dir == d


# -- one process per chip ----------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """Make the backend query answer 'TPU' (no program option: the query
    itself is steered)."""
    from repro.core import simulator
    monkeypatch.setattr(simulator, "jax_backend_is_tpu", lambda: True)


def test_backend_query_reads_jax_platforms_first(monkeypatch):
    from repro.core import simulator
    from repro.kernels import ops
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert simulator.jax_backend_is_tpu() is False
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert simulator.jax_backend_is_tpu() is True


def test_jax_workers_refused_on_tpu(on_tpu):
    from repro.core import simulator
    from repro.core.knobs import get_space
    from repro.core.simulator import run_simulation_batch
    _fresh_pool()
    wl = make_workload("gups", "8GiB-hot", threads=8, scale=0.02, seed=3)
    cfgs = [get_space("hemem").default_config()] * 2
    with pytest.raises(RuntimeError, match="TPU"):
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large",
                             backend="jax", workers=2)
    assert simulator._POOL is None, "a worker pool was started"


def test_auto_workers_resolve_to_one_on_tpu(on_tpu):
    import os
    from repro.core import simulator
    assert simulator._resolve_workers("auto", 8, "jax") == 1
    assert simulator._resolve_workers(1, 8, "jax") == 1
    assert simulator._resolve_workers("auto", 8, "numpy") == \
        min(os.cpu_count() or 1, 8)


def test_process_pools_refused_on_tpu(on_tpu):
    from repro.core.tune_service import FleetExecutor
    from repro.core.tune_service.executor import TrialExecutor
    with pytest.raises(RuntimeError, match="TPU"):
        FleetExecutor(workers=2, pool="process")
    with pytest.raises(RuntimeError, match="TPU"):
        TrialExecutor(slots=2, pool="process")


def _count_cache_files(d):
    import os
    return sum(len(fs) for _, _, fs in os.walk(d))


def _fresh_pool():
    from repro.core import simulator
    if simulator._POOL is not None:
        simulator._POOL.shutdown(wait=True, cancel_futures=True)
    simulator._POOL, simulator._POOL_SIZE = None, 0


def test_sharded_workers_warm_start_from_compile_cache(tmp_path,
                                                       monkeypatch):
    """A second worker pool must hit the shared XLA disk cache instead of
    re-jitting the epoch loop (the carried ROADMAP thread): the first
    sharded jax run populates ``compile_cache_dir()``, a pool spun up
    afterwards adds no new cache entries."""
    import os
    if (os.cpu_count() or 1) < 2:
        pytest.skip("needs >= 2 CPUs")
    from repro.core.knobs import get_space
    from repro.core.simulator import run_simulation_batch
    cache = tmp_path / "xla-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    wl = make_workload("gups", "8GiB-hot", threads=8, scale=0.02, seed=3)
    space = get_space("hemem")
    cfgs = [space.default_config(),
            space.sample(np.random.default_rng(5))]
    _fresh_pool()
    try:
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=9,
                             backend="jax", workers=2)
        n_cold = _count_cache_files(cache)
        assert n_cold > 0, "first sharded run wrote no cache entries"
        _fresh_pool()                       # new workers, cold jit caches
        run_simulation_batch(wl, "hemem", cfgs, "pmem-large", seeds=9,
                             backend="jax", workers=2)
        assert _count_cache_files(cache) == n_cold, \
            "second pool re-jitted instead of warm-starting from disk"
    finally:
        _fresh_pool()
