"""TieredKVCache / TieredParamStore behaviour + optimizer/compression tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiered_kv import KVSpec, TieredKVCache
from repro.core.tiered_params import TieredParamStore
from repro.optim import (Adafactor, AdamW, compressed_psum, ef_compress,
                         ef_decompress)


def _fill(cache: TieredKVCache, steps: int, rng):
    s = cache.spec
    for _ in range(steps):
        k = rng.normal(size=(cache.batch, s.n_layers, s.kv_heads,
                             s.head_dim))
        cache.append(k, k)


def test_tiered_kv_append_attend_roundtrip():
    rng = np.random.default_rng(0)
    spec = KVSpec(n_layers=2, kv_heads=2, head_dim=16, page_tokens=4)
    cache = TieredKVCache(spec, batch=2, max_pages_per_seq=8, hbm_pages=16)
    _fill(cache, 12, rng)
    q = rng.normal(size=(2, 4, 16))
    out = cache.attend(q)
    assert out.shape == (2, 4, 16)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert (cache.lengths == 12).all()


def test_tiered_kv_engine_keeps_hot_pages_resident():
    """With a tiny fast tier, the engine should keep the high-attention-mass
    pages (sink + recent) resident, beating a no-migration baseline."""
    rng = np.random.default_rng(1)
    spec = KVSpec(n_layers=1, kv_heads=1, head_dim=8, page_tokens=4)

    q = rng.normal(size=(1, 1, 8))

    def run(config, migrate: bool):
        cache = TieredKVCache(spec, batch=1, max_pages_per_seq=64,
                              hbm_pages=8, config=config)
        for step in range(180):
            k = rng.normal(size=(1, 1, 1, 8))
            cache.decode_step(k, k, q)
            if migrate and step % 10 == 9:
                cache.step_engine(100.0)
        return cache

    tuned = run(dict(read_hot_threshold=1, sampling_period=100,
                     migration_period=10), migrate=True)
    frozen = run(dict(), migrate=False)
    assert tuned.recall() > frozen.recall()
    assert tuned.migrations > 0


def test_tiered_kv_attend_only_uses_resident_pages():
    rng = np.random.default_rng(2)
    spec = KVSpec(n_layers=1, kv_heads=1, head_dim=8, page_tokens=4)
    cache = TieredKVCache(spec, batch=1, max_pages_per_seq=8, hbm_pages=2)
    _fill(cache, 16, rng)   # 4 pages; only 2 fit
    assert (cache.slot_of >= 0).sum() <= 2
    q = rng.normal(size=(1, 1, 8))
    out = cache.attend(q)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_tiered_params_hot_experts_promoted():
    rng = np.random.default_rng(3)
    weights = {"w": rng.normal(size=(16, 8, 8)).astype(np.float32)}
    store = TieredParamStore(weights, hbm_experts=4,
                             config=dict(read_hot_threshold=1,
                                         sampling_period=100))
    hot = np.array([12, 13, 14, 15])
    for _ in range(30):
        store.route(np.repeat(hot, 50))
        store.step_engine(100.0)
    assert set(np.flatnonzero(store.slot_of >= 0)) >= set(hot.tolist())
    # gather returns correct values regardless of tier
    g = store.gather("w", np.array([12, 0]))
    np.testing.assert_allclose(np.asarray(g[0], np.float32),
                               weights["w"][12], atol=2e-2)


def test_adamw_and_adafactor_reduce_quadratic():
    def loss(p):
        return jnp.sum((p["w"] - 3.0) ** 2) + jnp.sum((p["b"] + 1.0) ** 2)

    for opt in (AdamW(lr=0.1), Adafactor(lr=0.5)):
        params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
        state = opt.init(params)
        l0 = float(loss(params))
        for _ in range(60):
            g = jax.grad(loss)(params)
            params, state = opt.update(g, state, params)
        assert float(loss(params)) < 0.05 * l0, type(opt).__name__


def test_ef_int8_compression_error_feedback():
    rng = np.random.default_rng(4)
    g_stream = [jnp.asarray(rng.normal(size=(64,)), jnp.float32)
                for _ in range(50)]
    residual = jnp.zeros((64,))
    err_accum = jnp.zeros((64,))
    for g in g_stream:
        q, scale, residual = ef_compress(g, residual)
        out = ef_decompress(q, scale)
        err_accum = err_accum + (g - out)
    # with error feedback, the *accumulated* error stays bounded (the
    # residual carries it forward instead of losing it)
    assert float(jnp.abs(residual).max()) < 0.05
    per_step_err = float(jnp.abs(err_accum).mean()) / len(g_stream)
    assert per_step_err < 0.01
