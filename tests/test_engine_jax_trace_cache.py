"""The compiled epoch loop's per-workload trace cache and HeMem's cooling
sweep by page ranges (``repro.core.engine_jax``).

Each workload's whole-run trace is built and copied to the device once; a
launch then ships only ``(B,)`` knob vectors and scalars.  Results must not
depend on whether the trace came from the cache.
"""

import dataclasses
import gc
import weakref

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import engine_jax, spans
from repro.core.knobs import HEMEM_SPACE, get_space
from repro.core.simulator import (PAGE_BYTES, _epoch_consts, _fast_capacity,
                                  get_machine, scale_config)
from repro.core.workloads import make_workload

B = 3
OUT_KEYS = ("wall_ms", "cum_migrations", "hit_rate", "sampling_ms",
            "stall_ms")


def _wl(scale=0.02):
    return make_workload("gups", "8GiB-hot", threads=8, scale=scale, seed=3)


def _setup(wl):
    machine = get_machine("pmem-large")
    space = get_space("hemem")
    rng = np.random.default_rng(5)
    cfgs = [scale_config("hemem", c, wl.scale) for c in
            [space.default_config()] + [space.sample(rng)
                                        for _ in range(B - 1)]]
    return (cfgs, _epoch_consts(wl, "hemem", machine, PAGE_BYTES),
            _fast_capacity(wl, 8.0, None))


def _run(wl, **kw):
    cfgs, const, fast_cap = _setup(wl)
    return engine_jax.run_epochs(wl, "hemem", cfgs, const, fast_cap,
                                 PAGE_BYTES, [0, 1, 2], "elementwise", **kw)


def _assert_same(a, b):
    for k in OUT_KEYS:
        assert np.array_equal(a[k], b[k]), k


def _is_cached(wl):
    tr = getattr(wl, "_trace", None)
    return tr is not None and tr.epoch_access == wl.epoch_access


def _forget(wl):
    vars(wl).pop("_trace", None)


def test_miss_hit_and_cleared_runs_are_bitwise_equal():
    wl = _wl()
    assert not _is_cached(wl)
    miss = _run(wl)
    assert _is_cached(wl)
    hit = _run(wl)
    _forget(wl)
    assert not _is_cached(wl)
    again = _run(wl)
    _assert_same(miss, hit)
    _assert_same(miss, again)
    for k in ("trace_reads", "trace_writes"):
        assert np.array_equal(miss[k], again[k])


def test_cached_trace_equals_a_launch_fed_host_arrays():
    """A fresh numpy build of the trace, handed to the same compiled run
    as host arrays, gives the cached path's trace and results."""
    wl = _wl()
    out = _run(wl)
    reads, writes = (np.stack(a).astype(np.float32) for a in zip(
        *[wl.epoch_access(e) for e in range(wl.n_epochs)]))
    assert out["trace_reads"].dtype == np.float32
    assert np.array_equal(out["trace_reads"], reads)
    assert np.array_equal(out["trace_writes"], writes)
    cfgs, const, fast_cap = _setup(wl)
    from repro.kernels import ops
    edef, run = engine_jax._get_compiled(
        "hemem", B, wl.n_pages, wl.n_epochs, fast_cap, "elementwise",
        wl.scale, PAGE_BYTES, False, ops.select_path())
    kv = edef.knobs(cfgs)
    carry = engine_jax.init_carry(
        edef, kv, engine_jax.base_keys([0, 1, 2], 0, False),
        np.full(B, wl.epoch_ms, np.float32))
    _, outs = run(kv, reads, writes,
                  {k: np.float32(v) for k, v in const.items()}, carry,
                  np.arange(wl.n_epochs, dtype=np.int32))
    for key, arr in zip(OUT_KEYS, outs):
        assert np.array_equal(np.asarray(arr), out[key]), key


def test_segments_from_a_warm_cache_equal_one_run():
    wl = _wl()
    whole = _run(wl)
    assert _is_cached(wl)
    k = 23
    first = _run(wl, epoch_stop=k, return_carry=True)
    second = _run(wl, epoch_start=k, carry=first["carry"])
    for key in OUT_KEYS:
        assert np.array_equal(
            np.concatenate([first[key], second[key]]), whole[key]), key
    assert np.array_equal(second["trace_reads"], whole["trace_reads"][k:])
    assert np.array_equal(first["trace_writes"], whole["trace_writes"][:k])


def test_replaced_epoch_access_misses():
    wl = _wl()
    base = _run(wl)

    def doubled(e, f=wl.epoch_access):
        r, w = f(e)
        return 2 * r, 2 * w

    other = dataclasses.replace(wl, epoch_access=doubled)
    assert not _is_cached(other)
    out = _run(other)
    assert np.array_equal(out["trace_reads"], 2 * base["trace_reads"])
    assert not np.array_equal(out["wall_ms"], base["wall_ms"])
    # the same object with a new callable misses too
    wl.epoch_access = doubled
    assert not _is_cached(wl)
    _assert_same(_run(wl), out)


def test_dropped_workload_frees_its_entry():
    wl = _wl()
    _run(wl)
    reads = weakref.ref(wl._trace.reads)
    del wl
    gc.collect()
    assert reads() is None


def test_python_loop_keeps_the_trace_on_the_host():
    wl = _wl()
    loop = _run(wl, python_loop=True)
    assert _is_cached(wl) and wl._trace.reads_d is None
    scanned = _run(wl)
    assert wl._trace.reads_d is not None
    for k in OUT_KEYS:   # the scan-against-loop contract of test_jax_backend
        assert np.allclose(scanned[k], loop[k], rtol=1e-5, atol=1e-5), k


def test_returned_trace_is_read_only():
    out = _run(_wl())
    for k in ("trace_reads", "trace_writes"):
        assert not out[k].flags.writeable
        with pytest.raises(ValueError):
            out[k][0, 0] = 1.0


@pytest.mark.parametrize("cooling_pages", [16, 24, 64, 1000],
                         ids=["divides", "does-not-divide", "equals-n",
                              "above-n"])
def test_cooling_sweep_by_page_ranges_equals_the_chunk_formula(
        cooling_pages):
    """HeMem's knobs are ``(B,)`` vectors only; the sweep's page ranges
    equal the per-page chunk formula ``(j // p - m0) % M < r`` for every
    start chunk ``m0`` and count ``r``."""
    n = 64
    edef = engine_jax._HeMemDef(2, n, 8, "elementwise")
    cfgs = [HEMEM_SPACE.default_config(),
            dict(HEMEM_SPACE.default_config(), cooling_pages=cooling_pages)]
    kv = edef.knobs(cfgs)
    assert all(np.shape(v) == (2,) for v in kv.values())
    p = min(cooling_pages, n)
    M = -(-n // p)
    assert kv["cool_pages"][1] == p and kv["M"][1] == M
    m0, r = (a.ravel().astype(np.int32) for a in np.meshgrid(
        np.arange(M), np.arange(M)))
    rows = len(m0)
    full = lambda v: np.full(rows, v, np.int32)  # noqa: E731
    got = jax.jit(engine_jax._sweep_extra, static_argnums=0)(
        n, full(p), m0, r, full(M))
    cj = np.arange(n, dtype=np.int32)[None, :] // p
    want = (cj - m0[:, None]) % M < r[:, None]
    assert np.array_equal(np.asarray(got), want)


def test_hit_launch_ships_no_per_page_bytes(monkeypatch):
    got = []

    class recorded(spans.span):
        __slots__ = ()

        def count(self, **counts):
            got.append((self.name, counts))
            super().count(**counts)

    monkeypatch.setattr(spans, "span", recorded)
    h2d = {}
    for scale in (0.02, 0.04):
        wl = _wl(scale)
        _run(wl)
        del got[:]
        _run(wl)
        assert ("repro.sim.trace", {"cache_hit": 1}) in got
        h2d[wl.n_pages], = [c["h2d_bytes"] for name, c in got
                            if name == "repro.sim.launch"]
    (n1, b1), (n2, b2) = sorted(h2d.items())
    assert n2 > n1
    assert b1 == b2 < 64 * 1024
