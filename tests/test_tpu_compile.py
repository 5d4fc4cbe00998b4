"""Compiles of the main path for a TPU v5e chip that is described, not
attached.

The TPU's compiler is installed with jax, so these tests hand it the
Pallas kernels and the compiled epoch loop at their real widths: what the
chip's compiler refuses (a block not aligned to the (8, 128) tile, more
scoped VMEM than a kernel may use) fails here instead of on the chip.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and pytest-xdist
workers that collected different tests would run none.  Keep these tests in
this one file, so one worker loads the library.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

#: Gemma 2 9B's KV page (42 layers, 16 tokens, 8 KV heads, head_dim 256)
GEMMA2_PAGE = (42, 16, 8, 256)
#: pages of the serving smoke geometry: 192 HBM and 8 x 64 host, plus one
#: dump row each
HBM_POOL, HOST_POOL = 193, 513
#: the paper-size GUPS trace: 64.03 GiB at 2 MiB pages
GUPS_PAGES = 32783


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent compilation cache off: a
    compile for a described chip is written there but cannot be read
    back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,n", [(8, GUPS_PAGES), (1, GUPS_PAGES),
                                 (8, 65535)])
def test_select_topk_compiles(one_chip, B, n):
    from repro.kernels.select_topk import select_topk
    rows = [_shape(one_chip, (B, n), t)
            for t in (bool, jnp.float32, bool, jnp.float32)]
    counts = [_shape(one_chip, (B,), jnp.float32)] * 2
    compiled = select_topk.lower(*rows, *counts, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dst,src,dtype", [
    ((HBM_POOL,) + GEMMA2_PAGE, (HOST_POOL,) + GEMMA2_PAGE, jnp.bfloat16),
    ((HOST_POOL,) + GEMMA2_PAGE, (HBM_POOL,) + GEMMA2_PAGE, jnp.bfloat16),
    ((512, 16384), (512, 16384), jnp.bfloat16),
    ((64, 128), (64, 128), jnp.float32),
])
def test_page_migrate_compiles_in_place(one_chip, dst, src, dtype):
    from repro.kernels.page_migrate import page_migrate
    ids = _shape(one_chip, (192,), jnp.int32)
    compiled = page_migrate.lower(
        _shape(one_chip, dst, dtype), _shape(one_chip, src, dtype), ids,
        ids, interpret=False).compile()
    # the donated destination pool is updated in place, not copied
    pool_bytes = int(np.prod(dst)) * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


def test_paged_attention_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention
    _, page, kv, d = GEMMA2_PAGE
    pool = _shape(one_chip, (HBM_POOL, page, kv, d), jnp.bfloat16)
    compiled = paged_attention.lower(
        _shape(one_chip, (8, 16, d), jnp.bfloat16), pool, pool,
        _shape(one_chip, (8, 64), jnp.int32),
        _shape(one_chip, (8,), jnp.int32), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_epoch_loop_compiles_with_pallas_selection(one_chip, monkeypatch):
    """The hemem epoch loop at B=8 over the paper-size GUPS trace, with
    selection through the Pallas kernel compiled for the chip."""
    from repro.core import engine_jax as ej
    from repro.core.knobs import get_space
    from repro.core.simulator import (PAGE_BYTES, _epoch_consts,
                                      _fast_capacity, get_machine,
                                      scale_config)
    from repro.core.workloads import make_workload
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret", lambda: False)
    assert ej.have_jax()
    machine = get_machine("pmem-large")
    wl = make_workload("gups", "8GiB-hot", threads=machine.default_threads,
                       scale=1.0)
    assert wl.n_pages == GUPS_PAGES
    B, n, E = 8, wl.n_pages, wl.n_epochs
    space = get_space("hemem")
    rng = np.random.default_rng(0)
    cfgs = [scale_config("hemem", c, wl.scale) for c in
            [space.default_config()] + [space.sample(rng)
                                        for _ in range(B - 1)]]
    edef, run = ej._build_run_fn(
        "hemem", B, n, E, _fast_capacity(wl, 8.0, None), "elementwise",
        wl.scale, PAGE_BYTES, False, "pallas")
    kv = edef.knobs(cfgs)
    carry = ej.init_carry(edef, kv, ej.base_keys([0] * B, 0, False),
                          np.full(B, wl.epoch_ms, np.float32))
    const = {k: np.float32(v) for k, v in
             _epoch_consts(wl, "hemem", machine, PAGE_BYTES).items()}
    args = (kv, np.zeros((E, n), np.float32), np.zeros((E, n), np.float32),
            const, carry, np.arange(E, dtype=np.int32))
    shapes = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, np.shape(a), a.dtype), args)
    compiled = jax.jit(run).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serving_decode_compiles(one_chip, monkeypatch):
    """The fused serving decode step at the Command R+ tight geometry (B=8,
    128 pages a sequence, 96 HBM pages, 64 layers x 8 KV heads x 128 dims),
    with paged attention through the Pallas kernel, keeps the kernel under
    the name the benchmark's trace reader looks up."""
    from repro.core.serving_jax import CompiledServing
    from repro.core.tiered_kv import KVSpec
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FORCE", "pallas")
    monkeypatch.setattr(ops, "interpret", lambda: False)
    spec = KVSpec(n_layers=64, kv_heads=8, head_dim=128, page_tokens=16,
                  dtype=jnp.bfloat16)
    srv = CompiledServing(spec, 8, 128, 96)
    state = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(srv.fresh_state))
    kv_new = _shape(one_chip, (8, 64, 8, 128), jnp.bfloat16)
    compiled = srv._decode_fn.lower(
        state, kv_new, kv_new, _shape(one_chip, (8, 96, 128), jnp.bfloat16),
        _shape(one_chip, (8,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%paged_attention" in text
