"""Pallas TPU page-migration kernel: the tiering engine's datapath.

Executes one migration plan (promote + demote lists) as a sequence of
page-sized DMAs between the two pools.  Both pools stay where XLA put them
(``pl.ANY``, i.e. HBM); the page ids are scalar-prefetched into SMEM and
each valid ``(dst, src)`` pair issues one ``src_pool[src] -> dst_pool[dst]``
copy.  Nothing is staged through VMEM, so the page size is bounded by the
pools, not by the scoped VMEM limit (a Gemma-2-9B KV page — 42 layers x 16
tokens x 8 heads x 256 dims in bf16 — is 2.6 MiB, more than a
double-buffered VMEM block may hold).

A page is one index of the pools' leading axis: pools are ``(P, *page)``
arrays of any rank, and a DMA moves the whole ``page`` slab.  Keep the page
axes in the pool's own shape rather than flattening them to
``(P, page_elems)``: the flattening is a relayout copy of the whole pool on
a TPU.

On a real system the source pool rows live in host memory and arrive via DMA;
here both pools are device arrays and the kernel is the device-side half of
the copy (the host side is jax.device_put with donation, see
core/tiered_kv.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dst_ids, src_ids, src_hbm, dst_in_hbm, dst_hbm, sem):
    del dst_in_hbm  # aliased to dst_hbm: untouched pages keep their data

    def body(i, carry):
        d = dst_ids[i]
        s = src_ids[i]

        @pl.when((d >= 0) & (s >= 0))
        def _copy():
            cp = pltpu.make_async_copy(src_hbm.at[s], dst_hbm.at[d], sem)
            cp.start()
            cp.wait()

        return carry

    lax.fori_loop(0, dst_ids.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def page_migrate(dst_pool, src_pool, dst_ids, src_ids, *,
                 interpret: bool = True):
    """dst/src_pool: ``(P, *page)`` with equal page shapes; ids: ``(N,)``
    int32, -1 = no-op.  Copies ``src_pool[src_ids[i]]`` to
    ``dst_pool[dst_ids[i]]`` in order and returns the updated dst_pool
    (buffer donated and aliased)."""
    if dst_pool.ndim == 2:
        # one row of a 2-D pool is a slice of the tiled sublane axis, which
        # a DMA cannot address; give each page tile-shaped axes of its own
        P, E = dst_pool.shape
        page = (E // 128, 128) if E % 128 == 0 else (1, E)
        out = page_migrate(dst_pool.reshape((P,) + page),
                           src_pool.reshape((src_pool.shape[0],) + page),
                           dst_ids, src_ids, interpret=interpret)
        return out.reshape(P, E)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="page_migrate",
    )(dst_ids.astype(jnp.int32), src_ids.astype(jnp.int32),
      src_pool.astype(dst_pool.dtype), dst_pool)
