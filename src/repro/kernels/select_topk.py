"""Pallas TPU exact top-k page-selection kernel: the migration planner's sort.

Every tiering engine's ``plan`` step reduces to the same primitive: given a
candidate mask and a per-page priority, pick the top ``n_promote`` hottest
promotion candidates and the top ``n_demote`` coldest demotion candidates,
breaking priority ties by page index exactly like the numpy reference's
stable sorts.  The compiled jax epoch loop used to approximate this with
8-bit log-quantized priorities (exact *counts*, near-exact order); this
kernel removes the approximation: selection is a radix-select over the full
**(priority, index)** key, bit-exact against ``np.argsort(kind="stable")``.

Per batch row (one grid step) the kernel runs three phases, all expressed as
compare + reduce passes over the row (no dense sort, no data movement):

1. **priority cutoff** — a 32-step bitwise binary search per side finds the
   k-th best order-preserving float bit pattern (promotions descend,
   demotions ascend via complemented bits);
2. **strict set** — pages strictly better than the cutoff are all selected;
3. **boundary tier** — among pages *equal* to the cutoff, a 17-step bitwise
   search over descending-index weights picks the remaining
   ``k - |strict|`` pages with the smallest indices — numpy's stable
   tie-break, exactly.

Priorities must be NaN-free; every engine's priorities are nonnegative
sample counts/rates, and the conformance suite (``tests/test_select_topk``)
pins both this kernel and the pure-jnp fallback (:func:`repro.kernels.ref.
select_topk_ref`) to the numpy stable-sort reference bit-for-bit.

The kernel's grid runs over batch rows.  Each program holds one row of
packed keys in VMEM, padded to a multiple of 8 x 128 and laid out as
``(n_pad // 128, 128)`` so it fills whole (8, 128) vector tiles (keys +
masks, double-buffered, 2 MiB at the backend's 64k-page ceiling); the
batch axis of each block is squeezed, and the per-row selection counts
are scalar-prefetched into SMEM.  On CPU it runs in interpret mode (CI); on
TPU the compare/reduce passes map onto VPU lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bits of the index weight searched in phase 3 (page index < 2**16 by the
#: jax backend's page ceiling; padding can push the weight to 2**16, so one
#: extra bit)
_IDX_BITS = 17


def order_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Map float32 to uint32 preserving total order (NaN-free inputs):
    larger float <=> larger unsigned bit pattern."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where((bits >> 31) == 0, bits | np.uint32(1 << 31), ~bits)


def pack_keys(p_mask, p_heat, d_mask, d_heat):
    """Selection keys: 0 marks a non-candidate; candidates map their heat to
    order-preserving bits, complemented on the demote side so 'colder'
    ranks higher.  Candidate keys are always nonzero (heat is a NaN-free
    float, so its order bits never reach the complement's zero)."""
    vp = jnp.where(p_mask, order_bits(p_heat), np.uint32(0))
    vd = jnp.where(d_mask, ~order_bits(d_heat), np.uint32(0))
    return vp, vd


#: lanes of a TPU vector register; each row's keys are laid out as
#: ``(n_pad // LANES, LANES)`` so a row fills whole (8, 128) vreg tiles
LANES = 128
#: row-length granule: ``n_pad`` is a multiple of 8 x 128, so the
#: second-minor block dimension is a multiple of the 8-sublane tile
_ROW_GRANULE = 8 * LANES


def _kernel(kp_ref, kd_ref, vp_ref, vd_ref, pm_ref, dm_ref):
    b = pl.program_id(0)
    vp = vp_ref[...]                       # (rows, LANES) uint32 keys
    vd = vd_ref[...]
    kp = kp_ref[b]                         # per-row selection counts (f32)
    kd = kd_ref[b]

    def count_ge(v, t):
        # counts stay < 2**24, exact in f32
        return jnp.sum((v >= t).astype(jnp.float32))

    # phase 1: dual bitwise search for each side's k-th best key
    tp = jnp.uint32(0)
    td = jnp.uint32(0)
    for i in range(31, -1, -1):
        bit = np.uint32(1 << i)
        tp = jnp.where(count_ge(vp, tp | bit) >= kp, tp | bit, tp)
        td = jnp.where(count_ge(vd, td | bit) >= kd, td | bit, td)

    # phase 2: everything strictly better than the cutoff is selected
    strict_p = vp > tp
    strict_d = vd > td
    bound_p = (vp == tp) & (vp > 0)        # v > 0 excludes non-candidates
    bound_d = (vd == td) & (vd > 0)
    take_p = kp - jnp.sum(strict_p.astype(jnp.float32))
    take_d = kd - jnp.sum(strict_d.astype(jnp.float32))

    # phase 3: fill from the boundary tier in page-index order — a second
    # bitwise search over descending-index weights (weights are distinct,
    # so the take-th largest threshold selects exactly `take` pages)
    rows = vp.shape[0]
    idx = (lax.broadcasted_iota(jnp.int32, vp.shape, 0) * LANES
           + lax.broadcasted_iota(jnp.int32, vp.shape, 1))
    iv = lax.bitcast_convert_type(rows * LANES - idx, jnp.uint32)
    wp = jnp.where(bound_p, iv, np.uint32(0))
    wd = jnp.where(bound_d, iv, np.uint32(0))
    sp = jnp.uint32(0)
    sd = jnp.uint32(0)
    for i in range(_IDX_BITS - 1, -1, -1):
        bit = np.uint32(1 << i)
        sp = jnp.where(count_ge(wp, sp | bit) >= take_p, sp | bit, sp)
        sd = jnp.where(count_ge(wd, sd | bit) >= take_d, sd | bit, sd)

    pm = strict_p | (bound_p & (wp >= sp) & (take_p > 0))
    dm = strict_d | (bound_d & (wd >= sd) & (take_d > 0))
    pm_ref[...] = (pm & (kp > 0)).astype(jnp.int32)
    dm_ref[...] = (dm & (kd > 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def select_topk(p_mask, p_heat, d_mask, d_heat, n_promote, n_demote, *,
                interpret: bool = True):
    """Exact top-``n_promote`` (by ``p_heat`` desc) and top-``n_demote``
    (by ``d_heat`` asc) selection masks, ties by page index ascending.

    All array arguments are ``(B, n)`` (masks bool, heats float,
    ``n_promote``/``n_demote`` ``(B,)`` integer-valued floats); returns two
    ``(B, n)`` bool masks bit-identical to the numpy stable-sort reference.
    """
    B, n = p_mask.shape
    vp, vd = pack_keys(p_mask, p_heat, d_mask, d_heat)
    n_pad = -(-n // _ROW_GRANULE) * _ROW_GRANULE
    if n_pad != n:  # padding keys are 0 == non-candidate
        vp = jnp.pad(vp, ((0, 0), (0, n_pad - n)))
        vd = jnp.pad(vd, ((0, 0), (0, n_pad - n)))
    rows = n_pad // LANES
    vp = vp.reshape(B, rows, LANES)
    vd = vd.reshape(B, rows, LANES)
    kp = jnp.floor(n_promote.astype(jnp.float32)).reshape(B)
    kd = jnp.floor(n_demote.astype(jnp.float32)).reshape(B)
    row = pl.BlockSpec((None, rows, LANES), lambda b, kp, kd: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row, row],
        out_specs=[row, row],
    )
    pm, dm = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((B, rows, LANES), jnp.int32)],
        interpret=interpret,
        name="select_topk",
    )(kp, kd, vp, vd)
    return (pm.reshape(B, n_pad)[:, :n] != 0,
            dm.reshape(B, n_pad)[:, :n] != 0)
