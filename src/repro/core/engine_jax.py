"""Compiled JAX epoch loop: the ``backend="jax"`` fast path of the simulator.

This module re-implements the five batch tiering engines
(:mod:`repro.core.engine`) and the monitoring samplers as **pure functions**
over a pytree of ``(B, n_pages)`` arrays, and drives them with one
``jax.lax.scan`` over epochs — observe (fused Poisson/Bernoulli sampling),
plan (packed-key selection of migration candidates), tier update and the
access-cost model all compile into a single XLA program per (engine,
workload shape).  The numpy engines remain the **bit-exact reference**: they
reproduce the historical per-page RNG streams exactly, while this path
trades stream compatibility for compilation — the *distributions* are
identical (tested statistically) but individual draws differ.

Randomness is **counter-based**: every monitoring draw is a deterministic
hash of ``(seed, batch row, epoch, draw site, page)`` — no sequential RNG
state threads through the scan, so the compiled loop, a Python epoch loop
over the same step function, and any sharding of the batch all produce
identical draws.  Setting ``crn=True`` (common random numbers,
``SimOptions(crn=True)``) drops the ``(seed_b, batch row)`` components in
favour of the batch-shared ``seeds[0]``: all B configs of a batch then see
*bitwise-identical* monitoring noise, which sharpens SMAC's within-batch
candidate comparisons (the paired-evaluation idea of the SMAC paper) at the
cost of correlated errors across the batch.

Performance notes (what made the compiled loop beat the numpy reference):

* Poisson draws fuse into the observe step as a branchless hybrid kernel —
  exact inverse-CDF below :data:`POISSON_SWITCH` (:data:`POISSON_KMAX`
  accumulated pmf terms), and above it a transcendental-free normal
  approximation whose standard normal comes from ``popcount`` of the hash
  word plus uniform smoothing (Box–Muller's log/cos are the slowest ops in
  an XLA CPU epoch).
* Migration-candidate selection avoids dense stable argsorts (the dominant
  cost of a naive port: ~13 ms per (8, 8k) argsort on CPU).
  :func:`select_top` dispatches to the exact top-k selection kernel
  (:mod:`repro.kernels.select_topk` on TPU / under ``FORCE="pallas"``,
  its pure-jnp oracle :func:`repro.kernels.ref.select_topk_ref`
  otherwise): a radix-select over the full (priority, index) key — dual
  bitwise cutoff search on order-preserving float bits plus an index-order
  boundary fill — whose selected index sets are **bit-identical** to the
  reference's stable sorts.  ``SimOptions(exact_select=False)`` restores
  the historical 8-bit log-quantized approximation
  (:func:`select_top_quantized`: exact counts, near-exact order) for
  ablations.
* DAMON's region probes reduce to ``Binomial(K, p̄)`` drawn as K masked
  Bernoullis — exactly the distribution of the numpy Monte-Carlo probe
  loop, for both sampler spellings.
* The first-touch allocation state is a single shared ``(n,)`` vector: the
  trace is shared across the batch, so rows allocate identically.

Jitted epoch functions are cached per ``(engine, n_pages, sampler)`` (plus
the remaining static shape parameters) so repeated ``Study.tune``
iterations never retrace; a one-line warning is logged when a new shape
forces a recompilation of an already-compiled engine.  Each workload's
epoch trace is built and copied to the device once (``_cached_trace``),
so a launch ships only ``(B,)`` knob vectors and scalars.
"""

from __future__ import annotations

import copy
import logging
import threading
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import numpy as np

from . import spans

log = logging.getLogger(__name__)

# jax is imported lazily on first use: merely importing this module (which
# repro.core.simulator does unconditionally) must not pull in jax — the
# numpy path stays jax-free, which also keeps the process-pool fork path
# available for numpy-only runs.
jax = None
jnp = None
lax = None
_HAVE_JAX: "bool | None" = None


def have_jax() -> bool:
    """Import jax on first call; False if it is not installed."""
    global jax, jnp, lax, _HAVE_JAX
    if _HAVE_JAX is None:
        try:
            import jax as _jax
            import jax.numpy as _jnp
            from jax import lax as _lax
            jax, jnp, lax = _jax, _jnp, _lax
            _HAVE_JAX = True
        except ImportError:  # pragma: no cover - env without jax
            _HAVE_JAX = False
    return _HAVE_JAX

#: the BUILTIN engines this module compiles end-to-end.  The live registry
#: is ``jax_engines()`` — custom engines join it through the lifted-engine
#: protocol (:func:`register_jax_engine`); anything not registered there
#: falls back to the numpy epoch loop (with the vmapped jax cost model).
JAX_ENGINES = ("hemem", "hmsdk", "memtis", "static", "oracle", "kv-hemem")
#: builtin sampler names the fused kernels cover.  "elementwise" and
#: "sparse" are *stream* variants of the same distribution in numpy, so the
#: compiled path implements them with one kernel.
JAX_SAMPLERS = ("elementwise", "sparse")

#: rate below which the fused Poisson kernel inverts the CDF exactly;
#: at/above it the popcount-normal approximation takes over
POISSON_SWITCH = 5.0
#: pmf terms accumulated by the inverse-CDF branch (tail mass beyond this
#: at lam < POISSON_SWITCH is < 1e-4)
POISSON_KMAX = 16

#: 1/sigma of (popcount(u32) - 16 + uniform - 0.5): sqrt(8 + 1/12)
_POPCOUNT_NORM = 1.0 / 2.8431203

# draw-site identifiers folded into the counter-based hash so distinct
# sampling sites never share uniforms
# (each draw also folds site+1 for its second hash word)
_S_READ = 0x11
_S_WRITE = 0x21
_S_PROBE = 0x31
_S_JITTER = 0x41


# ---------------------------------------------------------------------------
# Counter-based uniforms (lowbias32-style avalanche; works for numpy and jax
# uint32 arrays alike, which is what makes the draws backend-independent).
# ---------------------------------------------------------------------------
_GOLDEN = np.uint32(0x9E3779B9)
_MUL1 = np.uint32(0x7FEB352D)
_MUL2 = np.uint32(0x846CA68B)


def mix32(h):
    """Finalizing 32-bit avalanche (murmur3-style)."""
    h = h ^ (h >> 16)
    h = h * _MUL1
    h = h ^ (h >> 15)
    h = h * _MUL2
    h = h ^ (h >> 16)
    return h


def fold(h, w):
    """Fold word ``w`` into hash state ``h`` (boost::hash_combine-style);
    broadcasting shapes the output counter grid."""
    return mix32(h ^ (w + _GOLDEN + (h << 6) + (h >> 2)))


def counter_hash(key, *words):
    """Deterministic uint32 hash of ``key`` and the counter ``words`` (site
    id, epoch, page index, ...), broadcast over the inputs."""
    h = key
    for w in words:
        h = fold(h, w)
    return h


def hash_uniform(h):
    """Map a hash word to a float32 uniform in (0, 1)."""
    return ((h >> 8).astype(np.float32) + np.float32(0.5)) * \
        np.float32(1.0 / (1 << 24))


def counter_uniform(key, *words):
    """Counter-based uniforms in (0, 1): ``hash_uniform(counter_hash(...))``."""
    return hash_uniform(counter_hash(key, *words))


def base_keys(seeds: Sequence[int], batch_offset: int, crn: bool) -> np.ndarray:
    """Per-row base hash keys.

    ``crn=False``: fold ``(seed_b, global batch index)`` so equal-seed rows
    still draw independent noise (counter-based streams never diverge by
    consumption the way stateful RNGs do).  ``crn=True``: every row uses
    ``(seeds[0], 0)`` — all rows share every subsequent draw bitwise.
    """
    seeds = np.asarray(seeds, dtype=np.uint32)
    if crn:
        seeds = np.full_like(seeds, seeds[0])
        rows = np.zeros_like(seeds)
    else:
        rows = (np.arange(len(seeds)) + batch_offset).astype(np.uint32)
    h0 = np.full(len(seeds), 0xC0FFEE, dtype=np.uint32)
    return np.asarray(fold(fold(h0, seeds), rows), dtype=np.uint32)


# ---------------------------------------------------------------------------
# Fused samplers
# ---------------------------------------------------------------------------
def _poisson_from_hash(lam, h1, h2):
    """Branchless Poisson(lam) from two hash words per element.

    ``lam < POISSON_SWITCH``: exact inverse-CDF on ``uniform(h1)``.
    Larger rates: normal approximation ``floor(lam + sqrt(lam) z + 1/2)``
    with ``z`` from popcount(h1) + uniform(h2) smoothing — mean/variance
    match Poisson to O(1/12); no transcendentals beyond one ``exp``.
    """
    u1 = hash_uniform(h1)
    lam_s = jnp.minimum(lam, POISSON_SWITCH)
    pmf = jnp.exp(-lam_s)
    cdf = pmf
    k = (u1 > cdf).astype(jnp.float32)
    for i in range(1, POISSON_KMAX):
        pmf = pmf * (lam_s / np.float32(i))
        cdf = cdf + pmf
        k = k + (u1 > cdf)
    z = (lax.population_count(h1).astype(jnp.float32) - np.float32(16.0)
         + hash_uniform(h2) - np.float32(0.5)) * np.float32(_POPCOUNT_NORM)
    normal = jnp.maximum(0.0, jnp.floor(lam + jnp.sqrt(lam) * z + 0.5))
    return jnp.where(lam < POISSON_SWITCH, k, normal)


def _as_u32(epoch):
    return epoch.astype(jnp.uint32) if hasattr(epoch, "astype") \
        else np.uint32(epoch)


def monitor_draw(keys, epoch, site, base, period):
    """Fused PEBS monitoring draw: Poisson(base / period) for every page of
    every batch row, from counter-based hashes keyed by
    ``(row key, site, epoch, page)``."""
    n = base.shape[-1]
    pages = np.arange(n, dtype=np.uint32)[None, :]
    e = _as_u32(epoch)
    h1 = counter_hash(keys[:, None], np.uint32(site), e, pages)
    h2 = counter_hash(keys[:, None], np.uint32(site + 1), e, pages)
    lam = base[None, :].astype(jnp.float32) / period[:, None]
    return _poisson_from_hash(lam, h1, h2)


def monitor_draw2(keys, epoch, reads, writes, sp, wsp):
    """Both monitoring draws (load + store PEBS sites); returns
    ``(sampled_reads, sampled_writes)``.  Two separate (B, n) kernels fuse
    better under XLA CPU than one concatenated (2B, n) kernel."""
    sr = monitor_draw(keys, epoch, _S_READ, reads, sp)
    sw = monitor_draw(keys, epoch, _S_WRITE, writes, wsp)
    return sr, sw


# ---------------------------------------------------------------------------
# Migration-plan top-k selection.  select_top() dispatches between the exact
# (priority, index) radix-select kernel (repro.kernels.select_topk / its
# pure-jnp ref — bit-exact vs the numpy stable sorts) and the historical
# 8-bit log-quantized approximation kept for ablations
# (select_top_quantized: exact counts, near-exact order).
# ---------------------------------------------------------------------------
#: selection implementations select_top() can dispatch to
SELECT_MODES = ("pallas", "ref", "quantized")


def select_top(p_mask, p_heat, d_mask, d_heat, n_promote, n_demote,
               mode: "str | None" = None):
    """Top-``n_promote`` (by ``p_heat`` desc) and top-``n_demote`` (by
    ``d_heat`` asc) selection masks for a ``(B, n)`` batch.

    ``mode`` picks the implementation: ``"pallas"`` (the Pallas kernel,
    interpret mode off-TPU), ``"ref"`` (its pure-jnp oracle) — both
    bit-exact against the numpy reference's stable sorts, ties by page
    index — or ``"quantized"`` (the historical 8-bit log-quantized
    approximation; exact counts only).  ``None`` resolves through
    :func:`repro.kernels.ops.select_path`, honouring the kernels layer's
    ``FORCE`` switch.
    """
    if mode == "quantized":
        return select_top_quantized(p_mask, p_heat, d_mask, d_heat,
                                    n_promote, n_demote)
    if mode in (None, "pallas", "ref"):
        from ..kernels import ops as kernel_ops
        return kernel_ops.select_topk(p_mask, p_heat, d_mask, d_heat,
                                      n_promote, n_demote, mode=mode)
    raise ValueError(f"unknown selection mode {mode!r}; "
                     f"expected one of {SELECT_MODES}")


# ---------------------------------------------------------------------------
# Quantized selection (ablation path): dual bitwise cutoff search over
# log-quantized priorities + one blocked prefix-sum for the cutoff tiers.
# ---------------------------------------------------------------------------
def _quantize(heat, qbits: int):
    """Per-row LOG-scale quantization of nonnegative priorities into
    [0, 2**qbits - 1].  Log spacing preserves the ordering of magnitude
    classes even when a few very hot pages dominate the linear scale (e.g.
    Silo's 1% hot pages are ~500x hotter than the warm tier — linear
    buckets would collapse warm vs cold into one tier and turn the
    demotion order into page-index order)."""
    lg = jnp.log2(1.0 + heat)
    hi = jnp.max(lg, axis=-1, keepdims=True)
    q = lg * (np.float32((1 << qbits) - 1) / jnp.maximum(hi, 1e-30))
    return q.astype(jnp.uint32)


#: quantized-priority width of the selection search (order within
#: collisions falls back to page-index order; selection counts stay exact)
_SEL_QBITS = 8
#: block width of the matmul prefix-sum (see :func:`_blocked_cumsum`)
_CS_BLOCK = 64


def _blocked_cumsum(x):
    """Inclusive cumsum along the last axis of a (B, n) uint32 array whose
    values may pack two 16-bit counters (so row totals stay < 2**32).

    XLA CPU lowers ``jnp.cumsum`` over the minor axis to a scalar chain
    (~0.7 ms at epoch-loop shapes); a block-local cumsum expressed as a
    GEMM against a lower-triangular ones matrix plus a short cross-block
    prefix is several times faster.  Block-local sums stay below the f32
    integer range (64 * 2**16 < 2**24), so the GEMM is exact; cross-block
    accumulation happens in uint32.
    """
    B, n = x.shape
    blk = _CS_BLOCK
    pad = (-n) % blk
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    nb = (n + pad) // blk
    tri = jnp.asarray(np.tril(np.ones((blk, blk), np.float32)))
    t = xp.reshape(B * nb, blk).astype(jnp.float32)
    # HIGHEST: a TPU's default precision would round the packed counters
    # to bf16's 8-bit mantissa
    within = jnp.matmul(t, tri.T, precision=lax.Precision.HIGHEST
                        ).astype(jnp.uint32)      # block-local inclusive
    within = within.reshape(B, nb, blk)
    totals = within[:, :, -1]
    offsets = jnp.cumsum(totals, axis=-1) - totals  # exclusive, (B, nb)
    out = (within + offsets[:, :, None]).reshape(B, -1)
    return out[:, :n] if pad else out


def _count_ge(v, t, ones):
    """Per-row count of ``v >= t`` via a dot product (XLA CPU's reductions
    of predicates are scalar; its GEMV is vectorized)."""
    return (v >= t).astype(jnp.float32) @ ones


def select_top_quantized(p_mask, p_heat, d_mask, d_heat, n_promote,
                         n_demote):
    """Approximate top-k selection masks over log-quantized priorities —
    the ablation path behind ``SimOptions(exact_select=False)``.

    Priorities quantize to :data:`_SEL_QBITS` bits; a dual bitwise binary
    search finds each side's cutoff priority (the k-th best), and one
    packed cumulative sum takes the exact remainder from the cutoff tier in
    page-index order.  Selection *counts* are therefore exact (capacity and
    rate caps hold precisely); only the order among pages whose priority
    collides within the quantization differs from the reference's stable
    sorts (ties there break by page index too).  ~9 fused compare-count
    passes and one blocked cumsum; the exact kernel replaces this as the
    default (see :func:`select_top`).
    """
    n = p_mask.shape[-1]
    ones = jnp.ones(n, jnp.float32)
    kp = n_promote.astype(jnp.float32)[:, None]
    kd = n_demote.astype(jnp.float32)[:, None]
    qmax = np.uint32((1 << _SEL_QBITS) - 1)
    # candidate priority in [1, qmax+1], 0 = not a candidate; larger = picked
    # earlier (promotions: hottest first; demotions: coldest first)
    vp = jnp.where(p_mask, _quantize(p_heat, _SEL_QBITS) + np.uint32(1),
                   np.uint32(0))
    vd = jnp.where(d_mask, (qmax - _quantize(d_heat, _SEL_QBITS))
                   + np.uint32(1), np.uint32(0))
    tp = jnp.zeros((kp.shape[0], 1), dtype=jnp.uint32)
    td = jnp.zeros((kd.shape[0], 1), dtype=jnp.uint32)
    for i in range(_SEL_QBITS, -1, -1):  # cutoff = k-th best priority value
        bit = np.uint32(1 << i)
        cp = _count_ge(vp, tp | bit, ones)[:, None]
        cd = _count_ge(vd, td | bit, ones)[:, None]
        tp = jnp.where(cp >= kp, tp | bit, tp)
        td = jnp.where(cd >= kd, td | bit, td)
    strict_p = vp > tp
    strict_d = vd > td
    bound_p = p_mask & (vp == tp)
    bound_d = d_mask & (vd == td)
    take_p = kp - (strict_p.astype(jnp.float32) @ ones)[:, None]
    take_d = kd - (strict_d.astype(jnp.float32) @ ones)[:, None]
    # one packed cumsum resolves both boundary tiers in page-index order
    cs = _blocked_cumsum(bound_p.astype(jnp.uint32)
                         + (bound_d.astype(jnp.uint32) << np.uint32(16)))
    pmask = strict_p | (bound_p & ((cs & np.uint32(0xFFFF)).astype(jnp.float32)
                                   <= take_p))
    dmask = strict_d | (bound_d & ((cs >> np.uint32(16)).astype(jnp.float32)
                                   <= take_d))
    return pmask & (kp > 0), dmask & (kd > 0)


def kth_largest(values, k: int):
    """Exact k-th largest value per row (k static, 1-based ... actually the
    value at ascending-sorted position ``n - 1 - k`` like ``np.partition``),
    via binary search on the order-preserving bit pattern — no dense sort."""
    bits = lax.bitcast_convert_type(values.astype(jnp.float32), jnp.uint32)
    bits = jnp.where((bits >> 31) == 0, bits | np.uint32(1 << 31), ~bits)
    n = values.shape[-1]
    ones = jnp.ones(n, jnp.float32)
    want = np.float32(k + 1)  # count of elements >= result
    t = jnp.zeros(values.shape[:-1] + (1,), dtype=jnp.uint32)
    for i in range(31, -1, -1):
        cand = t | np.uint32(1 << i)
        cnt = _count_ge(bits, cand, ones)[:, None]
        t = jnp.where(cnt >= want, cand, t)
    t = t[..., 0]
    f = lax.bitcast_convert_type(
        jnp.where((t >> 31) != 0, t & np.uint32(0x7FFFFFFF), ~t), jnp.float32)
    return f


# ---------------------------------------------------------------------------
# Engine state + step functions.  Each engine contributes:
#   knobs(configs)  -> dict of per-config vectors / static arrays
#   init(kv)        -> state pytree of (B, ...) arrays
#   observe(...)    -> (state, samples (B,))
#   plan(...)       -> (state, promote_mask, demote_mask, overhead_ms)
# ---------------------------------------------------------------------------
def _knob_vec(configs, name, default=None, dtype=np.float32):
    vals = [c.get(name, default) if default is not None else c[name]
            for c in configs]
    return np.asarray(vals, dtype=dtype)


def _runs_update(credit, period, est_wall):
    credit = credit + est_wall
    runs = jnp.floor(credit / period).astype(jnp.int32)
    credit = credit - runs.astype(jnp.float32) * period
    return credit, runs


def _rate_pages(rate_gibs, est_wall, page_bytes):
    """Unscaled per-engine migration-rate cap (pages), int-truncated —
    mirrors ``migration_rate_pages(..., scale=1.0)``."""
    return jnp.floor(rate_gibs * np.float32(2 ** 30) * (est_wall / 1e3)
                     / page_bytes)


def _truncate_to_rate(n_promote, n_d, room, rate_pages):
    """The shared promotion/demotion rate-cap truncation every numpy engine
    applies: demotions free room first, promotions take what remains."""
    n_promote = n_promote.astype(jnp.float32)
    n_d = n_d.astype(jnp.float32)
    room = room.astype(jnp.float32)
    over = (n_promote + n_d) > rate_pages
    n_d2 = jnp.where(over, jnp.minimum(n_d, rate_pages), n_d)
    n_p2 = jnp.where(
        over,
        jnp.maximum(0.0, jnp.minimum(jnp.minimum(n_promote, room + n_d2),
                                     rate_pages - n_d2)),
        n_promote)
    return n_p2, n_d2


class _EngineDef:
    """Bundle of the pure functions defining one compiled engine — the
    **lifted-engine protocol**.

    A registered engine that also registers an ``_EngineDef`` subclass via
    :func:`register_jax_engine` gets the whole ``lax.scan``/jit/CRN/pmap
    machinery for free under ``backend="jax"`` instead of the warned
    numpy-epoch-loop fallback.  The contract (all methods pure — no Python
    side effects, jax ops only, shapes fixed by ``(B, n)``):

    ``knobs(configs) -> dict``
        Per-config knob vectors / static arrays from the B config dicts
        (numpy; traced as jit inputs, so new configs never retrace).  Must
        include ``"rate"`` (GiB/s migration cap; ``super().knobs`` provides
        it).
    ``init(kv) -> state``
        Initial engine-state pytree of ``(B, ...)`` arrays.
    ``observe(state, kv, keys, e, reads, writes, est_wall)
      -> (state, samples)``
        Fold one epoch of true per-page access counts into the monitoring
        state; ``samples`` is the per-row sampling volume ``(B,)`` the cost
        model charges.  Monitoring noise must come from the counter-based
        hashes (:func:`counter_uniform` keyed on ``keys``/``e``) so scan,
        eager replay and sharding agree bitwise.
    ``plan(state, kv, keys, e, reads, writes, in_fast, allocated,
      est_wall, max_pages) -> (state, promote_mask, demote_mask,
      overhead_ms)``
        One migration-thread step: boolean ``(B, n)`` selection masks
        (use :meth:`select` for exact rate-capped top-k) plus per-row
        kernel-overhead ms.

    Class attributes: ``plans = False`` skips ``plan`` entirely (static
    placement); ``zero_cost = True`` charges no migration bandwidth
    (oracle-style analysis).  The driver overwrites ``page_bytes`` with the
    workload's page granule before building the step.
    """

    zero_cost = False
    plans = True

    def __init__(self, B, n, fast_cap, sampler, select_mode: str = "ref"):
        self.B, self.n, self.fast_cap, self.sampler = B, n, fast_cap, sampler
        self.select_mode = select_mode
        self.page_bytes = np.float32(2 ** 21)  # overwritten by the driver

    def select(self, p_mask, p_heat, d_mask, d_heat, n_promote, n_demote):
        """Migration-plan top-k selection under this engine's configured
        implementation (see :func:`select_top`)."""
        return select_top(p_mask, p_heat, d_mask, d_heat, n_promote,
                          n_demote, mode=self.select_mode)

    def knobs(self, configs) -> Dict[str, np.ndarray]:
        return {"rate": _knob_vec(configs, "max_migration_rate", default=1e9)}

    def init(self, kv):
        return {}

    def observe(self, st, kv, keys, e, reads, writes, est_wall):
        return st, jnp.zeros(self.B, dtype=jnp.float32)

    def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
             est_wall, max_pages):
        none = jnp.zeros((self.B, self.n), dtype=bool)
        return st, none, none, jnp.zeros(self.B, dtype=jnp.float32)


class _StaticDef(_EngineDef):
    plans = False


class _OracleDef(_EngineDef):
    zero_cost = True

    def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
             est_wall, max_pages):
        heat = (reads + writes).astype(jnp.float32)  # clairvoyant knowledge
        alloc = jnp.broadcast_to(allocated[None, :] if allocated.ndim == 1
                                 else allocated, (self.B, self.n))
        n_alloc = alloc.sum(axis=-1)
        cap = jnp.minimum(self.fast_cap, n_alloc)
        # want = the `cap` hottest allocated pages (ties by index)
        heat_b = jnp.broadcast_to(heat[None, :], (self.B, self.n))
        none = jnp.zeros((self.B, self.n), bool)
        want, _ = self.select(alloc, heat_b, none, heat_b,
                              cap.astype(jnp.float32), jnp.zeros(self.B))
        prom_c = want & ~in_fast
        dem_c = ~want & in_fast
        free = self.fast_cap - in_fast.sum(axis=1)
        need = jnp.maximum(0, prom_c.sum(axis=1) - free)
        # index-order prefixes, like the reference's flatnonzero slices;
        # one packed blocked cumsum serves both sides
        cs = _blocked_cumsum(prom_c.astype(jnp.uint32)
                             + (dem_c.astype(jnp.uint32) << np.uint32(16)))
        cs_p = (cs & np.uint32(0xFFFF)).astype(jnp.int32)
        cs_d = (cs >> np.uint32(16)).astype(jnp.int32)
        d_sel = dem_c & (cs_d <= need[:, None])
        n_d = d_sel.sum(axis=1)
        p_sel = prom_c & (cs_p <= (free + n_d)[:, None])
        return st, p_sel, d_sel, jnp.zeros(self.B, dtype=jnp.float32)


class _HeMemDef(_EngineDef):
    COOL_UNIT_PAGES = 16.0

    def knobs(self, configs):
        kv = super().knobs(configs)
        kv.update(
            sp=_knob_vec(configs, "sampling_period"),
            wsp=_knob_vec(configs, "write_sampling_period"),
            read_hot=_knob_vec(configs, "read_hot_threshold"),
            write_hot=_knob_vec(configs, "write_hot_threshold"),
            period=_knob_vec(configs, "migration_period"),
            cool_pages=np.minimum(
                _knob_vec(configs, "cooling_pages", dtype=np.int32), self.n),
            hot_ring=_knob_vec(configs, "hot_ring_reqs_threshold",
                               dtype=np.int32),
            cold_ring=_knob_vec(configs, "cold_ring_reqs_threshold",
                                dtype=np.int32),
            trigger=np.maximum(
                _knob_vec(configs, "cooling_threshold") * self.n
                / self.COOL_UNIT_PAGES, 1.0).astype(np.float32),
        )
        p = kv["cool_pages"]
        # static per config: chunks per cooling sweep
        kv["M"] = ((self.n + p - 1) // p).astype(np.int32)
        return kv

    def init(self, kv):
        B, n = self.B, self.n
        z = jnp.zeros((B, n), dtype=jnp.float32)
        zb = jnp.zeros(B, dtype=jnp.float32)
        return {"rc": z, "wc": z, "cursor": jnp.zeros(B, dtype=jnp.int32),
                "since": zb, "credit": zb}

    def _draws(self, kv, keys, e, reads, writes):
        """Monitoring-noise hook: sampled (reads, writes), both ``(B, n)``.
        The default is the fused counter-based Poisson PEBS model;
        :class:`KVHeMemDef` overrides it with deterministic means."""
        return monitor_draw2(keys, e, reads, writes, kv["sp"], kv["wsp"])

    def observe(self, st, kv, keys, e, reads, writes, est_wall):
        sr, sw = self._draws(kv, keys, e, reads, writes)
        samples = jnp.sum(sr + sw, axis=-1)
        since = st["since"] + samples
        k = jnp.floor(since / kv["trigger"]).astype(jnp.int32)
        p = kv["cool_pages"]
        k_eff = k.astype(jnp.float32) * p.astype(jnp.float32) / self.n
        factor = jnp.where(
            k > 0, (2.0 - jnp.exp2(-k_eff)) / (k_eff + 1.0), 1.0)
        # the cooling sweep: chunk c_j = j // cooling_pages, M chunks per
        # sweep; k triggers from chunk m0 halve chunk c exactly
        # k//M + [ (c - m0) mod M < k mod M ] times — the closed form of
        # the reference's per-trigger cursor loop
        M = kv["M"]
        m0 = st["cursor"] // p
        halv = (k // M)[:, None] + _sweep_extra(self.n, p, m0, k % M, M)
        decay = jnp.exp2(-halv.astype(jnp.float32))
        rc = st["rc"] * decay + sr * factor[:, None]
        wc = st["wc"] * decay + sw * factor[:, None]
        st = dict(st, rc=rc, wc=wc,
                  cursor=((m0 + k) % M) * p,
                  since=since - k.astype(jnp.float32) * kv["trigger"])
        return st, samples

    def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
             est_wall, max_pages):
        credit, runs = _runs_update(st["credit"], kv["period"], est_wall)
        st = dict(st, credit=credit)
        run_row = runs > 0
        hot = (st["rc"] >= kv["read_hot"][:, None]) | \
            (st["wc"] >= kv["write_hot"][:, None])
        heat = st["rc"] + st["wc"]
        cand_p = hot & ~in_fast & allocated
        cand_d = ~hot & in_fast
        rate_pages = jnp.minimum(
            _rate_pages(kv["rate"], est_wall, self.page_bytes), max_pages)
        # counts first (selection masks are derived from ONE packed sort)
        n_p = jnp.minimum(cand_p.sum(axis=1), kv["hot_ring"] * runs)
        room = self.fast_cap - in_fast.sum(axis=1)
        watermark = max(1, self.fast_cap // 50)
        pressure = jnp.maximum(0, watermark - room)
        need = jnp.maximum(jnp.maximum(0, n_p - room), pressure)
        n_d = jnp.minimum(cand_d.sum(axis=1),
                          jnp.minimum(need, kv["cold_ring"] * runs))
        n_promote = jnp.minimum(n_p, room + n_d)
        n_p2, n_d2 = _truncate_to_rate(n_promote, n_d, room,
                                       jnp.maximum(0.0, rate_pages))
        gate = run_row.astype(jnp.float32)
        pmask, dmask = self.select(cand_p, heat, cand_d, heat,
                                   n_p2 * gate, n_d2 * gate)
        return st, pmask, dmask, jnp.zeros(self.B, dtype=jnp.float32)


def _sweep_extra(n, p, m0, r, M):
    """Pages that a cooling sweep halves once more than the rest: the ``r``
    chunks of ``p`` pages from chunk ``m0`` on, wrapping after chunk
    ``M - 1`` (the last chunk may be partial).  For page ``j`` this is
    ``(j // p - m0) % M < r``, taken as page ranges so that no per-page
    integer division runs in the scan.  Needs ``0 <= m0, r < M``."""
    j = jnp.arange(n, dtype=jnp.int32)[None, :]
    end = m0 + r
    wrap = end > M
    lo = (m0 * p)[:, None]
    hi = (jnp.where(wrap, end - M, end) * p)[:, None]
    return jnp.where(wrap[:, None], (j >= lo) | (j < hi), (j >= lo) & (j < hi))


class _MemtisDef(_EngineDef):
    KERNEL_MS_PER_PAGE = 0.02

    def knobs(self, configs):
        kv = super().knobs(configs)
        kv.update(
            sp=_knob_vec(configs, "sampling_period"),
            wsp=_knob_vec(configs, "write_sampling_period"),
            cool_period=_knob_vec(configs, "cooling_period_ms"),
            adapt_period=_knob_vec(configs, "adaptation_period_ms"),
            period=_knob_vec(configs, "migration_period"),
            warm=_knob_vec(configs, "warm_pct") / np.float32(100.0),
        )
        return kv

    def init(self, kv):
        B, n = self.B, self.n
        z = jnp.zeros((B, n), dtype=jnp.float32)
        zb = jnp.zeros(B, dtype=jnp.float32)
        return {"rc": z, "wc": z, "thr": jnp.full(B, 4.0, dtype=jnp.float32),
                "cool": zb, "adapt": zb, "credit": zb}

    def observe(self, st, kv, keys, e, reads, writes, est_wall):
        sr, sw = monitor_draw2(keys, e, reads, writes, kv["sp"], kv["wsp"])
        rc = st["rc"] + sr
        wc = st["wc"] + sw
        samples = jnp.sum(sr + sw, axis=-1)
        cool_c = st["cool"] + est_wall
        cool = cool_c >= kv["cool_period"]
        cool_c = jnp.where(cool, 0.0, cool_c)
        rc = jnp.where(cool[:, None], rc * 0.5, rc)
        wc = jnp.where(cool[:, None], wc * 0.5, wc)
        adapt_c = st["adapt"] + est_wall
        adapt = adapt_c >= kv["adapt_period"]
        adapt_c = jnp.where(adapt, 0.0, adapt_c)
        # smallest threshold whose hot set fits the fast tier: the value at
        # ascending position n-1-k of the heat row (np.partition semantics)
        part = kth_largest(rc + wc, min(self.fast_cap, self.n - 1))
        thr = jnp.where(adapt, jnp.maximum(part, 1.0), st["thr"])
        st = dict(st, rc=rc, wc=wc, thr=thr, cool=cool_c, adapt=adapt_c)
        return st, samples

    def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
             est_wall, max_pages):
        credit, runs = _runs_update(st["credit"], kv["period"], est_wall)
        st = dict(st, credit=credit)
        run_row = runs > 0
        heat = st["rc"] + st["wc"]
        hot = heat >= st["thr"][:, None]
        warm = ~hot & (heat >= (st["thr"] * (1.0 - kv["warm"]))[:, None])
        cand_p = hot & ~in_fast & allocated
        cand_d = in_fast & ~hot & ~warm
        rate_pages = jnp.minimum(
            _rate_pages(kv["rate"], est_wall, self.page_bytes), max_pages)
        n_p = cand_p.sum(axis=1)
        room = self.fast_cap - in_fast.sum(axis=1)
        need = jnp.maximum(
            0.0, jnp.minimum(n_p.astype(jnp.float32), rate_pages) - room)
        n_d = jnp.minimum(cand_d.sum(axis=1).astype(jnp.float32), need)
        n_promote = jnp.minimum(n_p.astype(jnp.float32), room + n_d)
        n_p2, n_d2 = _truncate_to_rate(n_promote, n_d, room, rate_pages)
        gate = run_row.astype(jnp.float32)
        pmask, dmask = self.select(cand_p, heat, cand_d, heat,
                                   n_p2 * gate, n_d2 * gate)
        overhead = jnp.where(
            run_row,
            (pmask.sum(axis=1) + dmask.sum(axis=1)).astype(jnp.float32)
            * np.float32(self.KERNEL_MS_PER_PAGE), 0.0)
        return st, pmask, dmask, overhead


class _HMSDKDef(_EngineDef):
    MAX_PROBES = 64  # DAMON cost cap, as in the reference

    def knobs(self, configs):
        kv = super().knobs(configs)
        nr = np.minimum(_knob_vec(configs, "nr_regions", dtype=np.int32),
                        self.n)
        kv.update(
            nr_regions=nr,
            sample_us=_knob_vec(configs, "sample_us"),
            hot_pct=_knob_vec(configs, "hot_access_pct"),
            cold_aggr=_knob_vec(configs, "cold_aggr_intervals"),
            period=_knob_vec(configs, "migration_period"),
        )
        # ragged equal-size region maps, padded to Rmax across the batch
        Rmax = int(nr.max())
        B, n = len(nr), self.n  # kv arrays are built for the FULL batch
        region_of_page = np.zeros((B, n), dtype=np.int32)
        sizes = np.zeros((B, Rmax), dtype=np.float32)
        valid = np.zeros((B, Rmax), dtype=bool)
        for b in range(B):
            R = int(nr[b])
            bounds = np.linspace(0, n, R + 1).astype(np.int64)
            region_of_page[b] = np.searchsorted(bounds[1:], np.arange(n),
                                                side="right")
            sizes[b, :R] = (bounds[1:] - bounds[:-1])
            valid[b, :R] = True
        kv.update(region_of_page=region_of_page, sizes=sizes, valid=valid)
        self.Rmax = Rmax
        return kv

    def init(self, kv):
        B = self.B
        zr = jnp.zeros((B, self.Rmax), dtype=jnp.float32)
        return {"acc": zr, "idle": zr,
                "credit": jnp.zeros(B, dtype=jnp.float32)}

    def observe(self, st, kv, keys, e, reads, writes, est_wall):
        B, Rmax = self.B, self.Rmax
        total = (reads + writes).astype(jnp.float32)
        rate = total[None, :] / jnp.maximum(est_wall, 1e-9)[:, None]
        sample_ms = kv["sample_us"] / 1e3
        nr_samples = jnp.maximum(1.0, jnp.floor(est_wall / sample_ms))
        p_hit = 1.0 - jnp.exp(-rate * sample_ms[:, None])
        K = jnp.minimum(nr_samples, self.MAX_PROBES)
        # region-mean hit probability: a probe picks a uniform page in the
        # region then tests its accessed bit, so each probe is
        # Bernoulli(p̄) and K probes are Binomial(K, p̄) — drawn as
        # MAX_PROBES masked Bernoullis (exactly the distribution of the
        # reference's Monte-Carlo probe loop, for both sampler spellings)
        ids = kv["region_of_page"] + \
            (np.arange(B, dtype=np.int32) * Rmax)[:, None]
        pbar = jax.ops.segment_sum(p_hit.reshape(-1), ids.reshape(-1),
                                   num_segments=B * Rmax).reshape(B, Rmax)
        pbar = jnp.clip(pbar / jnp.maximum(kv["sizes"], 1.0), 0.0, 1.0)
        probes = np.arange(self.MAX_PROBES, dtype=np.uint32)[None, :, None]
        regions = np.arange(Rmax, dtype=np.uint32)[None, None, :]
        u = counter_uniform(keys[:, None, None], np.uint32(_S_PROBE),
                            _as_u32(e), probes, regions)
        active = probes.astype(np.float32) < K[:, None, None]
        hits = ((u < pbar[:, None, :]) & active).sum(axis=1)
        acc = hits.astype(jnp.float32) / K[:, None]
        acc = jnp.where(kv["valid"], acc, 0.0)
        idle = jnp.where(kv["valid"] & (acc <= 0.0), st["idle"] + 1.0, 0.0)
        samples = nr_samples * kv["nr_regions"].astype(np.float32) / 50.0
        st = dict(st, acc=acc, idle=idle)
        return st, samples

    def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
             est_wall, max_pages):
        credit, runs = _runs_update(st["credit"], kv["period"], est_wall)
        st = dict(st, credit=credit)
        run_row = runs > 0
        hot_r = st["acc"] >= (kv["hot_pct"] / 100.0)[:, None]
        cold_r = st["idle"] >= kv["cold_aggr"][:, None]
        regions = np.arange(self.Rmax, dtype=np.uint32)[None, :]
        jitter = counter_uniform(keys[:, None], np.uint32(_S_JITTER),
                                 _as_u32(e), regions) * np.float32(1e-6)
        est = st["acc"] + jitter
        rop = kv["region_of_page"]
        hp = jnp.take_along_axis(hot_r, rop, axis=1)
        cp = jnp.take_along_axis(cold_r, rop, axis=1)
        est_p = jnp.take_along_axis(est, rop, axis=1)
        cand_p = hp & ~in_fast & allocated
        rate_pages = jnp.minimum(
            _rate_pages(kv["rate"], est_wall, self.page_bytes), max_pages)
        n_p = cand_p.sum(axis=1)
        room = self.fast_cap - in_fast.sum(axis=1)
        need = jnp.maximum(
            0.0, jnp.minimum(n_p.astype(jnp.float32), rate_pages) - room)
        # demotion preference chain (idle-cold by page index, then lukewarm
        # by estimated rate, then hot by estimated rate) as one composite
        # ascending key
        class1 = ~hp & ~cp & in_fast
        class2 = hp & in_fast
        key_d = jnp.where(cp & in_fast, 0.0,
                          jnp.where(class1, 10.0 + est_p,
                                    jnp.where(class2, 20.0 + est_p, 40.0)))
        cand_d = in_fast
        n_d = jnp.minimum(cand_d.sum(axis=1).astype(jnp.float32), need)
        n_promote = jnp.minimum(n_p.astype(jnp.float32), room + n_d)
        n_p2, n_d2 = _truncate_to_rate(n_promote, n_d, room, rate_pages)
        gate = run_row.astype(jnp.float32)
        pmask, dmask = self.select(cand_p, est_p, cand_d, key_d,
                                   n_p2 * gate, n_d2 * gate)
        return st, pmask, dmask, jnp.zeros(self.B, dtype=jnp.float32)


class KVHeMemDef(_HeMemDef):
    """The tiered-KV cache's HeMem analog — the first **lifted** engine.

    Identical cooling/threshold/ring/rate machinery to :class:`_HeMemDef`,
    but monitoring is **deterministic mean sampling**: the serving path
    measures per-page attention mass *exactly* (the paged-attention kernel
    computes it), so there is no PEBS interrupt noise to emulate —
    ``sampled = true_counts / sampling_period``.  Determinism is also what
    lets the compiled serving step be pinned bit-identical to the eager
    Python ``TieredKVCache`` loop (same jnp ops, jit vs eager).
    """

    def _draws(self, kv, keys, e, reads, writes):
        sr = reads.astype(jnp.float32)[None, :] / kv["sp"][:, None]
        sw = writes.astype(jnp.float32)[None, :] / kv["wsp"][:, None]
        return sr, sw


#: name -> _EngineDef subclass; the compiled-path registry behind
#: supports()/_build_run_fn.  Builtins are seeded here; anything else goes
#: through register_jax_engine (the lifted-engine protocol).
_ENGINE_DEFS = {
    "hemem": _HeMemDef,
    "hmsdk": _HMSDKDef,
    "memtis": _MemtisDef,
    "static": _StaticDef,
    "oracle": _OracleDef,
    "kv-hemem": KVHeMemDef,
}

#: public alias of the lifted-engine protocol base class
EngineDef = _EngineDef


def register_jax_engine(name: str, def_cls: "type | None" = None, *,
                        overwrite: bool = False):
    """Register an :class:`EngineDef` subclass as the compiled (lifted)
    implementation of engine ``name``; usable as a decorator.

    Pair it with ``@register_engine(name)`` on the numpy side: the numpy
    batch engine remains the ``backend="numpy"`` implementation and the
    lifted def compiles the same policy under ``backend="jax"`` — once both
    are registered, :func:`supports` returns True and the simulator stops
    warning/falling back to the numpy epoch loop for this engine.

        @register_jax_engine("my-policy")
        class MyPolicyDef(EngineDef):
            def plan(self, st, kv, keys, e, reads, writes, in_fast,
                     allocated, est_wall, max_pages):
                ...

    See :class:`EngineDef` for the observe/plan purity contract.
    """
    def _add(cls: type) -> type:
        if not (isinstance(cls, type) and issubclass(cls, _EngineDef)):
            raise TypeError(f"lifted engine {name!r} must be an EngineDef "
                            f"subclass, got {cls!r}")
        if name in _ENGINE_DEFS and not overwrite:
            raise ValueError(
                f"lifted engine {name!r} is already registered "
                f"(to {_ENGINE_DEFS[name]!r}); pass overwrite=True to "
                f"replace it")
        _ENGINE_DEFS[name] = cls
        return cls

    return _add if def_cls is None else _add(def_cls)


def jax_engines() -> Tuple[str, ...]:
    """Names with a registered lifted def (compiled under backend='jax')."""
    return tuple(sorted(_ENGINE_DEFS))


#: page-count ceiling of the compiled path (the packed boundary cumsum
#: carries two 16-bit counters per element)
MAX_PAGES = (1 << 16) - 1


def supports(engine_name: str, sampler: str,
             n_pages: "int | None" = None) -> bool:
    """True if the compiled path covers this (engine, sampler[, trace
    size]) combination; anything unsupported falls back to the numpy
    epoch loop."""
    if engine_name not in _ENGINE_DEFS or sampler not in JAX_SAMPLERS:
        return False
    if n_pages is not None and n_pages > MAX_PAGES:
        return False
    return have_jax()


# ---------------------------------------------------------------------------
# Scan driver + jit cache
# ---------------------------------------------------------------------------
def _build_step(edef: "_EngineDef", const, page_bytes, scale,
                record_placement):
    from .simulator import _access_cost  # late: avoids a circular import
    B, n, fast_cap = edef.B, edef.n, edef.fast_cap
    edef.page_bytes = np.float32(page_bytes)
    touch_floor = np.float32(1.0 / max(n, 1))
    zero_cost = edef.zero_cost

    def step(carry, xs, kv):
        in_fast, allocated, est_wall, eng_state, cum_mig, keys = carry
        reads, writes, e = xs
        # first-touch allocation: the trace is shared across the batch, so
        # `allocated` is one shared (n,) vector; only in_fast is per-row.
        # Most epochs touch no new pages, so the (B, n) update is gated.
        acc = reads + writes
        touched = acc > touch_floor
        new = touched & ~allocated
        room = fast_cap - in_fast.sum(axis=1)
        rank_new = jnp.cumsum(new)
        in_fast = in_fast | (new[None, :] & (rank_new[None, :]
                                             <= room[:, None]))
        allocated = allocated | new

        eng_state, samples = edef.observe(
            eng_state, kv, keys, e, reads, writes, est_wall)
        max_pages = jnp.floor(kv["rate"] * np.float32(2 ** 30)
                              * (est_wall / 1e3) / np.float32(page_bytes)
                              * np.float32(scale))
        if edef.plans:
            eng_state, pmask, dmask, overhead_ms = edef.plan(
                eng_state, kv, keys, e, reads, writes, in_fast, allocated,
                est_wall, max_pages)
        else:
            pmask = jnp.zeros((B, n), dtype=bool)
            dmask = pmask
            overhead_ms = jnp.zeros(B, dtype=jnp.float32)
        n_promote = pmask.sum(axis=1).astype(jnp.float32)
        n_demote = dmask.sum(axis=1).astype(jnp.float32)
        in_fast = (in_fast & ~dmask) | pmask
        cum_mig = cum_mig + n_promote + n_demote

        acc_sum = acc.sum()
        reads_f = jnp.sum(jnp.where(in_fast, reads, 0.0), axis=-1)
        writes_f = jnp.sum(jnp.where(in_fast, writes, 0.0), axis=-1)
        acc_f = reads_f + writes_f
        reads_s = reads.sum() - reads_f
        writes_s = writes.sum() - writes_f
        if zero_cost:
            pb = db = w_mig = jnp.zeros(B, dtype=jnp.float32)
        else:
            pb = n_promote * np.float32(page_bytes)
            db = n_demote * np.float32(page_bytes)
            w_mig = jnp.sum(jnp.where(pmask | dmask, writes, 0.0), axis=-1)
        wall_ms, stall_s, sampling_s, hit = _access_cost(
            jnp, acc_f, acc_sum - acc_f, reads_s, writes_s, pb, db, w_mig,
            est_wall, samples, overhead_ms, const)
        out = (wall_ms, cum_mig, hit, sampling_s * 1e3, stall_s * 1e3)
        if record_placement:
            out = out + (in_fast,)
        carry = (in_fast, allocated, wall_ms, eng_state, cum_mig, keys)
        return carry, out

    return step


def init_carry(edef: "_EngineDef", kv, keys, est0):
    """The epoch-0 scan carry: ``(in_fast (B, n), allocated (n,), est_wall
    (B,), engine state pytree, cum_migrations (B,), row keys (B,))``.

    The carry is an explicit input/output of the compiled scan driver so an
    epoch loop can be CHECKPOINTED mid-run and resumed (the tune service's
    partial-budget trials): running epochs ``[0, k)`` and then ``[k, E)``
    from the returned carry is bitwise identical to one unsegmented run,
    because every monitoring draw is keyed by the *absolute* epoch index
    carried in the ``xs`` epoch-id stream, not by scan position.
    """
    B, n = len(keys), edef.n
    if edef.B != B:
        # the per-device def of a pmapped loop: the carry spans the batch
        edef = copy.copy(edef)
        edef.B = B
    return (jnp.zeros((B, n), dtype=bool), jnp.zeros(n, dtype=bool),
            jnp.asarray(est0, dtype=jnp.float32), edef.init(kv),
            jnp.zeros(B, dtype=jnp.float32), jnp.asarray(keys))


def carry_to_host(carry):
    """Materialize a scan carry as a picklable numpy pytree (checkpoint
    payload for the study journal / process-pool trial executors)."""
    return jax.tree_util.tree_map(np.asarray, carry)


def broadcast_carry_row(carry, row: int, B: int):
    """Broadcast ONE batch row of a host carry to a fresh ``B``-row carry.

    The online tuner's counterfactual hook: the deployed system's state at
    epoch ``t`` (row ``row``) becomes the shared starting state for a
    candidate batch evaluating "what if we switched configs now" over the
    next window.  The shared first-touch ``allocated`` vector has no batch
    axis and passes through.

    Only meaningful under CRN (``SimOptions(crn=True)``), where every row's
    base key is identical — broadcasting row ``row``'s key then changes no
    draw.  Without CRN the copied per-row keys would collapse the rows onto
    one noise stream, so callers must pass ``crn=True`` downstream.
    """
    in_fast, allocated, est, eng, cum, keys = carry

    def pick(a):
        a = np.asarray(a)
        return np.repeat(a[row:row + 1], B, axis=0)

    return (pick(in_fast), np.asarray(allocated), pick(est),
            jax.tree_util.tree_map(pick, eng), pick(cum), pick(keys))


def _build_run_fn(engine_name, B, n, n_epochs, fast_cap, sampler, scale,
                  page_bytes, record_placement, select_mode="ref"):
    """Compiled scan driver over ``n_epochs`` epochs (the SEGMENT length).

    ``run(kv, reads_t, writes_t, const, carry, epoch_ids)`` advances the
    carry through one segment and returns ``(final_carry, outs)``.  Epoch
    indices travel as data (``epoch_ids``, int32 ``(n_epochs,)``), so one
    compiled function per segment *length* serves any epoch offset —
    resuming a checkpointed trial never recompiles.
    """
    edef = _ENGINE_DEFS[engine_name](B, n, fast_cap, sampler, select_mode)

    def run(kv, reads_t, writes_t, const, carry, epoch_ids):
        step = _build_step(edef, const, page_bytes, scale, record_placement)
        xs = (reads_t, writes_t, epoch_ids)
        return jax.lax.scan(lambda c, x: step(c, x, kv), carry, xs)

    return edef, run


#: compiled-function cache: key -> (edef, jitted run).  The leading
#: (engine, n_pages, sampler) prefix is the contract of the small-fix
#: satellite: same prefix + same remaining shape params == no retrace.
_COMPILED: Dict[Tuple, Tuple[Any, Any]] = {}

#: shape-parameter names aligned with _get_compiled's key[3:] — used to
#: name the fields a recompile changed
_KEY_FIELDS = ("B", "n_epochs", "fast_cap", "scale", "page_bytes",
               "record_placement", "pmapped", "select_mode")

#: recompile causes already warned about, keyed ((engine, n, sampler),
#: changed-field names).  A phase-shifting study that alternates between
#: two shapes (e.g. window evaluations on two drift phases) retraces each
#: shape ONCE (the compiled functions are cached and reused when the shape
#: repeats) but used to WARN on every first-sighting of a shape; warning
#: once per cause keeps logs readable across phase switches.
_RECOMPILE_WARNED: "set[Tuple]" = set()


def reset_recompile_warnings() -> None:
    """Forget which recompile causes have warned (tests)."""
    _RECOMPILE_WARNED.clear()


def _n_devices() -> int:
    """Local XLA device count (on a CPU host 1 unless it is split, e.g.
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    return jax.local_device_count()


def _get_compiled(engine_name, B, n, n_epochs, fast_cap, sampler, scale,
                  page_bytes, record_placement, select_mode):
    ndev = _n_devices()
    pmapped = ndev > 1 and B % ndev == 0 and B >= ndev
    key = (engine_name, n, sampler, B, n_epochs, fast_cap, float(scale),
           int(page_bytes), bool(record_placement), pmapped, select_mode)
    hit = _COMPILED.get(key)
    if hit is not None:
        return hit
    prefix = key[:3]
    same_prefix = [k for k in _COMPILED if k[:3] == prefix]
    if same_prefix:
        # name the shape fields this recompile changed, against the
        # closest already-compiled shape (fewest differing fields)
        def _diff(k):
            return tuple(name for name, a, b
                         in zip(_KEY_FIELDS, k[3:], key[3:]) if a != b)

        changed = min((_diff(k) for k in same_prefix), key=len)
        if changed == ("n_epochs",):
            # only the segment LENGTH differs — routine for the tune
            # service's partial-epoch (ASHA rung) evaluations, not churn
            log.debug("compiling %d-epoch segment driver for %s "
                      "(n_pages=%d, B=%d)", n_epochs, engine_name, n, B)
        else:
            # warn once per CAUSE (prefix + changed-field set), not once
            # per switch: a drift study alternating between two phase
            # shapes logs one warning, then debug lines
            cause = (prefix, changed)
            msg = ("recompiling jax epoch loop for %s (n_pages=%d, "
                   "sampler=%s): %s changed to B=%d, E=%d, fast_cap=%d, "
                   "select=%s")
            fields = ("/".join(changed) or "shape", B, n_epochs, fast_cap,
                      select_mode)
            if cause in _RECOMPILE_WARNED:
                log.debug(msg + " (repeat cause)", engine_name, n, sampler,
                          *fields)
            else:
                _RECOMPILE_WARNED.add(cause)
                log.warning(msg, engine_name, n, sampler, *fields)
    if pmapped:
        # data-parallel over local XLA devices: each device runs the scan on
        # a B/ndev slice of the batch.  Per-row draws are keyed by global
        # batch index (shipped in the carry's `keys`), so device placement
        # never changes results.  The shared first-touch `allocated` vector
        # is replicated (in_axes None) and comes back identical per device.
        Bl = B // ndev
        edef, run = _build_run_fn(engine_name, Bl, n, n_epochs, fast_cap,
                                  sampler, scale, page_bytes,
                                  record_placement, select_mode)
        prun = jax.pmap(run, in_axes=(0, None, None, None,
                                      (0, None, 0, 0, 0, 0), None))

        def sharded(kv, reads_t, writes_t, const, carry, epoch_ids):
            def shard(a):
                return jnp.reshape(a, (ndev, Bl) + a.shape[1:])

            def unshard(a):
                return jnp.reshape(a, (B,) + a.shape[2:])

            kv_s = {k: shard(v) for k, v in kv.items()}
            in_fast, allocated, est, eng, cum, keys = carry
            carry_s = (shard(in_fast), allocated, shard(est),
                       jax.tree_util.tree_map(shard, eng), shard(cum),
                       shard(keys))
            fin, outs = prun(kv_s, reads_t, writes_t, const, carry_s,
                             epoch_ids)
            f_in_fast, f_alloc, f_est, f_eng, f_cum, f_keys = fin
            fin_carry = (unshard(f_in_fast), f_alloc[0], unshard(f_est),
                         jax.tree_util.tree_map(unshard, f_eng),
                         unshard(f_cum), unshard(f_keys))
            # (ndev, E, Bl, ...) -> (E, B, ...)
            outs = tuple(
                jnp.moveaxis(o, 0, 1).reshape((n_epochs, B) + o.shape[3:])
                for o in outs)
            return fin_carry, outs

        _COMPILED[key] = (edef, sharded)
        return edef, sharded
    edef, run = _build_run_fn(engine_name, B, n, n_epochs, fast_cap, sampler,
                              scale, page_bytes, record_placement,
                              select_mode)
    jitted = jax.jit(run)
    _COMPILED[key] = (edef, jitted)
    return edef, jitted


def compiled_cache_info() -> List[Tuple]:
    """Keys of the jitted-epoch-function cache (tests/debugging)."""
    return list(_COMPILED)


class _Trace:
    """One workload's whole-run epoch trace ``[0, n_epochs)``: the float32
    host arrays, read-only, and their device copies once a compiled run
    asks for them.  It lives on the workload (``_trace``) and goes with it;
    every ``epoch_access`` is a pure function of the epoch index."""

    __slots__ = ("epoch_access", "reads", "writes", "reads_d", "writes_d")

    def __init__(self, workload):
        self.epoch_access = workload.epoch_access
        trace = [workload.epoch_access(e) for e in range(workload.n_epochs)]
        self.reads = np.stack([r for r, _ in trace]).astype(np.float32)
        self.writes = np.stack([w for _, w in trace]).astype(np.float32)
        self.reads.setflags(write=False)
        self.writes.setflags(write=False)
        self.reads_d = self.writes_d = None

    def on_device(self, start: int, stop: int):
        """The device copies of epochs ``[start, stop)``, sliced there."""
        if stop - start == len(self.reads):
            return self.reads_d, self.writes_d
        return tuple(lax.dynamic_slice_in_dim(a, start, stop - start)
                     for a in (self.reads_d, self.writes_d))


_TRACE_LOCK = threading.Lock()


def _cached_trace(workload, device: bool) -> Tuple[_Trace, bool]:
    """The whole-run trace of ``workload`` (copied to the device once if
    ``device``) and whether it was cached.  A workload whose
    ``epoch_access`` was replaced misses."""
    with _TRACE_LOCK:
        tr = getattr(workload, "_trace", None)
        hit = tr is not None and tr.epoch_access == workload.epoch_access
        if not hit:
            tr = workload._trace = _Trace(workload)
        if device and tr.reads_d is None:
            tr.reads_d, tr.writes_d = jax.device_put((tr.reads, tr.writes))
        return tr, hit


def _host_bytes(*trees) -> int:
    """Bytes of the numpy arrays among the leaves of ``trees``: what handing
    them to a jitted call copies to the device."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(trees)
               if isinstance(x, (np.ndarray, np.generic)))


def run_epochs(workload, engine_name: str,
               sim_configs: Sequence[Mapping[str, Any]],
               const: Mapping[str, float], fast_cap: int, page_bytes: int,
               seeds: Sequence[int], sampler: str, crn: bool = False,
               batch_offset: int = 0, record_placement: bool = False,
               python_loop: bool = False,
               exact_select: bool = True,
               epoch_start: int = 0,
               epoch_stop: "int | None" = None,
               carry: Any = None,
               return_carry: bool = False) -> Dict[str, np.ndarray]:
    """Run the compiled epoch loop; returns per-epoch result arrays.

    ``sim_configs`` must already be scale-adjusted (``scale_config``).
    ``python_loop=True`` runs the identical step function eagerly epoch by
    epoch instead of under ``lax.scan`` — the reference the scan is tested
    against.  ``exact_select=True`` (default) plans migrations with the
    exact top-k selection kernel (Pallas or its pure-jnp ref, resolved by
    :func:`repro.kernels.ops.select_path`); ``False`` restores the
    log-quantized ablation path.

    **Segments (checkpoint/restore).**  ``epoch_start``/``epoch_stop``
    bound the evaluated epoch range ``[start, stop)`` (default: the whole
    workload).  Starting past epoch 0 requires ``carry`` — the scan carry a
    previous segment returned under ``return_carry=True`` (as ``"carry"``
    in the output dict, numpy-ified and picklable).  Segmented execution is
    bitwise identical to one unsegmented scan: draws are keyed by absolute
    epoch ids shipped as data, so segment boundaries are invisible to the
    numerics (pinned by the tune-service conformance tests).

    Output dict: ``wall_ms``/``cum_migrations``/``hit_rate``/
    ``sampling_ms``/``stall_ms`` as ``(n_epochs, B)`` float arrays (segment
    epochs only), ``trace_reads``/``trace_writes`` (the segment's float32
    trace, read-only views of the workload's cached trace), plus
    ``in_fast`` ``(n_epochs, B, n)`` when ``record_placement`` and
    ``carry`` when ``return_carry``.
    """
    if not have_jax():  # pragma: no cover - env without jax
        raise RuntimeError("backend='jax' requires jax; install it or use "
                           "the default numpy backend")
    B = len(sim_configs)
    n = workload.n_pages
    if n > MAX_PAGES:  # callers route via supports(); this is the backstop
        raise ValueError(
            f"backend='jax' supports up to {MAX_PAGES} pages "
            f"(workload has {n}); use the numpy backend for larger traces")
    E = workload.n_epochs
    start = int(epoch_start)
    stop = E if epoch_stop is None else min(int(epoch_stop), E)
    if not 0 <= start < stop:
        raise ValueError(f"empty epoch segment [{start}, {stop}) "
                         f"(workload has {E} epochs)")
    if start > 0 and carry is None:
        raise ValueError("epoch_start > 0 requires the carry returned by "
                         "the previous segment (return_carry=True)")
    seg = stop - start
    with spans.span("repro.sim.trace", epochs=seg, pages=n) as sp:
        tr, hit = _cached_trace(workload, device=not python_loop)
        sp.count(cache_hit=int(hit))
        if not python_loop:
            reads_t, writes_t = tr.on_device(start, stop)
    reads_h, writes_h = tr.reads[start:stop], tr.writes[start:stop]
    epoch_ids = np.arange(start, stop, dtype=np.int32)
    const = {k: np.float32(v) for k, v in const.items()}
    scale = workload.scale
    if exact_select:
        from ..kernels import ops as kernel_ops
        select_mode = kernel_ops.select_path()
    else:
        select_mode = "quantized"

    if python_loop:
        edef, _ = _build_run_fn(engine_name, B, n, seg, fast_cap, sampler,
                                scale, page_bytes, record_placement,
                                select_mode)
        kv = edef.knobs(sim_configs)
        step = _build_step(edef, const, page_bytes, scale, record_placement)
        if carry is None:
            keys = base_keys(seeds, batch_offset, crn)
            est0 = np.full(B, workload.epoch_ms, dtype=np.float32)
            carry = init_carry(edef, kv, keys, est0)
        else:
            carry = jax.tree_util.tree_map(jnp.asarray, carry)
        outs = []
        for i, e in enumerate(epoch_ids):
            carry, o = step(carry, (jnp.asarray(reads_h[i]),
                                    jnp.asarray(writes_h[i]),
                                    jnp.int32(int(e))), kv)
            outs.append(o)
        stacked = tuple(jnp.stack([o[i] for o in outs])
                        for i in range(len(outs[0])))
    else:
        with spans.span("repro.sim.launch") as sp:
            n_compiled = len(_COMPILED)
            edef, run = _get_compiled(engine_name, B, n, seg, fast_cap,
                                      sampler, scale, page_bytes,
                                      record_placement, select_mode)
            kv = edef.knobs(sim_configs)
            sp.count(h2d_bytes=_host_bytes(kv, reads_t, writes_t, const,
                                           carry, epoch_ids),
                     cache_miss=int(len(_COMPILED) > n_compiled))
            if carry is None:
                keys = base_keys(seeds, batch_offset, crn)
                est0 = np.full(B, workload.epoch_ms, dtype=np.float32)
                carry = init_carry(edef, kv, keys, est0)
            else:
                carry = jax.tree_util.tree_map(jnp.asarray, carry)
            carry, stacked = run(kv, reads_t, writes_t, const, carry,
                                 epoch_ids)

    names = ["wall_ms", "cum_migrations", "hit_rate", "sampling_ms",
             "stall_ms"]
    if record_placement:
        names.append("in_fast")
    with spans.span("repro.sim.fetch"):
        out = {name: np.asarray(arr) for name, arr in zip(names, stacked)}
        if return_carry:
            out["carry"] = carry_to_host(carry)
    # hand the cached host trace back (read-only) so heatmap binning and
    # the drift detector in the caller do not regenerate it
    out["trace_reads"] = reads_h
    out["trace_writes"] = writes_h
    return out
