"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernel tests assert against, and also the
portable path used when running on CPU (including the dry-run lowering): the
flash reference uses the same online-softmax block recurrence as the kernel,
so its memory behaviour — O(S·block) instead of O(S²) — and FLOP profile
match what the TPU kernel does.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# ---------------------------------------------------------------------------
# flash attention (training/prefill)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        logit_softcap: float = 0.0,
                        block_kv: int = 512) -> jnp.ndarray:
    """Online-softmax attention. q: (B,S,H,D), k/v: (B,T,KV,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = (q.reshape(B, S, KV, G, D).astype(jnp.float32)) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    q_pos = jnp.arange(S)

    nblk = max(1, math.ceil(T / block_kv))
    Tpad = nblk * block_kv
    kf = jnp.pad(kf, ((0, 0), (0, Tpad - T), (0, 0), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, Tpad - T), (0, 0), (0, 0)))

    def body(carry, blk_idx):
        m, l, acc = carry
        start = blk_idx * block_kv
        kb = jax.lax.dynamic_slice_in_dim(kf, start, block_kv, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vf, start, block_kv, axis=1)
        k_pos = start + jnp.arange(block_kv)
        s = jnp.einsum("bskgd,btkd->bskgt", qg, kb)
        s = _softcap(s, logit_softcap)
        mask = (k_pos[None, :] < T)[None, None, None]
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])[None, :, None, None]
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)[None, :, None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, KV, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, S, KV, G), jnp.float32)
    acc0 = jnp.zeros((B, S, KV, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nblk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode attention (the TieredKVCache HBM side)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pages, v_pages, block_table, lengths,
                        *, logit_softcap: float = 0.0) -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    q:           (B, H, D)         one new token per sequence
    k/v_pages:   (P, page, KV, D)  global page pool
    block_table: (B, pages_per_seq) int32 page ids (-1 = unused)
    lengths:     (B,)              current sequence lengths
    -> (B, H, D)
    """
    B, H, D = q.shape
    Pn, page, KV, _ = k_pages.shape
    G = H // KV
    ppseq = block_table.shape[1]
    scale = 1.0 / math.sqrt(D)

    table = jnp.maximum(block_table, 0)
    kk = k_pages[table]          # (B, ppseq, page, KV, D)
    vv = v_pages[table]
    kk = kk.reshape(B, ppseq * page, KV, D).astype(jnp.float32)
    vv = vv.reshape(B, ppseq * page, KV, D).astype(jnp.float32)
    qg = q.reshape(B, KV, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kk)
    s = _softcap(s, logit_softcap)
    pos = jnp.arange(ppseq * page)[None]
    valid = (pos < lengths[:, None]) & \
        (block_table[:, pos[0] // page] >= 0)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(valid[:, None, None], p, 0.0)
    out = jnp.einsum("bkgt,btkd->bkgd", p, vv) \
        / jnp.maximum(p.sum(-1)[..., None], 1e-30)
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# page migration (gather/scatter datapath of the tiering engine)
# ---------------------------------------------------------------------------

def page_migrate_ref(dst_pool, src_pool, dst_ids, src_ids):
    """Copy pages src_pool[src_ids] -> dst_pool[dst_ids]; -1 ids are no-ops.

    pools: (P, *page) — returns updated dst_pool.
    """
    valid = (src_ids >= 0) & (dst_ids >= 0)
    rows = src_pool[jnp.where(valid, src_ids, 0)].astype(dst_pool.dtype)
    # no-op lanes scatter out of bounds and are dropped
    dst = jnp.where(valid, dst_ids, dst_pool.shape[0])
    return dst_pool.at[dst].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# exact top-k page selection (the migration planner's sort)
# ---------------------------------------------------------------------------

def _order_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 preserving total order (NaN-free inputs)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where((bits >> 31) == 0, bits | np.uint32(1 << 31), ~bits)


def select_topk_ref(p_mask, p_heat, d_mask, d_heat, n_promote, n_demote):
    """Exact top-``n_promote`` (by ``p_heat`` desc) / top-``n_demote`` (by
    ``d_heat`` asc) selection masks with page-index tie-break — bit-exact
    against numpy's stable argsorts, without a dense sort.

    The pure-jnp oracle of :mod:`repro.kernels.select_topk` and the CPU
    fast path of the compiled epoch loop: a dual 32-step bitwise search
    finds each side's k-th best order-preserving float bit pattern, strict
    winners are taken wholesale, and the boundary tier (priority exactly
    equal to the cutoff) is filled in page-index order by a second bitwise
    search over descending-index weights.  All passes are f32
    compare-count GEMVs (XLA CPU's predicate reductions are scalar, its
    GEMV is vectorized); counts stay below 2**24 so the f32 arithmetic is
    exact.  Priorities must be NaN-free (engine priorities are nonnegative
    counts/rates).
    """
    n = p_mask.shape[-1]
    ones = jnp.ones(n, jnp.float32)
    kp = jnp.floor(n_promote.astype(jnp.float32))[:, None]
    kd = jnp.floor(n_demote.astype(jnp.float32))[:, None]
    vp = jnp.where(p_mask, _order_bits(p_heat), np.uint32(0))
    vd = jnp.where(d_mask, ~_order_bits(d_heat), np.uint32(0))

    def count_ge(v, t):
        return ((v >= t).astype(jnp.float32) @ ones)[:, None]

    tp = jnp.zeros((kp.shape[0], 1), dtype=jnp.uint32)
    td = jnp.zeros((kd.shape[0], 1), dtype=jnp.uint32)
    for i in range(31, -1, -1):
        bit = np.uint32(1 << i)
        tp = jnp.where(count_ge(vp, tp | bit) >= kp, tp | bit, tp)
        td = jnp.where(count_ge(vd, td | bit) >= kd, td | bit, td)
    strict_p = vp > tp
    strict_d = vd > td
    bound_p = (vp == tp) & (vp > 0)
    bound_d = (vd == td) & (vd > 0)
    take_p = kp - (strict_p.astype(jnp.float32) @ ones)[:, None]
    take_d = kd - (strict_d.astype(jnp.float32) @ ones)[:, None]
    # boundary tier in index order: search over descending-index weights
    # (distinct per row, so the take-th largest threshold takes exactly
    # `take` pages)
    iv = np.uint32(n) - jnp.arange(n, dtype=jnp.uint32)[None, :]
    wp = jnp.where(bound_p, iv, np.uint32(0))
    wd = jnp.where(bound_d, iv, np.uint32(0))
    sp = jnp.zeros_like(tp)
    sd = jnp.zeros_like(td)
    for i in range(16, -1, -1):
        bit = np.uint32(1 << i)
        sp = jnp.where(count_ge(wp, sp | bit) >= take_p, sp | bit, sp)
        sd = jnp.where(count_ge(wd, sd | bit) >= take_d, sd | bit, sd)
    pm = strict_p | (bound_p & (wp >= sp) & (take_p > 0))
    dm = strict_d | (bound_d & (wd >= sd) & (take_d > 0))
    return pm & (kp > 0), dm & (kd > 0)


# ---------------------------------------------------------------------------
# hotness update (access counting + threshold classification)
# ---------------------------------------------------------------------------

def hotness_update_ref(counts, page_ids, *, cool: bool,
                       hot_threshold: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter-add sampled accesses into per-page counters, optionally halve
    (cooling), and classify.  counts: (P,), page_ids: (N,) (-1 = no sample).
    Returns (new_counts, hot_mask)."""
    valid = page_ids >= 0
    ids = jnp.where(valid, page_ids, 0)
    upd = jnp.zeros_like(counts).at[ids].add(
        valid.astype(counts.dtype))
    new = (counts + upd) * (0.5 if cool else 1.0)
    return new, new >= hot_threshold
