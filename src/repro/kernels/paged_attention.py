"""Pallas TPU paged decode attention over the TieredKVCache HBM pool.

One new token per sequence attends over that sequence's pages, located via a
block table (-1 = no page).  The kernel fetches only the pages a step
attends, all KV heads of a page at once, several pages per grid step.

**Compaction** (:func:`compact_table`, a few XLA ops on the ``(B,
pages_per_seq)`` table).  An entry *counts* when its page id is ``>= 0``
and its first token lies below the sequence's length.  Each row is stably
partitioned so the counted entries come first; beside each entry the
compacted table keeps its token count ``clip(length - j*page, 0, page)``,
and beside each row the number of counted entries.  This is exact: decode
attention is a softmax over a *set* of tokens — no position enters it — so
the order in which pages are visited changes only the float rounding of
the sums, not the mathematics.

**Grid** ``(B, ceil(pages_per_seq / pages_per_block))``, both axes
sequential.  Step ``(b, i)`` attends block ``i`` of sequence ``b``'s
compacted entries: ``pages_per_block`` pages, all KV heads.  Blocks at or
past the row's count start no DMA and run no compute (``pl.when``), so a
call costs the resident pages, not the table's length.
``pages_per_block`` comes from the shapes alone: as many pages as make
about 128 tokens, at most the table's length.

**DMA.**  The pools stay where XLA put them (``pl.ANY``).  Their native
``(P, page, KV, D)`` layout is read as ``(P, page*KV, D)``: on the TPU's
tiled layout that reshape is free (KV a multiple of 8), where ``(P, page,
KV*D)`` would copy the whole pool every call.  One async copy moves a whole
page, every KV head, into a VMEM block.  Two VMEM blocks alternate: an
active step starts the copies of the *next* active block (the next block
of its row, else block 0 of the next row with a counted entry) before it
waits on its own, so the fetch overlaps the compute.  The copies of the
very first active block start at step ``(0, 0)``.

**Compute.**  The block is cast to float32 once, into VMEM cut in
128-lane column chunks; a KV head's tokens are then every KV-th row, one
strided load (Mosaic strides 32-bit rows only).  Per KV head, the head's
``G`` query rows score the block's tokens in float32; a token is valid
when its offset inside its page is below that entry's token count.
Online-softmax state ``(m, l, acc)`` per (KV head, group row) is carried
across blocks in float32 scratch.  A sequence with no counted entry has
``l = 0`` and gets output 0.

BlockSpec tiling:
  q:       (1, KV, G, D)        all head groups of one sequence
  k/v:     pl.ANY               (P, page*KV, D) pools, copied by hand
  out:     (1, KV, G, D)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: tokens a grid step attends at most (fewer where the table is shorter)
BLOCK_TOKENS = 128


def pages_per_block(page: int, pages_per_seq: int) -> int:
    """Pages a grid step fetches: about :data:`BLOCK_TOKENS` tokens."""
    return max(1, min(pages_per_seq, BLOCK_TOKENS // page))


def compact_table(block_table, lengths, page: int):
    """Stable-partition each row of ``block_table`` so the entries that
    count (page id >= 0, first token below the length) come first.

    Returns ``(ids, tokens, counts)``: ``ids`` and ``tokens`` ``(B,
    pages_per_seq)`` int32, the compacted page ids (-1 past the count) and
    each entry's token count (0 past the count); ``counts`` ``(B,)`` the
    number of counted entries per row."""
    table = block_table.astype(jnp.int32)
    start = jnp.arange(table.shape[1], dtype=jnp.int32) * page
    tokens = jnp.clip(lengths.astype(jnp.int32)[:, None] - start, 0, page)
    counted = (table >= 0) & (tokens > 0)
    order = jnp.argsort(~counted, axis=1, stable=True)
    ids = jnp.take_along_axis(jnp.where(counted, table, -1), order, axis=1)
    tokens = jnp.take_along_axis(jnp.where(counted, tokens, 0), order,
                                 axis=1)
    return ids, tokens, counted.sum(axis=1, dtype=jnp.int32)


def _kernel(ids_ref, tok_ref, cnt_ref, nxt_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, k_f32, v_f32, m_scr, l_scr, acc_scr, *,
            page: int, ppb: int, stride: int, kv_heads: int, head_dim: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_rows = pl.num_programs(0)

    def copies(row, blk, slot):
        """The page copies of block ``blk`` of ``row`` into VMEM block
        ``slot``, each guarded by whether its entry counts."""
        out = []
        for p in range(ppb):
            e = blk * ppb + p
            pid = jnp.maximum(ids_ref[row * stride + e], 0)
            live = e < cnt_ref[row]
            dst = pl.ds(p * page * kv_heads, page * kv_heads)
            out.append((live, pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, dst], sems.at[0, slot])))
            out.append((live, pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, dst], sems.at[1, slot])))
        return out

    def start(row, blk, slot):
        for live, cp in copies(row, blk, slot):
            pl.when(live)(cp.start)

    def wait(row, blk, slot):
        for live, cp in copies(row, blk, slot):
            pl.when(live)(cp.wait)

    @pl.when((b == 0) & (i == 0))
    def _first():
        slot_ref[0] = 0
        first = nxt_ref[0]

        @pl.when(first < n_rows)
        def _():
            start(first, 0, 0)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i * ppb < cnt_ref[b])
    def _block():
        slot = slot_ref[0]
        more = (i + 1) * ppb < cnt_ref[b]
        nb = jnp.where(more, b, nxt_ref[b + 1])
        ni = jnp.where(more, i + 1, 0)

        @pl.when(nb < n_rows)
        def _prefetch():
            start(nb, ni, 1 - slot)

        slot_ref[0] = 1 - slot
        wait(b, i, slot)

        T = ppb * page

        def valid(shape, axis):
            """Token t of the block is valid when it lies below its page's
            start plus that entry's token count."""
            t = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            end = jnp.zeros(shape, jnp.int32)
            for p in range(ppb):
                end = jnp.where(t >= p * page,
                                p * page + tok_ref[b * stride + i * ppb + p],
                                end)
            return t < end

        valid_col = valid((T, 1), 0)
        valid_row = valid((1, T), 1)
        # the block in float32, token-major (row t * KV + h), in lane-wide
        # column chunks: a head's tokens are every KV-th row
        lanes = k_f32.shape[-1]
        nc = head_dim // lanes
        for c in range(nc):
            cols = pl.ds(c * lanes, lanes)
            k_f32[c] = k_buf[slot, :, cols].astype(jnp.float32)
            v_f32[c] = v_buf[slot, :, cols].astype(jnp.float32)
        scale = 1.0 / math.sqrt(head_dim)
        for h in range(kv_heads):
            rows = pl.ds(h, T, stride=kv_heads)
            q = q_ref[0, h].astype(jnp.float32) * scale     # (G, D)
            k = jnp.concatenate([k_f32[c, rows, :] for c in range(nc)],
                                axis=1)                     # (T, D)
            v = jnp.concatenate([v_f32[c, rows, :] for c in range(nc)],
                                axis=1)
            # stale VMEM behind an entry that was not fetched may hold
            # anything: zero it so 0 * garbage cannot reach the sum
            v = jnp.where(valid_col, v, 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid_row, s, NEG_INF)            # (G, T)
            m_prev = m_scr[h]                               # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + pr.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                pr, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, lengths,
                    *, interpret: bool = True):
    """q: (B,H,D); k/v_pages: (P,page,KV,D); block_table: (B,ppseq);
    lengths: (B,) -> (B,H,D)."""
    B, H, D = q.shape
    Pn, page, KV, _ = k_pages.shape
    G = H // KV
    ppseq = block_table.shape[1]
    ppb = pages_per_block(page, ppseq)
    n_blocks = -(-ppseq // ppb)
    stride = n_blocks * ppb
    lanes = 128 if D % 128 == 0 else D

    ids, tokens, counts = compact_table(block_table, lengths, page)
    pad = ((0, 0), (0, stride - ppseq))
    ids = jnp.pad(ids, pad, constant_values=-1).reshape(-1)
    tokens = jnp.pad(tokens, pad).reshape(-1)
    # nxt[r]: the first row >= r with a counted entry, else B
    rows = jnp.where(counts > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.append(jax.lax.cummin(rows, reverse=True), jnp.int32(B))

    qr = q.reshape(B, KV, G, D)
    kr = k_pages.reshape(Pn, page * KV, D)
    vr = v_pages.reshape(Pn, page * KV, D)
    q_spec = pl.BlockSpec((1, KV, G, D), lambda b, i, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_blocks),
        in_specs=[q_spec,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page * KV, D), k_pages.dtype),
            pltpu.VMEM((2, ppb * page * KV, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((D // lanes, ppb * page * KV, lanes), jnp.float32),
            pltpu.VMEM((D // lanes, ppb * page * KV, lanes), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, ppb=ppb, stride=stride,
                          kv_heads=KV, head_dim=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(ids, tokens, counts, nxt, qr, kr, vr)
    return out.reshape(B, H, D)
