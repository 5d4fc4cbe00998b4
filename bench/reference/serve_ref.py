"""Plain reference of the tiered paged KV cache under decode.

A batch of sequences decodes one token per step.  Each token's K and V go
to its sequence's current page; a page's first token places it in the
fast tier's lowest free slot, or in the slow tier when none is free.  Decode
attention reads, for each sequence, the tokens of its pages that are in the
fast tier (layer 0, grouped query heads over the KV heads, scaled dot
product, softmax).  Each step charges every page of a sequence its share of
the attention-mass profile (sink, recency, uniform base) in integer access
counts; every engine epoch runs HeMem over those counts and moves whole
pages: demotions first, then promotions, lowest page id to lowest free slot.
A finished sequence frees its pages and starts again from length 0.

The reference keeps no K/V values of its own.  It keeps, for every token
position of every pool row, which step input and which sequence wrote it,
and reads the values from the step inputs when it attends or compares a
page.  It imports nothing of the system under test.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

import numpy as np

from . import hemem
from .hemem import F32, FP32, Precision


def read_counts(lengths, max_pages: int, page_tokens: int, scale: int):
    """Integer access counts ``(B, max_pages)`` of one decode step: every
    page of a sequence covering ``n_p`` pages gets ``scale // (20 n_p)``,
    page 0 another 35 %, and the last ``min(n_p, 2)`` pages 45 % split
    between them."""
    lengths = np.asarray(lengths, np.int64)
    ar = np.arange(max_pages)[None, :]
    n_p = ((np.maximum(lengths, 1) - 1) // page_tokens + 1)[:, None]
    c = scale // (20 * n_p)
    c = c + np.where(ar == 0, 35 * scale // 100, 0)
    c = c + np.where(ar >= n_p - 2, (45 * scale) // (100 * np.minimum(n_p, 2)),
                     0)
    return np.where(ar < n_p, c, 0).astype(np.int64)


class TieredKVReference:
    """Replays a decode loop: ``append`` + ``record`` every step,
    ``engine`` every epoch, ``reset`` when sequences finish."""

    def __init__(self, batch: int, max_pages: int, page_tokens: int,
                 hbm_pages: int, n_layers: int, kv_heads: int,
                 config: Mapping, page_bytes: float,
                 P: Precision = FP32):
        self.B, self.mp, self.pt, self.H = batch, max_pages, page_tokens, \
            hbm_pages
        self.n = n = batch * max_pages
        self.scale = page_tokens * kv_heads * n_layers * 64
        self.P = P
        self.page_bytes = F32(page_bytes)
        self.slot_of = np.full(n, -1, np.int64)
        self.page_of_slot = np.full(hbm_pages, -1, np.int64)
        self.lengths = np.zeros(batch, np.int64)
        self.allocated = np.zeros(n, bool)
        self.reads = np.zeros(n, np.int64)
        self.writes = np.zeros(n, np.int64)
        #: writer of each token position: step input * B + sequence, -1 none
        self.hbm_src = np.full((hbm_pages, page_tokens), -1, np.int64)
        self.host_src = np.full((n, page_tokens), -1, np.int64)
        self.kv = hemem.knobs([config], n)
        self.eng = hemem.init_state(1, n)
        self.migrations = 0
        self.moved: List[int] = []

    # -- one decode step ---------------------------------------------------
    def append(self, src: int):
        """Every sequence appends one token, written by step input ``src``."""
        B, mp, pt = self.B, self.mp, self.pt
        t = self.lengths
        pi, off = t // pt, t % pt
        pid = np.arange(B) * mp + pi
        self.allocated[pid] = True
        self.writes[pid] += 1
        free = np.flatnonzero(self.page_of_slot < 0)
        j = 0
        for b in range(B):
            if self.slot_of[pid[b]] < 0 and off[b] == 0 and j < len(free):
                self.slot_of[pid[b]] = free[j]
                self.page_of_slot[free[j]] = pid[b]
                j += 1
        for b in range(B):
            s = self.slot_of[pid[b]]
            if s >= 0:
                self.hbm_src[s, off[b]] = src * B + b
            else:
                self.host_src[pid[b], off[b]] = src * B + b
        self.lengths = t + 1

    def record(self) -> int:
        """Charge this step's access counts; returns the number of
        fast-tier tokens the step attends, summed over sequences."""
        counts = read_counts(self.lengths, self.mp, self.pt, self.scale)
        self.reads += counts.reshape(self.n)
        first = np.arange(self.mp)[None, :] * self.pt
        held = np.clip(self.lengths[:, None] - first, 0, self.pt)
        resident = self.slot_of.reshape(self.B, self.mp) >= 0
        return int(np.where(resident, held, 0).sum())

    def resident_tokens(self, b: int):
        """(positions, writers) of sequence ``b``'s fast-tier tokens."""
        L = int(self.lengths[b])
        pos = np.arange(L)
        slots = self.slot_of[b * self.mp + pos // self.pt]
        keep = slots >= 0
        pos = pos[keep]
        return pos, self.hbm_src[slots[keep], pos % self.pt]

    def attend(self, q, k0, v0, P: Precision = FP32) -> np.ndarray:
        """Decode attention ``(B, heads, D)`` in float32 (or ``P``).  ``q``
        ``(B, heads, D)``; ``k0``/``v0`` layer-0 step inputs
        ``(inputs * B, kv_heads, D)`` indexed by writer."""
        B, heads, D = q.shape
        KV = k0.shape[1]
        G = heads // KV
        out = np.zeros((B, heads, D), F32)
        for b in range(B):
            _, src = self.resident_tokens(b)
            if len(src) == 0:
                continue
            k = P(k0[src]).transpose(1, 0, 2)                # (KV, T, D)
            v = P(v0[src]).transpose(1, 0, 2)
            qb = P(q[b]).reshape(KV, G, D) * F32(1.0 / np.sqrt(D))
            s = np.matmul(qb, k.transpose(0, 2, 1))          # (KV, G, T)
            p = np.exp(s - s.max(-1, keepdims=True))
            o = np.matmul(p, v) / p.sum(-1, keepdims=True)
            out[b] = o.reshape(heads, D)
        return out

    # -- engine epoch ------------------------------------------------------
    def engine(self, dt_ms: float) -> int:
        P, n, H = self.P, self.n, self.H
        in_fast = (self.slot_of >= 0)[None, :]
        sr = P(self.reads.astype(F32)[None, :] / self.kv["sp"][:, None])
        sw = P(self.writes.astype(F32)[None, :] / self.kv["wsp"][:, None])
        eng, _ = hemem.observe(self.eng, self.kv, sr, sw, P)
        est = np.full(1, F32(dt_ms), F32)
        eng, pm, dm = hemem.plan(eng, self.kv, in_fast,
                                 self.allocated[None, :], est,
                                 np.full(1, F32(H)), H, self.page_bytes, P)
        self.eng = eng
        moved = 0
        for pid in np.flatnonzero(dm[0] & in_fast[0]):
            s = self.slot_of[pid]
            self.host_src[pid] = self.hbm_src[s]
            self.slot_of[pid] = -1
            self.page_of_slot[s] = -1
            moved += 1
        free = np.flatnonzero(self.page_of_slot < 0)
        cand = np.flatnonzero(pm[0] & (self.slot_of < 0) & self.allocated)
        for pid, s in zip(cand, free):
            self.hbm_src[s] = self.host_src[pid]
            self.slot_of[pid] = s
            self.page_of_slot[s] = pid
            moved += 1
        self.reads[:] = 0
        self.writes[:] = 0
        self.migrations += moved
        self.moved.append(moved)
        return moved

    def reset(self, done):
        done = np.asarray(done, bool)
        kill = np.repeat(done, self.mp)
        for pid in np.flatnonzero(kill & (self.slot_of >= 0)):
            self.page_of_slot[self.slot_of[pid]] = -1
        self.slot_of[kill] = -1
        self.allocated[kill] = False
        self.reads[kill] = 0
        self.writes[kill] = 0
        self.lengths[done] = 0
        self.eng = dict(self.eng, rc=np.where(kill[None], F32(0), self.eng["rc"]),
                        wc=np.where(kill[None], F32(0), self.eng["wc"]))

    # -- pool contents -----------------------------------------------------
    def page_writers(self, pid: int) -> np.ndarray:
        """Writers of the tokens of page ``pid`` that its sequence holds now
        (-1 past the sequence's length)."""
        b, j = divmod(pid, self.mp)
        s = self.slot_of[pid]
        src = (self.hbm_src[s] if s >= 0 else self.host_src[pid]).copy()
        held = j * self.pt + np.arange(self.pt) < self.lengths[b]
        return np.where(held, src, -1)

    def state(self) -> Dict[str, np.ndarray]:
        return {"slot_of": self.slot_of.copy(), "lengths": self.lengths.copy(),
                "migrations": np.int64(self.migrations)}
