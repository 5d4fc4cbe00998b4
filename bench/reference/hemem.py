"""HeMem's monitoring, cooling and migration plan in plain numpy.

The plain reference of the tiering engine that both benchmark kinds run:
the simulator's HeMem (sampled PEBS monitoring) and the tiered KV cache's
HeMem (monitoring by mean counts).  It follows the published mechanism as
the system under test states it:

* PEBS sampling: every page's true read and write counts of an epoch are
  sampled as Poisson(count / sampling_period) draws.  The draws are keyed by
  a counter-based hash of (row key, site, epoch, page), so a reference and
  the system under test draw the same numbers from the same keys.
* cooling: each ``cooling_threshold * n / 16`` samples halve the counters
  of the next ``cooling_pages`` pages of a cyclic sweep.
* plan: pages whose read or write counter reaches its hot threshold are
  promoted, hottest first, cold pages in the fast tier demoted, coldest
  first, ties by page index; each side is capped by its ring size per
  migration-thread run and both by the migration rate.

Arrays are ``(B, n)`` for B candidate configurations.  Arithmetic is float32
by default; ``Precision`` rounds every float result to a lower precision for
the control run.
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np

F32 = np.float32

# counter-based hash words
_GOLDEN = np.uint32(0x9E3779B9)
_MUL1 = np.uint32(0x7FEB352D)
_MUL2 = np.uint32(0x846CA68B)
SITE_READ = 0x11
SITE_WRITE = 0x21
#: rate below which a draw inverts the Poisson CDF exactly
POISSON_SWITCH = 5.0
POISSON_TERMS = 16
#: 1 / sigma of (popcount(u32) - 16 + uniform - 1/2)
POPCOUNT_NORM = 1.0 / 2.8431203
COOL_UNIT_PAGES = 16.0


class Precision:
    """Rounds float arrays to the precision a run states: float32 (``None``)
    or a lower one such as ``ml_dtypes.bfloat16`` for the control."""

    def __init__(self, dtype=None):
        self.dtype = dtype

    def __call__(self, x):
        x = np.asarray(x, F32)
        if self.dtype is None:
            return x
        return x.astype(self.dtype).astype(F32)


FP32 = Precision()


def _mix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _MUL1
    h = h ^ (h >> np.uint32(15))
    h = h * _MUL2
    return h ^ (h >> np.uint32(16))


def _fold(h, w):
    h = np.asarray(h, np.uint32)
    w = np.asarray(w, np.uint32)
    return _mix32(h ^ (w + _GOLDEN + (h << np.uint32(6)) + (h >> np.uint32(2))))


def counter_hash(key, *words):
    h = np.asarray(key, np.uint32)
    for w in words:
        h = _fold(h, w)
    return h


def hash_uniform(h):
    return ((h >> np.uint32(8)).astype(F32) + F32(0.5)) * F32(1.0 / (1 << 24))


def row_keys(seed: int, rows: Sequence[int]) -> np.ndarray:
    """Per-row base keys of a batch evaluated under one simulation seed:
    row ``b`` of a batch folds ``(seed, b)`` into a fixed start word."""
    rows = np.asarray(rows, np.uint32)
    seeds = np.full(len(rows), seed, np.uint32)
    h0 = np.full(len(rows), 0xC0FFEE, np.uint32)
    with np.errstate(over="ignore"):
        return _fold(_fold(h0, seeds), rows)


def poisson_draw(lam, h1, h2, P: Precision = FP32):
    """Poisson(lam): inverse CDF below POISSON_SWITCH, a normal with a
    popcount-built deviate above it."""
    u1 = hash_uniform(h1)
    lam_s = np.minimum(lam, F32(POISSON_SWITCH))
    pmf = P(np.exp(-lam_s))
    cdf = pmf
    k = (u1 > cdf).astype(F32)
    for i in range(1, POISSON_TERMS):
        pmf = P(pmf * (lam_s / F32(i)))
        cdf = P(cdf + pmf)
        k = k + (u1 > cdf)
    z = (np.bitwise_count(h1).astype(F32) - F32(16.0)
         + hash_uniform(h2) - F32(0.5)) * F32(POPCOUNT_NORM)
    normal = np.maximum(F32(0), np.floor(P(lam + P(np.sqrt(lam) * z))
                                         + F32(0.5)))
    return np.where(lam < F32(POISSON_SWITCH), k, normal).astype(F32)


def monitor(keys, epoch: int, site: int, base, period, P: Precision = FP32):
    """Sampled counts ``(B, n)`` of one monitoring site."""
    n = base.shape[-1]
    pages = np.arange(n, dtype=np.uint32)[None, :]
    e = np.uint32(epoch)
    with np.errstate(over="ignore"):
        h1 = counter_hash(keys[:, None], np.uint32(site), e, pages)
        h2 = counter_hash(keys[:, None], np.uint32(site + 1), e, pages)
    lam = P(base[None, :].astype(F32) / period[:, None])
    return poisson_draw(lam, h1, h2, P)


def knobs(configs: Sequence[Mapping], n: int) -> Dict[str, np.ndarray]:
    def vec(name, dtype=F32):
        return np.asarray([c[name] for c in configs], dtype)

    cool = np.minimum(vec("cooling_pages", np.int32), n).astype(np.int32)
    return {
        "rate": vec("max_migration_rate"),
        "sp": vec("sampling_period"), "wsp": vec("write_sampling_period"),
        "read_hot": vec("read_hot_threshold"),
        "write_hot": vec("write_hot_threshold"),
        "period": vec("migration_period"),
        "cool_pages": cool,
        "hot_ring": vec("hot_ring_reqs_threshold", np.int32),
        "cold_ring": vec("cold_ring_reqs_threshold", np.int32),
        "trigger": np.maximum(vec("cooling_threshold") * F32(n)
                              / F32(COOL_UNIT_PAGES), F32(1.0)).astype(F32),
        "M": ((n + cool - 1) // cool).astype(np.int32),
    }


def init_state(B: int, n: int) -> Dict[str, np.ndarray]:
    return {"rc": np.zeros((B, n), F32), "wc": np.zeros((B, n), F32),
            "cursor": np.zeros(B, np.int32), "since": np.zeros(B, F32),
            "credit": np.zeros(B, F32)}


def observe(st, kv, sr, sw, P: Precision = FP32):
    """Fold one epoch's sampled counts into the cooled counters; returns
    the new state and the samples taken per row."""
    n = sr.shape[-1]
    samples = P((sr + sw).sum(axis=-1, dtype=F32))
    since = P(st["since"] + samples)
    k = np.floor(since / kv["trigger"]).astype(np.int32)
    p = kv["cool_pages"]
    k_eff = P(k.astype(F32) * p.astype(F32) / F32(n))
    factor = np.where(k > 0, P((F32(2.0) - np.exp2(-k_eff))
                               / (k_eff + F32(1.0))), F32(1.0)).astype(F32)
    M = kv["M"]
    m0 = st["cursor"] // p
    cj = (np.arange(n, dtype=np.int32)[None, :] // p[:, None])
    halv = (k // M)[:, None] + (((cj - m0[:, None]) % M[:, None])
                                < (k % M)[:, None])
    decay = np.exp2(-halv.astype(F32))
    rc = P(st["rc"] * decay + P(sr * factor[:, None]))
    wc = P(st["wc"] * decay + P(sw * factor[:, None]))
    st = dict(st, rc=rc, wc=wc, cursor=((m0 + k) % M) * p,
              since=P(since - k.astype(F32) * kv["trigger"]))
    return st, samples


def top_mask(cand, heat, count, descending: bool):
    """Boolean mask of the first ``count[b]`` candidates of each row, by
    heat (descending or ascending), ties by page index ascending."""
    B, n = cand.shape
    out = np.zeros((B, n), bool)
    for b in range(B):
        k = int(count[b])
        if k <= 0:
            continue
        idx = np.flatnonzero(cand[b])
        h = heat[b, idx].astype(np.float64)
        order = np.argsort(-h if descending else h, kind="stable")
        out[b, idx[order[:k]]] = True
    return out


def plan(st, kv, in_fast, allocated, est_wall, max_pages, fast_cap: int,
         page_bytes: float, P: Precision = FP32):
    """One migration-thread step: ``(state, promote, demote)`` masks."""
    credit = P(st["credit"] + est_wall)
    runs = np.floor(credit / kv["period"]).astype(np.int32)
    credit = P(credit - runs.astype(F32) * kv["period"])
    st = dict(st, credit=credit)
    hot = (st["rc"] >= kv["read_hot"][:, None]) | \
        (st["wc"] >= kv["write_hot"][:, None])
    heat = P(st["rc"] + st["wc"])
    alloc = np.broadcast_to(allocated, in_fast.shape)
    cand_p = hot & ~in_fast & alloc
    cand_d = ~hot & in_fast
    rate_pages = np.minimum(
        np.floor(kv["rate"] * F32(2 ** 30) * (est_wall / F32(1e3))
                 / F32(page_bytes)), max_pages)
    n_p = np.minimum(cand_p.sum(axis=1), kv["hot_ring"] * runs)
    room = fast_cap - in_fast.sum(axis=1)
    watermark = max(1, fast_cap // 50)
    pressure = np.maximum(0, watermark - room)
    need = np.maximum(np.maximum(0, n_p - room), pressure)
    n_d = np.minimum(cand_d.sum(axis=1),
                     np.minimum(need, kv["cold_ring"] * runs))
    n_promote = np.minimum(n_p, room + n_d).astype(F32)
    n_d = n_d.astype(F32)
    rate = np.maximum(F32(0), rate_pages)
    over = (n_promote + n_d) > rate
    n_d2 = np.where(over, np.minimum(n_d, rate), n_d)
    n_p2 = np.where(over, np.maximum(F32(0), np.minimum(
        np.minimum(n_promote, room.astype(F32) + n_d2), rate - n_d2)),
        n_promote)
    gate = runs > 0
    pmask = top_mask(cand_p, heat, np.where(gate, n_p2, 0), True)
    dmask = top_mask(cand_d, heat, np.where(gate, n_d2, 0), False)
    return st, pmask, dmask
