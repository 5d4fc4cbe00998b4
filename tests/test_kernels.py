"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as fa
from repro.kernels.paged_attention import compact_table, pages_per_block
from repro.kernels.paged_attention import paged_attention as pa
from repro.kernels.page_migrate import page_migrate as pm


def _dense_attention(q, k, v, causal, window, cap):
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, D).astype(np.float64) / np.sqrt(D)
    s = np.einsum("bskgd,btkd->bskgt", qg, k.astype(np.float64))
    if cap > 0:
        s = cap * np.tanh(s / cap)
    qp = np.arange(S)[:, None]
    kp = np.arange(k.shape[1])[None, :]
    mask = np.ones((S, k.shape[1]), bool)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = np.where(mask[None, :, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(mask[None, :, None, None], p, 0.0)
    out = np.einsum("bskgt,btkd->bskgd", p / p.sum(-1, keepdims=True),
                    v.astype(np.float64))
    return out.reshape(B, S, H, D)


SWEEP = [
    # B, S, H, KV, D, causal, window, cap, dtype
    (1, 128, 4, 4, 64, True, 0, 0.0, jnp.float32),
    (2, 256, 8, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 256, 4, 1, 128, True, 128, 0.0, jnp.float32),
    (2, 128, 4, 4, 64, False, 0, 0.0, jnp.float32),
    (1, 256, 2, 2, 256, True, 0, 50.0, jnp.float32),
    (1, 128, 4, 4, 64, True, 0, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KV,D,causal,window,cap,dtype", SWEEP)
def test_flash_attention_vs_oracle(B, S, H, KV, D, causal, window, cap,
                                   dtype):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    out_pallas = fa(q, k, v, causal=causal, window=window, logit_softcap=cap,
                    interpret=True)
    out_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                      logit_softcap=cap)
    out_dense = _dense_attention(np.asarray(q, np.float64),
                                 np.asarray(k, np.float64),
                                 np.asarray(v, np.float64),
                                 causal, window, cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_pallas, np.float64), out_dense,
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(out_ref, np.float64), out_dense,
                               atol=tol, rtol=tol)


def _paged_table(rng, B, page, ppseq, P, table):
    """A block table and lengths for one ``table`` kind: ``full`` (every
    entry a page, random lengths), ``holes`` (-1 at the front and in the
    middle, full lengths), ``ragged`` (a resident count that is not a
    multiple of the kernel's pages per block) or ``empty`` (sequence 0 has
    no resident page, sequence 1 has length 0)."""
    tbl = np.stack([rng.choice(P, ppseq, replace=False) for _ in range(B)])
    lengths = np.full(B, page * ppseq)
    if table == "full":
        lengths = rng.integers(1, page * ppseq + 1, B)
    elif table == "holes":
        tbl[:, 0] = -1
        tbl[:, ppseq // 2: ppseq // 2 + 2] = -1
        tbl[rng.random(tbl.shape) < 0.25] = -1
    elif table == "ragged":
        n = pages_per_block(page, ppseq) + 3
        assert n % pages_per_block(page, ppseq) and n < ppseq
        for row in tbl:
            row[rng.choice(ppseq, ppseq - n, replace=False)] = -1
    elif table == "empty":
        tbl[0] = -1
        lengths[1] = 0
    return jnp.asarray(tbl, jnp.int32), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("B,H,KV,D,page,ppseq,P,table", [
    pytest.param(2, 8, 4, 64, 16, 4, 16, "full", id="2-8-4-64-16-4-16"),
    pytest.param(3, 4, 1, 128, 8, 8, 64, "full", id="3-4-1-128-8-8-64"),
    pytest.param(1, 16, 8, 64, 32, 2, 8, "full", id="1-16-8-64-32-2-8"),
    # Command R+'s grouping: 96 query heads over 8 KV heads of 128 dims
    pytest.param(2, 96, 8, 128, 16, 20, 24, "full", id="cmdrplus-full"),
    pytest.param(2, 96, 8, 128, 16, 20, 48, "holes", id="cmdrplus-holes"),
    pytest.param(2, 96, 8, 128, 16, 20, 48, "ragged", id="cmdrplus-ragged"),
    pytest.param(3, 8, 2, 64, 16, 24, 64, "holes", id="holes"),
    pytest.param(2, 8, 2, 32, 5, 40, 64, "ragged", id="ragged-page5"),
    pytest.param(3, 8, 4, 32, 8, 6, 16, "empty", id="empty"),
])
def test_paged_attention_vs_oracle(B, H, KV, D, page, ppseq, P, table):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    tbl, lengths = _paged_table(rng, B, page, ppseq, P, table)
    out_p = pa(q, kp, vp, tbl, lengths, interpret=True)
    out_r = ref.paged_attention_ref(q, kp, vp, tbl, lengths)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    if table == "empty":
        assert not np.asarray(out_p[:2]).any()


def test_paged_attention_ignores_unused_pages():
    """Pages past `lengths`, the rows of the last page past it, and the
    pages behind -1 holes (page 0, where a hole would clamp, and pages no
    entry names) must not affect the result: poisoning them with NaN
    leaves the output bit-identical."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(8, 8, 2, 32)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(8, 8, 2, 32)), jnp.float32)
    table = jnp.asarray([[-1, 3, -1, 4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([35], jnp.int32)   # entry 4 holds 3 tokens
    out_a = pa(q, kp, vp, table, lengths, interpret=True)
    kp2, vp2 = kp, vp
    for rows in (np.s_[0:3], np.s_[6:], np.s_[5, 3:]):
        kp2 = kp2.at[rows].set(jnp.nan)
        vp2 = vp2.at[rows].set(jnp.nan)
    out_b = pa(q, kp2, vp2, table, lengths, interpret=True)
    assert np.isfinite(np.asarray(out_a)).all()
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


@pytest.mark.parametrize("page,ppseq,hole_share", [
    (16, 128, 0.75), (5, 13, 0.3), (8, 4, 0.0), (32, 9, 1.0)])
def test_compact_table_is_a_stable_partition(page, ppseq, hole_share):
    """The compaction before the kernel equals a numpy stable partition of
    each row into its counted entries (page id >= 0, first token below the
    length), with each entry's token count."""
    rng = np.random.default_rng(5)
    B = 6
    tbl = rng.integers(0, 1000, (B, ppseq))
    tbl[rng.random(tbl.shape) < hole_share] = -1
    lengths = rng.integers(0, page * ppseq + 1, B)
    lengths[0] = page * ppseq
    ids, tokens, counts = compact_table(jnp.asarray(tbl, jnp.int32),
                                        jnp.asarray(lengths, jnp.int32), page)
    for b in range(B):
        keep = [j for j in range(ppseq)
                if tbl[b, j] >= 0 and j * page < lengths[b]]
        n = len(keep)
        assert counts[b] == n
        np.testing.assert_array_equal(ids[b, :n], tbl[b, keep])
        np.testing.assert_array_equal(
            tokens[b, :n], np.clip(lengths[b] - np.array(keep, int) * page,
                                   0, page))
        assert (np.asarray(ids[b, n:]) == -1).all()
        assert not np.asarray(tokens[b, n:]).any()


@pytest.mark.parametrize("P,elems,n", [(8, 64, 4), (16, 256, 16), (4, 32, 1)])
def test_page_migrate_vs_oracle(P, elems, n):
    rng = np.random.default_rng(11)
    dst = jnp.asarray(rng.normal(size=(P, elems)), jnp.float32)
    src = jnp.asarray(rng.normal(size=(P, elems)), jnp.float32)
    d_ids = jnp.asarray(rng.choice(P, n, replace=False), jnp.int32)
    s_ids = jnp.asarray(rng.choice(P, n, replace=False), jnp.int32)
    # sprinkle no-ops
    if n > 2:
        d_ids = d_ids.at[0].set(-1)
        s_ids = s_ids.at[1].set(-1)
    out_p = pm(dst.copy(), src, d_ids, s_ids, interpret=True)
    out_r = ref.page_migrate_ref(dst, src, d_ids, s_ids)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r))


def test_hotness_update_ref():
    counts = jnp.zeros(16)
    ids = jnp.asarray([3, 3, 5, -1, 3], jnp.int32)
    new, hot = ref.hotness_update_ref(counts, ids, cool=False,
                                      hot_threshold=2.0)
    assert new[3] == 3 and new[5] == 1
    assert bool(hot[3]) and not bool(hot[5])
    cooled, _ = ref.hotness_update_ref(new, jnp.asarray([-1], jnp.int32),
                                       cool=True, hot_threshold=2.0)
    assert cooled[3] == 1.5
