"""Least-work counts of the kernels, the peak table, and the roofline share."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import harness as H
from bench.reference.serve_ref import TieredKVReference


def test_select_topk_counts_keys_and_flags_once():
    # 3 rows of 5 pages: 2 sides x (4-byte key read + 4-byte flag write)
    w = H.work("select_topk", rows=3, n=5)
    assert w["bytes"] == 3 * 5 * 2 * 8
    assert w["flops"] == 3 * 5 * 2


def test_paged_attention_counts_attended_tokens():
    # 10 tokens of 2 KV heads x 4 dims in bf16, K and V: 10*2*4*2*2 = 320;
    # q and out of 1 step x 2 sequences x 4 heads x 4 dims: 2*2*4*4*2 = 128
    w = H.work("paged_attention", tokens=10, steps=1, batch=2, heads=4,
               kv_heads=2, head_dim=4, itemsize=2)
    assert w["bytes"] == 320 + 128
    assert w["flops"] == 4 * 10 * 4 * 4


def test_page_migrate_reads_and_writes_each_page():
    assert H.work("page_migrate", pages=3, page_bytes=7) == \
        {"flops": 0.0, "bytes": 42.0}


def test_attended_tokens_exclude_slow_tier_and_unused_entries():
    """The token count behind the paged-attention roofline counts fast-tier
    tokens below each sequence's length only."""
    knobs = H.load_json(H.ROOT, "bench", "configs",
                        "cmdrplus-kv.json")["engine_knobs"]
    ref = TieredKVReference(batch=2, max_pages=4, page_tokens=4,
                            hbm_pages=2, n_layers=1, kv_heads=1,
                            config=knobs, page_bytes=64)
    for _ in range(9):                   # 3 pages each; only 2 HBM slots
        ref.append(0)
    n = ref.record()
    # both sequences' page 0 took the two slots; pages 1 and 2 went to the
    # slow tier.  So 8 of the 18 tokens attend, and the two unused
    # block-table entries of each sequence count nothing.
    assert (ref.slot_of >= 0).sum() == 2
    assert n == 8


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        H.peaks_for("TPU v99")
    assert H.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_roofline_share():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # bytes bound: 50 B / 10 B/s = 5 s least, over 10 s measured
    assert H.roofline_share({"flops": 100.0, "bytes": 50.0}, 10.0, peaks) \
        == pytest.approx(50.0)
    assert H.roofline_share({"flops": 0.0, "bytes": 0.0}, 1.0, peaks) is None
    assert np.isfinite(H.roofline_share({"flops": 1e3, "bytes": 0.0}, 20.0,
                                        peaks))
