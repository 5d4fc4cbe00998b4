"""The trace reduction, on events written by hand and on a recorded trace."""

import os

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    # one device; window [100, 1100) ns; spans: a study [150, 700) holding a
    # round [200, 400); an engine span [800, 1000)
    host = [("bench.window", 100, 1000), ("bench.study", 150, 550),
            ("bench.round", 200, 200), ("bench.engine", 800, 200)]
    modules = [("jit_run(123)", 120, 300), ("jit_impl(9)", 600, 150)]
    ops = [("%while.3 = (...) while(...)", 120, 300),
           ("%select_topk.7 = (...) custom-call(...)", 130, 50),
           ("%select_topk.7 = (...) custom-call(...)", 250, 50),
           ("%select_topk.1 = (...) custom-call(...)", 600, 100),
           ("%fusion.2 = f32[] fusion(...)", 650, 100),
           ("%copy.1 = f32[] copy(...)", 1050, 100)]   # ends past the window
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_union_and_idle_share():
    red = T.reduce(_trace())
    # busy: [120,420) clipped to [100,..) -> [120,420) = 300; [600,750) = 150;
    # [1050,1100) clipped = 50
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(500e-9)
    assert red.idle_share == pytest.approx(0.5)


def test_per_kernel_and_program_sums():
    red = T.reduce(_trace())
    assert red.op_s["select_topk"] == pytest.approx(200e-9)
    assert red.op_calls["select_topk"] == 3
    assert red.kernel_s("select_topk", module="jit_run") == \
        pytest.approx(100e-9)
    assert red.kernel_s("select_topk", module="jit_impl") == \
        pytest.approx(100e-9)
    assert red.module_s == {"jit_run": pytest.approx(300e-9),
                            "jit_impl": pytest.approx(150e-9)}


def test_gaps_go_to_the_innermost_open_span():
    red = T.reduce(_trace())
    # idle: [100,120) outside any span but the window; [420,600) mid 510 in
    # the study; [750,1050) mid 900 in the engine span
    assert red.idle_by_span == {T.OUTSIDE: pytest.approx(20e-9),
                                "bench.study": pytest.approx(180e-9),
                                "bench.engine": pytest.approx(300e-9)}
    b = red.breakdown()
    assert b["idle_gaps"][0] == ["bench.engine", pytest.approx(300e-9)]
    assert len(b["device_ops"]) <= 10


def test_names():
    assert T.op_name("%paged_attention.1 = bf16[64] custom-call()") == \
        "paged_attention"
    assert T.module_name("jit__decode(18421459488512320761)") == \
        "jit__decode"


def test_window_span_required():
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "host": [("bench.step", 0, 5)]})


def test_recorded_trace():
    """A trace recorded on a TPU v5 lite: a bare decode loop at the serving
    cells' geometry with 512 HBM pages, recorded without the benchmark's
    spans, so the window is taken as the extent of its device events."""
    tr = T.load(os.path.join(DATA, "serve_probe.xplane.pb"))
    dev = tr["devices"]["/device:TPU:0"]
    events = dev["ops"] + dev["modules"]
    lo = min(s for n, s, d in events)
    hi = max(s + d for n, s, d in events)
    tr["host"].append((T.WINDOW, lo, hi - lo))
    red = T.reduce(tr)
    ivs = sorted((s, s + d) for n, s, d in dev["ops"])
    busy, end = 0.0, lo
    for a, b in ivs:                      # the union, counted plainly
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert 0.0 < red.idle_share < 1.0
    for kernel in ("paged_attention", "page_migrate", "select_topk"):
        t = sum(d for n, s, d in dev["ops"] if T.op_name(n) == kernel)
        assert t > 0
        assert red.kernel_s(kernel) == pytest.approx(t / 1e9)
    assert red.module_calls["jit__decode"] == sum(
        1 for n, s, d in dev["modules"] if n.startswith("jit__decode("))
    assert red.kernel_s("paged_attention", module="jit__decode") == \
        pytest.approx(red.kernel_s("paged_attention"))
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)
