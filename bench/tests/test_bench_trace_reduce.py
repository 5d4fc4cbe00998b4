"""The trace reduction, on events written by hand and on a recorded trace."""

import os
import types

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
    # idle: [100,120) outside any span but the window; [420,600) in the
    # study's self time; [750,1050) split: [750,800) and [1000,1050)
    # outside, [800,1000) in the engine span
    assert red.idle_by_span == {T.OUTSIDE: pytest.approx(120e-9),
                                "bench.study": pytest.approx(180e-9),
                                "bench.engine": pytest.approx(200e-9)}
    b = red.breakdown()
    assert b["idle_gaps"][0] == ["bench.engine", pytest.approx(200e-9)]
    assert len(b["device_ops"]) <= 10


def test_self_time_excludes_children():
    red = T.reduce(_trace())
    assert red.span_s["bench.study"] == pytest.approx(550e-9)
    assert red.span_self_s == {"bench.study": pytest.approx(350e-9),
                               "bench.round": pytest.approx(200e-9),
                               "bench.engine": pytest.approx(200e-9)}
    assert red.span_calls == {"bench.study": 1, "bench.round": 1,
                              "bench.engine": 1}


def test_idle_gap_split_between_spans():
    # window [0, 100); siblings a [10, 40) and b [40, 90); the device is
    # busy [0, 20) and [70, 100): the gap [20, 70) is 20 in a and 30 in b
    ops = [("%fusion.1 = f32[] fusion(...)", 0, 20),
           ("%fusion.1 = f32[] fusion(...)", 70, 30)]
    red = T.reduce({"devices": {"/device:TPU:0": {"ops": ops,
                                                  "modules": []}},
                    "host": [("bench.window", 0, 100),
                             ("repro.a", 10, 30, {}),
                             ("repro.b", 40, 50, {})]})
    assert red.idle_by_span == {"repro.a": pytest.approx(20e-9),
                                "repro.b": pytest.approx(30e-9)}


def test_counts_summed_per_span():
    # three- and four-field host events together; a span begun before the
    # window counts a call, its counts and its time inside the window
    host = [("bench.window", 100, 1000), ("bench.study", 100, 900),
            ("repro.sim.launch", 50, 100, {"h2d_bytes": 999,
                                           "cache_miss": 1}),
            ("repro.sim.launch", 200, 10, {"h2d_bytes": 328,
                                           "cache_miss": 1}),
            ("repro.sim.launch", 400, 30, {"h2d_bytes": 988,
                                           "cache_miss": 0}),
            ("repro.bo.fit", 500, 40, {"n_obs": 4})]
    red = T.reduce({"devices": {}, "host": host})
    assert red.span_calls == {"bench.study": 1, "repro.sim.launch": 3,
                              "repro.bo.fit": 1}
    assert red.span_counts["repro.sim.launch"] == {"h2d_bytes": 2315,
                                                   "cache_miss": 2}
    assert red.span_counts["repro.bo.fit"] == {"n_obs": 4}
    assert red.span_counts["bench.study"] == {}
    assert red.span_self_s["repro.sim.launch"] == pytest.approx(90e-9)
    assert red.self_ms_per_call("repro.sim.launch") == pytest.approx(30e-6)
    assert red.self_ms_per_call("repro.sim.fetch") is None
    assert red.idle_under("repro.sim.") == 0.0
    assert red.idle_under("repro.study.") is None


def test_self_intervals_cover_the_window():
    # a child that outlives its parent keeps the time past the parent's end
    spans = [("p", 10, 50), ("c", 20, 60), ("q", 70, 80)]
    got = T.self_intervals(spans, 0, 100)
    assert got == [(0, 10, T.OUTSIDE), (10, 20, "p"), (20, 60, "c"),
                   (60, 70, T.OUTSIDE), (70, 80, "q"), (80, 100, T.OUTSIDE)]
    assert T.self_intervals([], 5, 9) == [(5, 9, T.OUTSIDE)]


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=[
            types.SimpleNamespace(name=e[0], start_ns=e[1], duration_ns=e[2],
                                  stats=list(e[3].items()) if len(e) > 3
                                  else [])
            for e in evs]) for n, evs in lines])


def test_spans_on_a_second_thread_are_dropped():
    host = _plane("/host:CPU", [
        ("main", [("bench.window", 0, 100), ("bench.study", 5, 90),
                  ("repro.bo.fit", 10, 5, {"n_obs": 3, "hit": True,
                                           "tag": "x"})]),
        ("worker", [("repro.sim.launch", 20, 5, {"h2d_bytes": 7}),
                    ("bench.reset", 30, 5), ("other", 40, 5)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [("%fusion.1 = f32[] fusion(...)", 10, 5)]),
        ("XLA Modules", [("jit_run(1)", 10, 5)]), ("Scalar Unit", [])])
    tr = T.from_planes([dev, host, _plane("/host:metadata", [])])
    assert sorted(tr["host"], key=lambda e: e[1]) == [
        ("bench.window", 0, 100), ("bench.study", 5, 90),
        ("repro.bo.fit", 10, 5, {"n_obs": 3}), ("bench.reset", 30, 5)]
    assert tr["devices"]["/device:TPU:0"]["modules"] == [("jit_run(1)", 10, 5)]
    red = T.reduce(tr)
    assert "repro.sim.launch" not in red.span_calls
    assert red.span_counts["repro.bo.fit"] == {"n_obs": 3}


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


def test_recorded_trace_reads_as_before():
    """The recorded v5e trace reads what the reduction read before it took
    the program's spans: busy time, kernels and programs."""
    tr = T.load(os.path.join(DATA, "serve_probe.xplane.pb"))
    assert tr["host"] == []               # no bench.window: no spans kept
    dev = tr["devices"]["/device:TPU:0"]
    events = dev["ops"] + dev["modules"]
    lo = min(s for n, s, d in events)
    hi = max(s + d for n, s, d in events)
    tr["host"].append((T.WINDOW, lo, hi - lo))
    red = T.reduce(tr)
    exact = pytest.approx
    assert red.window_s == exact(1.482863171, rel=1e-12)
    assert red.busy_s == exact(1.109026284, rel=1e-12)
    assert red.kernel_s("paged_attention") == exact(0.900702224, rel=1e-12)
    assert red.kernel_s("page_migrate") == exact(0.179996176, rel=1e-12)
    assert red.kernel_s("select_topk") == exact(7.8025e-05, rel=1e-12)
    assert (red.op_calls["paged_attention"], red.op_calls["page_migrate"],
            red.op_calls["select_topk"]) == (200, 48, 12)
    assert red.module_s["jit__decode"] == exact(0.92910143, rel=1e-12)
    assert red.module_s["jit__apply"] == exact(0.180395379, rel=1e-12)
    assert (red.module_calls["jit__decode"],
            red.module_calls["jit__apply"]) == (200, 12)
    assert red.idle_by_span == {T.OUTSIDE: exact(0.373836887, rel=1e-9)}
