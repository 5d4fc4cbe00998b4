"""The program's own host spans (``repro.*``) beside the benchmark's: what
the device-trace readers read does not depend on them, and the span
readers read them."""

import pytest

import bench_tiny
from bench import harness as H
from bench import trace_reduce as T

READERS = ["epoch_loop_ms_per_eval.tune", "select_topk_roofline.tune",
           "idle_share.tune"]
#: each span reader's answer on the hand-made trace (see the idle split in
#: ``test_program_spans_name_the_idle_gaps``; the trace is in ns)
SPAN_READERS = {f"{name}.{cell}": v
                for name, v in [("sim_launch_ms_per_call", 80e-6),
                                ("bo_fit_ms", 90e-6), ("idle_in_sim", 29.0),
                                ("idle_in_bo", 21.5)]
                for cell in ("tune", "seq_tune")}


def _trace(program_spans):
    # window [0, 1000); a study [10, 990) with one round of the program's
    # spans: an ask holding a fit and the pool's acquisition, an evaluation
    # holding the simulator's trace build, launch and fetch, a tell
    host = [("bench.window", 0, 1000), ("bench.study", 10, 980)]
    if program_spans:
        host += [("repro.study.tune", 15, 970), ("repro.bo.ask", 20, 180),
                 ("repro.bo.fit", 30, 90), ("repro.bo.pool", 140, 55),
                 ("repro.bo.acquire", 150, 40),
                 ("repro.study.eval", 200, 700), ("repro.sim.run", 210, 680),
                 ("repro.sim.trace", 220, 200), ("repro.sim.launch", 420, 80),
                 ("repro.sim.fetch", 500, 380), ("repro.bo.tell", 900, 60)]
    modules = [("jit_impl(1)", 160, 25), ("jit_run(2)", 480, 390)]
    ops = [("%fusion.1 = f32[] fusion(...)", 160, 25),
           ("%while.2 = (...) while(...)", 480, 390),
           ("%select_topk.3 = (...) custom-call(...)", 500, 100),
           ("%select_topk.3 = (...) custom-call(...)", 700, 100)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def _read(red, readers=READERS):
    cell = bench_tiny.tiny_cell("tune.gups-hemem.q16")
    rec = {"evals": [{"value": 1.0}] * 16, "n_epochs": 60, "n_pages": 655}
    ctx = {"peaks": H.peaks_for("TPU v5 lite"), "config": cell.config,
           "traffic": cell.traffic, "work": H.work,
           "roofline_share": H.roofline_share}
    out = {}
    for name in readers:
        mod = H.load_module(f"{H.BENCH}/metrics/{name}.py",
                            "bench_metric_" + name.replace(".", "_"))
        out[name] = mod.read(red, rec, ctx)
    return out


def test_accepted_readers_read_the_same_with_program_spans():
    bare, spanned = T.reduce(_trace(False)), T.reduce(_trace(True))
    for field in ("window_s", "busy_s", "op_s", "op_calls", "module_s",
                  "module_calls", "module_op_s"):
        assert getattr(spanned, field) == getattr(bare, field), field
    assert _read(spanned) == _read(bare)
    assert all(v is not None for v in _read(bare).values())


@pytest.mark.parametrize("name", [r.split(".")[0] for r in READERS])
def test_sequential_cell_readers_read_as_the_batched(name):
    # the sequential tuning cell's device-trace readers, on the same trace
    got = _read(T.reduce(_trace(True)), [f"{name}.tune", f"{name}.seq_tune"])
    assert got[f"{name}.tune"] is not None
    assert got[f"{name}.seq_tune"] == got[f"{name}.tune"]


def test_program_spans_name_the_idle_gaps():
    bare, spanned = T.reduce(_trace(False)), T.reduce(_trace(True))
    # idle: [0, 160), [185, 480), [870, 1000)
    assert bare.idle_by_span == {"bench.study": pytest.approx(565e-9),
                                 T.OUTSIDE: pytest.approx(20e-9)}
    # each gap split over the self time of the spans it overlaps
    ns = {T.OUTSIDE: 20, "bench.study": 10, "repro.study.tune": 30,
          "repro.bo.ask": 35, "repro.bo.fit": 90, "repro.bo.pool": 15,
          "repro.bo.acquire": 15, "repro.study.eval": 20,
          "repro.sim.run": 20, "repro.sim.trace": 200,
          "repro.sim.launch": 60, "repro.sim.fetch": 10,
          "repro.bo.tell": 60}
    assert spanned.idle_by_span == {k: pytest.approx(v * 1e-9)
                                    for k, v in ns.items()}
    assert sum(spanned.idle_by_span.values()) == \
        pytest.approx(sum(bare.idle_by_span.values()))


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_the_hand_made_trace(name):
    got = _read(T.reduce(_trace(True)), [name])[name]
    assert got == pytest.approx(SPAN_READERS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_finds_nothing_without_program_spans(name):
    assert _read(T.reduce(_trace(False)), [name])[name] is None


def test_program_spans_read_from_a_recorded_trace():
    """A tiny q16 run traced on this host: the program's spans reach the
    reduction with their counts, one launch and one fetch per simulation."""
    import shutil
    try:
        _, h, _ = bench_tiny.run_tiny("tune.gups-hemem.q16", trace=True)
        red = T.reduce(T.load(h.trace_path))
    finally:
        shutil.rmtree(H.TRACE_DIR, ignore_errors=True)
    calls = red.span_calls
    assert calls["repro.sim.run"] == calls["repro.sim.launch"] == \
        calls["repro.sim.fetch"] > 0
    assert red.span_counts["repro.sim.launch"]["h2d_bytes"] > 0
    assert red.span_counts["repro.sim.trace"]["cache_hit"] == \
        calls["repro.sim.trace"]
    assert 0 < red.span_self_s["repro.sim.run"] < red.span_s["repro.sim.run"]
    assert red.self_ms_per_call("repro.sim.launch") > 0
