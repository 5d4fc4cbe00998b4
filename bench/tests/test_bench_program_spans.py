"""The program's own host spans (``repro.*``) beside the benchmark's: what
the accepted tuning readers read does not depend on them."""

import pytest

import bench_tiny
from bench import harness as H
from bench import trace_reduce as T

READERS = ["bo_host_share.tune", "epoch_loop_ms_per_eval.tune",
           "select_topk_roofline.tune", "idle_share.tune"]


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


def _read(red):
    cell = bench_tiny.tiny_cell("tune.gups-hemem.q16")
    rec = {"studies": [{"round_times": [
               {"ask_s": 1.8e-7, "fit_s": 9e-8, "eval_s": 7e-7,
                "tell_s": 6e-8, "q": 16.0}]}],
           "evals": [{"value": 1.0}] * 16, "n_epochs": 60, "n_pages": 655}
    ctx = {"peaks": H.peaks_for("TPU v5 lite"), "config": cell.config,
           "traffic": cell.traffic, "work": H.work,
           "roofline_share": H.roofline_share}
    out = {}
    for name in READERS:
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


def test_program_spans_name_the_idle_gaps():
    bare, spanned = T.reduce(_trace(False)), T.reduce(_trace(True))
    assert set(bare.idle_by_span) == {"bench.study"}
    # each gap between the device's work falls, by its midpoint, in the fit,
    # the trace build or the tell
    assert spanned.idle_by_span == {
        "repro.bo.fit": pytest.approx(160e-9),
        "repro.sim.trace": pytest.approx(295e-9),
        "repro.bo.tell": pytest.approx(130e-9)}
    assert sum(spanned.idle_by_span.values()) == \
        pytest.approx(sum(bare.idle_by_span.values()))
