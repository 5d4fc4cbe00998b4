"""Program spans on the tuning path (``repro.core.spans``).

A tiny ``Study.tune`` runs with the JAX profiler on; the spans are read back
from the trace it writes, as the benchmark reads them, and checked for their
names, nesting, shared round ids and counts.  ``round_times`` and
``SMACOptimizer.fit_s`` are the same spans' durations.
"""

import collections
import glob
import sys
import time

import numpy as np
import pytest

from repro.core import ExperimentSpec, SimOptions, Study, WorkloadSpec
from repro.core import spans
from repro.core.bo.smac import SMACOptimizer
from repro.core.knobs import get_space

jax = pytest.importorskip("jax")

Span = collections.namedtuple("Span", "name start end stats parent")

#: span -> the span that opens it (nesting follows the ``with`` blocks)
PARENT = {
    "repro.study.tune": None,
    "repro.study.eval": "repro.study.tune",
    "repro.bo.ask": "repro.study.tune",
    "repro.bo.tell": "repro.study.tune",
    "repro.bo.fit": "repro.bo.ask",
    "repro.bo.pool": "repro.bo.ask",
    "repro.bo.acquire": "repro.bo.pool",
    "repro.sim.run": "repro.study.eval",
    "repro.sim.trace": "repro.sim.run",
    "repro.sim.launch": "repro.sim.run",
    "repro.sim.fetch": "repro.sim.run",
    "repro.sim.results": "repro.sim.run",
}


def _study(fast_capacity_pages=None):
    return Study(ExperimentSpec(
        engine="hemem", workload=WorkloadSpec("gups", scale=0.02),
        fast_capacity_pages=fast_capacity_pages,
        options=SimOptions(backend="jax", sampler="elementwise")))


def _read_spans(trace_dir):
    """The ``repro.`` host events of the trace, each with its parent."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("repro.")]
    out, stack = [], []
    for name, a, b, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1].end <= a:
            stack.pop()
        sp = Span(name, a, b, stats, stack[-1].name if stack else None)
        out.append(sp)
        stack.append(sp)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One ``Study.tune(budget=8, batch_size=4)`` under the profiler.  Its
    fast tier of 77 pages is a shape no other test compiles, so the study's
    first B=1 and first B=4 launches build new programs."""
    study = _study(fast_capacity_pages=77)
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        result = study.tune(budget=8, batch_size=4, seed=1, n_init=2)
    finally:
        jax.profiler.stop_trace()
    return study, result, _read_spans(trace_dir)


def test_tune_emits_every_span_nested_as_its_layers(traced):
    _, _, got = traced
    assert {s.name for s in got} == set(PARENT)
    for s in got:
        assert s.parent == PARENT[s.name], s
    calls = collections.Counter(s.name for s in got)
    # the default evaluation and two rounds of 4; the first round is the
    # random initial design, so only the second fits and acquires
    assert calls["repro.study.tune"] == 1
    assert calls["repro.study.eval"] == 3
    assert calls["repro.bo.ask"] == calls["repro.bo.tell"] == 2
    for name in ("repro.sim.run", "repro.sim.trace", "repro.sim.launch",
                 "repro.sim.fetch", "repro.sim.results"):
        assert calls[name] == 3, name
    assert calls["repro.bo.fit"] == calls["repro.bo.pool"] == \
        calls["repro.bo.acquire"] == 1


def test_a_round_id_is_shared_by_its_spans(traced):
    _, _, got = traced
    rounds = collections.defaultdict(set)
    for s in got:
        if "round" in s.stats:
            rounds[s.stats["round"]].add(s.name)
    # the default evaluation runs before round 0
    assert rounds[-1] == {"repro.study.eval", "repro.sim.run"}
    for r in (0, 1):
        assert rounds[r] == {"repro.bo.ask", "repro.study.eval",
                             "repro.sim.run", "repro.bo.tell"}, r
    for s in got:
        if s.name in ("repro.bo.ask", "repro.bo.tell"):
            assert s.stats["q"] == 4
        if s.name == "repro.sim.run":
            assert s.stats["B"] == (1 if s.stats["round"] == -1 else 4)


def test_span_counts(traced):
    study, _, got = traced
    wl = study.workload()
    by = collections.defaultdict(list)
    for s in got:
        by[s.name].append(s)
    assert by["repro.study.tune"][0].stats == {"budget": 8, "batch_size": 4}
    # the study's one workload: its trace is built and copied once
    assert [s.stats for s in by["repro.sim.trace"]] == [
        {"epochs": wl.n_epochs, "pages": wl.n_pages, "cache_hit": hit}
        for hit in (0, 1, 1)]
    # a launch hands over the knob vectors and constants, no (n,) array
    for s in by["repro.sim.launch"]:
        assert 0 < s.stats["h2d_bytes"] < 64 * 1024
    assert [s.stats["cache_miss"] for s in by["repro.sim.launch"]] == \
        [1, 1, 0]
    fit, = by["repro.bo.fit"]
    # round 0's four: the default evaluation is not an observation
    assert fit.stats == {"n_obs": 4}
    pool, = by["repro.bo.pool"]
    acquire, = by["repro.bo.acquire"]
    assert acquire.stats["pool"] == pool.stats["n_candidates"] >= 512
    assert 1 <= acquire.stats["q"] <= 4


def test_round_times_are_the_spans(traced):
    _, result, got = traced
    rt = result.round_times
    assert [set(r) for r in rt] == \
        [{"ask_s", "fit_s", "eval_s", "tell_s", "q"}] * 2
    for r in rt:
        assert r["ask_s"] >= r["fit_s"] >= 0 and r["q"] == 4.0
    for key, name in (("ask_s", "repro.bo.ask"), ("tell_s", "repro.bo.tell"),
                      ("eval_s", "repro.study.eval")):
        traced_s = [(s.end - s.start) / 1e9 for s in got if s.name == name
                    and s.stats["round"] >= 0]
        for r, t in zip(rt, traced_s):
            assert abs(t - r[key]) < 0.05, key
    fit_s = [(s.end - s.start) / 1e9 for s in got if s.name == "repro.bo.fit"]
    assert rt[0]["fit_s"] == 0.0
    assert abs(fit_s[0] - rt[1]["fit_s"]) < 0.05


@pytest.mark.parametrize("batch_size", [1, 3])
def test_round_times_without_the_profiler(batch_size):
    res = _study().tune(budget=5, batch_size=batch_size, seed=2, n_init=2)
    rounds = -(-5 // batch_size)
    assert len(res.round_times) == rounds
    assert [r["q"] for r in res.round_times] == \
        [float(min(batch_size, 5 - i * batch_size)) for i in range(rounds)]
    for r in res.round_times:
        assert r["eval_s"] > 0 and r["ask_s"] >= r["fit_s"] >= 0
        assert r["tell_s"] >= 0
    assert sum(r["fit_s"] for r in res.round_times) > 0


def test_fit_s_sums_the_fit_spans():
    space = get_space("hemem")
    opt = SMACOptimizer(space, seed=0, n_init=2)
    rng = np.random.default_rng(0)
    for _ in range(4):
        opt.tell(space.sample(rng), float(rng.uniform()))
    assert opt.fit_s == 0.0
    opt.surrogate()
    first = opt.fit_s
    assert first > 0
    opt.surrogate()                     # cached: no second fit
    assert opt.fit_s == first
    opt.tell(space.sample(rng), 0.5)
    opt.surrogate()
    assert opt.fit_s > first


def test_span_times_its_block_with_or_without_jax(monkeypatch):
    with spans.span("repro.test", n=1) as sp:
        time.sleep(0.01)
        sp.count(more=2)
    assert 0.01 <= sp.s < 1.0
    # a process that never imported jax cannot be profiled: the clock alone
    monkeypatch.setitem(sys.modules, "jax", None)
    with spans.span("repro.test", n=1) as sp:
        time.sleep(0.01)
        sp.count(more=2)
    assert 0.01 <= sp.s < 1.0


def test_span_counts_reach_the_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("repro.outer", a=3):
            with spans.span("repro.inner") as sp:
                sp.count(b=4)
    finally:
        jax.profiler.stop_trace()
    got = {s.name: s for s in _read_spans(tmp_path)}
    assert got["repro.outer"].stats == {"a": 3}
    assert got["repro.inner"].stats == {"b": 4}
    assert got["repro.inner"].parent == "repro.outer"


def test_round_of_nests_and_restores():
    assert spans.current_round() == -1
    with spans.round_of(3):
        assert spans.current_round() == 3
        with pytest.raises(RuntimeError):
            with spans.round_of(5):
                assert spans.current_round() == 5
                raise RuntimeError
        assert spans.current_round() == 3
    assert spans.current_round() == -1


def test_host_bytes_counts_numpy_leaves_only():
    from repro.core import engine_jax
    assert engine_jax.have_jax()
    tree = {"a": np.zeros(3, np.float32), "b": jax.numpy.zeros(5)}
    assert engine_jax._host_bytes(tree, np.float32(1), None,
                                  np.arange(4, dtype=np.int64)) == \
        12 + 4 + 32
