"""The comparison that decides ``correct``: sound runs pass; the control
(the reference in the precision below the configuration's) and the faults
a cell can have fail.  At tiny sizes on the CPU, through the drivers.

The faults break the timed path underneath the harness:

* a step that returns its state unchanged (tuning: the epoch loop's plan
  never moves a page; serving: a decode step that appends nothing);
* half of the batch left out, the mean taken over the rest;
* an answer altered where it is produced.

The exchange between chips does not exist in these one-chip cells.
"""

import numpy as np
import pytest

import bench_tiny
from bench import harness as H

TUNE = "tune.gups-hemem.q16"
SERVE = "serve.cmdrplus-kv.tight"
CELLS = [w["name"]
         for w in H.load_json(H.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out, _, _ = bench_tiny.run_tiny(cell, seed=2 ** 31 + 7)
    assert bench_tiny.correct(out), out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    cell_ = bench_tiny.tiny_cell(cell)
    driver = H.load_module(cell_.driver_path, "ctl_" + cell_.kind)
    out, _, _ = bench_tiny.run_tiny(cell, control=driver.control_precision())
    assert not bench_tiny.correct(out), out["checks"]


# -- tuning faults -------------------------------------------------------------
def _tune_fault(monkeypatch, fault):
    from repro.core import engine_jax
    from repro.core.study import Study
    if fault == "unchanged":
        def plan(self, st, kv, keys, e, reads, writes, in_fast, allocated,
                 est_wall, max_pages):
            import jax.numpy as jnp
            none = jnp.zeros((self.B, self.n), bool)
            return st, none, none, jnp.zeros(self.B, jnp.float32)
        monkeypatch.setattr(engine_jax._HeMemDef, "plan", plan)
        engine_jax._COMPILED.clear()
    else:
        run = Study.run

        def broken(self, configs=None):
            res = run(self, configs=configs)
            if configs is None:
                return res
            if fault == "half_batch" and len(res) > 1:
                keep = res[:(len(res) + 1) // 2]
                mean = float(np.mean([r.total_s for r in keep]))
                for r in res[len(keep):]:
                    r.total_s = mean
            if fault == "altered":
                res[0].total_s *= 1.5
            return res
        monkeypatch.setattr(Study, "run", broken)


def _run_tune_fault(monkeypatch, cell, fault):
    from repro.core import engine_jax
    _tune_fault(monkeypatch, fault)
    try:
        out, _, _ = bench_tiny.run_tiny(cell)
    finally:
        engine_jax._COMPILED.clear()
    assert not bench_tiny.correct(out), out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_tuning_faults_fail(monkeypatch, fault):
    _run_tune_fault(monkeypatch, TUNE, fault)


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_sequential_tuning_faults_fail(monkeypatch, fault):
    """The q1 cell evaluates one candidate at a time: it has no batch to
    leave half of out."""
    _run_tune_fault(monkeypatch, "tune.gups-hemem.q1", fault)


# -- serving faults ------------------------------------------------------------
def _serve_fault(monkeypatch, fault):
    import jax.numpy as jnp
    from repro.core.tiered_kv import TieredKVCache
    decode = TieredKVCache.decode_step

    def broken(self, k_new, v_new, q, active=None, dt_ms=None):
        if fault == "unchanged":
            # attend without appending: the step leaves the cache as it was
            self._st, out, res, tot = self._srv.attend(
                self._st, jnp.asarray(q), jnp.ones(self.batch, bool))
            return out
        if fault == "half_batch":
            half = np.arange(self.batch) < (self.batch + 1) // 2
            out = decode(self, k_new, v_new, q, active=half)
            keep = out[:len(half) // 2 + len(half) % 2]
            return out.at[~half].set(keep.mean(axis=0))
        out = decode(self, k_new, v_new, q, active, dt_ms)
        return out.at[0, 0, 0].add(jnp.abs(out).max() * 0.25)
    monkeypatch.setattr(TieredKVCache, "decode_step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_serving_faults_fail(monkeypatch, fault):
    _serve_fault(monkeypatch, fault)
    out, _, _ = bench_tiny.run_tiny(SERVE)
    assert not bench_tiny.correct(out), out["checks"]
