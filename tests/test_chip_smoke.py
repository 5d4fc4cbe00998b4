"""``chip_smoke.py`` off the chip: it refuses to report without a TPU, and
its phases pass on the CPU at small sizes (Pallas kernels in interpret
mode or through their refs), so a chip run only has the chip to find."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def force(monkeypatch):
    from repro.kernels import ops

    def pin(path):
        monkeypatch.setattr(ops, "FORCE", path)
    return pin


def _run(args, cwd, tmp_path, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **env)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True])
def test_fails_without_tpu_or_repo(tmp_path, alone):
    """On the CPU, and as a lone copy without the repo's source, the
    script exits non-zero and prints no ``ok`` line."""
    cwd = ROOT
    if alone:
        cwd = str(tmp_path / "alone")
        os.makedirs(cwd)
        shutil.copy(SCRIPT, cwd)
    r = _run(["chip_smoke.py"], cwd, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if not alone:
        assert "[FAIL] JAX platform is tpu (got cpu)" in r.stdout


def test_tuning_phase_passes_on_cpu(smoke, force):
    force("ref")
    smoke.tuning_phase(scale=0.25, budget=16, batch=8)


def test_serving_phase_passes_on_cpu(smoke, force):
    import jax.numpy as jnp
    from repro.core.tiered_kv import KVSpec
    force("pallas")
    spec = KVSpec(n_layers=2, kv_heads=2, head_dim=128, page_tokens=16,
                  dtype=jnp.bfloat16)
    smoke.serving_phase(spec=spec, n_heads=4, max_pages=8, hbm_pages=24,
                        steps=80, engine_every=8)


def test_serving_phase_catches_divergence(smoke, force, monkeypatch):
    """A reference that never migrates fails the phase."""
    import jax.numpy as jnp
    from bench.reference.serve_ref import TieredKVReference
    from repro.core.tiered_kv import KVSpec
    force("ref")
    monkeypatch.setattr(TieredKVReference, "engine", lambda self, dt_ms: 0)
    spec = KVSpec(n_layers=1, kv_heads=2, head_dim=128, page_tokens=16,
                  dtype=jnp.bfloat16)
    with pytest.raises(smoke.SmokeFailure, match="compiled == reference"):
        smoke.serving_phase(spec=spec, n_heads=2, max_pages=8, hbm_pages=24,
                            steps=80, engine_every=8)


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The pmapped B=8 epoch loop equals the one-device jit on four
    virtual CPU devices (the multi-chip path's control flow)."""
    code = ("import sys; sys.path.insert(0, '.'); import chip_smoke as s; "
            "from repro.kernels import ops; ops.FORCE = 'ref'; "
            "s.four_chip_phase(scale=0.02)")
    r = _run(["-c", code], ROOT, tmp_path,
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[PASS] pmapped B=8 == one-device jit, bitwise" in r.stdout


def test_last_line_is_the_device_json(smoke, capsys, monkeypatch):
    """With every check passing, the last stdout line is exactly the
    JSON object the contract names (phases stubbed: no TPU here)."""
    from repro.core import simulator
    from repro.kernels import ops
    monkeypatch.setattr(simulator, "enable_compile_cache", lambda: ROOT)
    monkeypatch.setattr(smoke, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(ops, "FORCE", "pallas")
    monkeypatch.setattr(ops, "interpret", lambda: False)
    ran = []
    monkeypatch.setattr(smoke, "tuning_phase", lambda: ran.append("t"))
    monkeypatch.setattr(smoke, "serving_phase", lambda: ran.append("s"))
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert ran == ["t", "s"]


def test_serving_phase_catches_nan_output(smoke, force, monkeypatch):
    """A decode step whose attention output is all NaN fails the phase:
    the attention check must not read a NaN error as a small one."""
    import jax.numpy as jnp
    from repro.core.tiered_kv import KVSpec, TieredKVCache
    force("ref")
    real = TieredKVCache.decode_step

    def nan_out(self, *a, **kw):
        return jnp.full_like(real(self, *a, **kw), jnp.nan)

    monkeypatch.setattr(TieredKVCache, "decode_step", nan_out)
    spec = KVSpec(n_layers=1, kv_heads=2, head_dim=128, page_tokens=16,
                  dtype=jnp.bfloat16)
    with pytest.raises(smoke.SmokeFailure, match="decode attention"):
        smoke.serving_phase(spec=spec, n_heads=2, max_pages=8, hbm_pages=24,
                            steps=80, engine_every=8)
