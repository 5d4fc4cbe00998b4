import os
import sys

# tests must see ONE device (the dry-run sets 512 itself, in its own process)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the checkout itself, for the plain references under bench/reference
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

# CI conformance matrix: REPRO_KERNELS_FORCE=pallas|ref pins the kernels
# dispatch (repro.kernels.ops.FORCE) for the whole session, so both paths
# run the full suite on CPU (pallas in interpret mode)
_force = os.environ.get("REPRO_KERNELS_FORCE")
if _force:
    if _force not in ("pallas", "ref"):
        raise ValueError(
            f"REPRO_KERNELS_FORCE must be 'pallas' or 'ref', got {_force!r}")
    from repro.kernels import ops as _kernel_ops
    _kernel_ops.FORCE = _force

# CI surrogate matrix: REPRO_SURROGATE_FORCE=reference|fast pins the BO
# forest builder (repro.core.bo.rf.FORCE) for the whole session, so both
# paths run the suite (they must be bit-identical — tests/test_bo.py)
_sforce = os.environ.get("REPRO_SURROGATE_FORCE")
if _sforce:
    if _sforce not in ("reference", "fast"):
        raise ValueError("REPRO_SURROGATE_FORCE must be 'reference' or "
                         f"'fast', got {_sforce!r}")
    from repro.core.bo import rf as _bo_rf
    _bo_rf.FORCE = _sforce
