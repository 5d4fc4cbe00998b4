"""TieredKVCache — the paper's technique as a first-class serving feature.

Two-tier paged KV cache for long-context decode on TPU:
  fast tier = HBM page pool (jnp arrays, attended by the Pallas
              paged-attention kernel);
  slow tier = host-DRAM page pool (on a real v5e host this is the
              PCIe-attached host memory JAX host-offload uses).

The HeMem mechanism maps 1:1 (DESIGN.md §2):
  PEBS access sampling  -> per-page ATTENTION-MASS access counts (reads)
                           and appends (writes), subsampled by
                           sampling_period / write_sampling_period;
  hot/cold thresholds   -> the same read/write_hot_threshold knobs;
  cooling               -> identical batched halving (cooling_threshold,
                           cooling_pages);
  migration thread      -> step_engine(dt) promotes/demotes whole pages,
                           rate-limited by max_migration_rate and the ring
                           sizes; the device-side copy is the page_migrate
                           Pallas kernel.

Decode attends over the HBM-RESIDENT pages of each sequence (attention-mass
concentrates on few pages in long contexts; the engine's job — and the
tuner's — is to keep those pages resident).  ``recall()`` reports the
fraction of true attention mass that was resident, the quality metric the
serving benchmark tracks alongside latency.

Every knob keeps its Table-2 name, so the SMAC tuner drives this store
through the exact same KnobSpace as the simulator.

The cache is a stateful wrapper over the jitted functions of
:mod:`~repro.core.serving_jax`::

    cache = TieredKVCache(spec, batch=256, max_pages_per_seq=32,
                          hbm_pages=2048, config=cfg)
    out = cache.decode_step(k, v, q)           # ONE jitted call per step
    cache.step_engine(50.0)                    # batched migrations

``decode_step`` fuses append + paged attention + read recording; engine
epochs batch all page moves through one ``page_migrate`` call per
direction.  The decision math is the **lifted engine** ``kv-hemem``
(:class:`~repro.core.engine_jax.KVHeMemDef` — registered via
``register_jax_engine``, so ``backend="jax"`` simulations of ``kv-hemem``
compile instead of falling back to the numpy loop), compiled once per
cache geometry.

The plain reference is ``bench/reference/serve_ref.py``
(``TieredKVReference``): a numpy replay of the same append, record, engine
and reset semantics that imports nothing of this package.  The tests
(``tests/test_serving.py``), ``chip_smoke.py`` and the benchmark's serving
cell hold this cache to it: equal residency, lengths and migration counts,
and attention outputs within the cell's ``attn_gap`` limit.

Lifted-engine contract (what ``kv-hemem`` implements): pure
``knobs``/``init``/``observe``/``plan`` over ``(B, pages)`` arrays — see
:class:`~repro.core.engine_jax._EngineDef` for the full protocol.  Serving
uses deterministic mean sampling (``counts / period``) because the
attention kernel measures page mass exactly; the simulator twin
(``repro.core.engine.BatchKVHeMemEngine``) draws the same means.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.knobs import HEMEM_SPACE
from repro.core.serving_jax import get_serving


@dataclasses.dataclass(frozen=True)
class KVSpec:
    n_layers: int
    kv_heads: int
    head_dim: int
    page_tokens: int = 64
    dtype: Any = jnp.bfloat16


class TieredKVCache:
    """Single-sequence-group paged KV cache (batch of B sequences that share
    a page pool).  Holds the state pytree of the jitted serving functions
    (see module docstring); every method is one call on it.

    ``compiled`` is accepted for callers that still pass ``True``; there is
    one implementation, and ``False`` raises ``ValueError``."""

    def __init__(self, spec: KVSpec, batch: int, max_pages_per_seq: int,
                 hbm_pages: int, config: Optional[Mapping[str, Any]] = None,
                 seed: int = 0, compiled: bool = True):
        if not compiled:
            raise ValueError(
                "TieredKVCache has one implementation, the compiled one; "
                "compiled=False is not supported (the plain reference is "
                "bench/reference/serve_ref.py)")
        self.spec = spec
        self.batch = batch
        self.max_pages = max_pages_per_seq
        self.n_logical = batch * max_pages_per_seq
        self.hbm_pages = hbm_pages
        self.config = HEMEM_SPACE.validate(dict(config or {}))
        # jitted serving functions, shared by every cache of this geometry
        self._srv = get_serving(spec, batch, max_pages_per_seq, hbm_pages)
        self._kv = self._srv.edef.knobs([self.config])
        self._st = self._srv.fresh_state()
        self._last_pages: Optional[Tuple[Any, Any]] = None

    # -- state views -------------------------------------------------------
    # reads are materialized with copy=True: the serving jits donate their
    # state pytree, so a zero-copy view of a device buffer could be
    # overwritten in place by the next step
    @property
    def lengths(self) -> np.ndarray:
        return np.array(self._st["lengths"], copy=True)

    @property
    def slot_of(self) -> np.ndarray:
        """HBM slot of every logical page ``seq * max_pages + i``; -1 where
        the page is in the slow tier or unused."""
        return np.array(self._st["slot_of"][:self.n_logical], copy=True)

    @property
    def page_of_slot(self) -> np.ndarray:
        return np.array(self._st["page_of_slot"][:self.hbm_pages], copy=True)

    @property
    def migrations(self) -> int:
        return int(self._st["migrations"])

    @migrations.setter
    def migrations(self, v: int):
        self._st = dict(self._st, migrations=jnp.int32(v))

    @property
    def last_step_pages(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(resident_pages, total_pages) per sequence for the most recent
        recorded step — the inputs of the benchmark's latency model.
        Materialized lazily: the decode loop stays asynchronous unless the
        caller actually reads these."""
        if self._last_pages is None:
            return None
        return tuple(np.array(a, copy=True) for a in self._last_pages)

    def _active(self, active) -> np.ndarray:
        if active is None:
            return np.ones(self.batch, bool)
        return np.asarray(active, bool)

    # -- decode ------------------------------------------------------------
    def append(self, k_new: np.ndarray, v_new: np.ndarray, active=None):
        """k/v_new: (B, L, KV, D) — one token per (active) sequence.  New
        tokens land in the HBM tier first (first-touch), falling back to
        host."""
        self._st = self._srv.append(self._st, jnp.asarray(k_new),
                                    jnp.asarray(v_new),
                                    jnp.asarray(self._active(active)))

    def attend(self, q: np.ndarray, active=None) -> jnp.ndarray:
        """q: (B, H, D) one decode step (single layer's query is the common
        case; for multi-layer pools q attends the layer-0 view and the
        access statistics apply to the whole page).  Returns (B, H, D) and
        records the step's attention-mass reads."""
        self._st, out, res, tot = self._srv.attend(
            self._st, jnp.asarray(q), jnp.asarray(self._active(active)))
        self._last_pages = (res, tot)   # device arrays; see property
        return out

    def decode_step(self, k_new, v_new, q, active=None,
                    dt_ms: Optional[float] = None) -> jnp.ndarray:
        """The fused serving step: append + attend + record in ONE jitted
        call (plus one engine epoch when ``dt_ms`` is given)."""
        act = self._active(active)
        self._st, out, res, tot = self._srv.decode(
            self._st, jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(q), jnp.asarray(act))
        self._last_pages = (res, tot)   # device arrays; see property
        if dt_ms is not None:
            self.step_engine(dt_ms)
        return out

    # -- tiering (the paper's engine — the lifted kv-hemem def) ------------
    def step_engine(self, dt_ms: float):
        """One engine epoch: observe accumulated access counts, plan, and
        apply the promote/demote masks with batched ``page_migrate``."""
        self._st, _ = self._srv.engine_step(self._st, self._kv, dt_ms)

    # -- sequence lifecycle (traffic replay) -------------------------------
    def reset_seqs(self, done):
        """Retire finished sequences (boolean ``(B,)`` mask): zero their
        lengths, access counters and engine heat, free their HBM slots.
        Pool rows keep stale data; the next occupant overwrites them."""
        self._st = self._srv.reset_seqs(
            self._st, jnp.asarray(np.asarray(done, bool)))

    # -- metrics -----------------------------------------------------------
    def recall(self) -> float:
        """Fraction of true attention mass served from the fast tier."""
        return float(self._st["recall_num"]) / \
            max(float(self._st["recall_den"]), 1e-12)

    def hbm_utilization(self) -> float:
        return float((self.page_of_slot >= 0).mean())
