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

Compiled serving
----------------

``TieredKVCache(..., compiled=True)`` replaces the per-page Python loops
with the fused jitted step from :mod:`~repro.core.serving_jax`::

    cache = TieredKVCache(spec, batch=256, max_pages_per_seq=32,
                          hbm_pages=2048, config=cfg, compiled=True)
    out = cache.decode_step(k, v, q)           # ONE jitted call per step
    cache.step_engine(50.0)                    # batched migrations

``decode_step`` fuses append + paged attention + read recording; engine
epochs batch all page moves through one ``page_migrate`` call per
direction.  Both modes share the exact same engine arithmetic: the
decision math is the **lifted engine** ``kv-hemem``
(:class:`~repro.core.engine_jax.KVHeMemDef` — registered via
``register_jax_engine``, so ``backend="jax"`` simulations of ``kv-hemem``
compile instead of falling back to the numpy loop), compiled once per
cache geometry and invoked by the reference loop and the compiled path
alike.  Page-residency sets and migration counts are therefore
bit-identical across modes (pinned by ``tests/test_serving.py``); the
reference loop remains the readable specification, the compiled path is
the fast one.

Lifted-engine contract (what ``kv-hemem`` implements): pure
``knobs``/``init``/``observe``/``plan`` over ``(B, pages)`` arrays — see
:class:`~repro.core.engine_jax._EngineDef` for the full protocol.  Serving
uses deterministic mean sampling (``counts / period``) because the
attention kernel measures page mass exactly; the simulator twin
(``repro.core.engine.BatchKVHeMemEngine``) draws the same means.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.knobs import HEMEM_SPACE
from repro.core.serving_jax import get_serving, step_read_counts
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class KVSpec:
    n_layers: int
    kv_heads: int
    head_dim: int
    page_tokens: int = 64
    dtype: Any = jnp.bfloat16


class TieredKVCache:
    """Single-sequence-group paged KV cache (batch of B sequences that share
    a page pool).  ``compiled=False`` runs the per-page Python reference
    loop; ``compiled=True`` the fused jitted step (see module docstring)."""

    def __init__(self, spec: KVSpec, batch: int, max_pages_per_seq: int,
                 hbm_pages: int, config: Optional[Mapping[str, Any]] = None,
                 seed: int = 0, compiled: bool = False):
        self.spec = spec
        self.batch = batch
        self.max_pages = max_pages_per_seq
        n_logical = batch * max_pages_per_seq
        self.n_logical = n_logical
        self.hbm_pages = hbm_pages
        self.compiled = compiled

        s = spec
        page_shape = (s.n_layers, s.page_tokens, s.kv_heads, s.head_dim)
        self.page_elems = int(np.prod(page_shape))
        self.page_shape = page_shape

        self.config = HEMEM_SPACE.validate(dict(config or {}))
        # jitted serving functions + the shared engine-decision executable
        self._srv = get_serving(spec, batch, max_pages_per_seq, hbm_pages)
        self._kv = self._srv.edef.knobs([self.config])
        self._epoch = 0
        self._last_pages: Optional[Tuple[np.ndarray, np.ndarray]] = None

        if compiled:
            self._st = self._srv.fresh_state()
            return

        self.hbm_k = jnp.zeros((hbm_pages,) + page_shape, s.dtype)
        self.hbm_v = jnp.zeros((hbm_pages,) + page_shape, s.dtype)
        self.host_k = np.zeros((n_logical,) + page_shape, np.float32)
        self.host_v = np.zeros((n_logical,) + page_shape, np.float32)

        # logical page -> hbm slot (-1 = host-resident)
        self._slot_of = np.full(n_logical, -1, np.int64)
        self._page_of_slot = np.full(hbm_pages, -1, np.int64)
        self._lengths = np.zeros(batch, np.int64)
        self._allocated = np.zeros(n_logical, bool)

        self._eng = self._srv.edef.init(None)
        self._reads = np.zeros(n_logical, np.int64)
        self._writes = np.zeros(n_logical, np.int64)
        self._migrations = 0
        self._recall_num = 0.0
        self._recall_den = 0.0
        self._mass_fn = None

    # -- state views (identical API across modes) --------------------------
    # compiled-state reads are materialized with copy=True: the serving jits
    # donate their state pytree, so a zero-copy view of a device buffer
    # could be overwritten in place by the next step
    @property
    def lengths(self) -> np.ndarray:
        return np.array(self._st["lengths"], copy=True) if self.compiled \
            else self._lengths

    @property
    def slot_of(self) -> np.ndarray:
        return np.array(self._st["slot_of"][:self.n_logical], copy=True) \
            if self.compiled else self._slot_of

    @property
    def page_of_slot(self) -> np.ndarray:
        return np.array(self._st["page_of_slot"][:self.hbm_pages],
                        copy=True) if self.compiled else self._page_of_slot

    @property
    def migrations(self) -> int:
        return int(self._st["migrations"]) if self.compiled \
            else self._migrations

    @migrations.setter
    def migrations(self, v: int):
        if self.compiled:
            self._st = dict(self._st, migrations=jnp.int32(v))
        else:
            self._migrations = int(v)

    @property
    def last_step_pages(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(resident_pages, total_pages) per sequence for the most recent
        recorded step — the inputs of the benchmark's latency model.
        Materialized lazily: the compiled decode loop stays asynchronous
        unless the caller actually reads these."""
        if self._last_pages is None:
            return None
        return tuple(np.array(a, copy=True) for a in self._last_pages)

    # -- logical addressing ------------------------------------------------
    def _page_id(self, seq: int, page_idx: int) -> int:
        return seq * self.max_pages + page_idx

    def block_table(self) -> jnp.ndarray:
        """(B, max_pages) of HBM slots; -1 where non-resident/unused."""
        tbl = self.slot_of.reshape(self.batch, self.max_pages)
        return jnp.asarray(tbl, jnp.int32)

    def _active(self, active) -> np.ndarray:
        if active is None:
            return np.ones(self.batch, bool)
        return np.asarray(active, bool)

    # -- appends (writes) --------------------------------------------------
    def append(self, k_new: np.ndarray, v_new: np.ndarray, active=None):
        """k/v_new: (B, L, KV, D) — one token per (active) sequence.  New
        tokens land in the HBM tier first (first-touch), falling back to
        host."""
        act = self._active(active)
        if self.compiled:
            self._st = self._srv.append(self._st, jnp.asarray(k_new),
                                        jnp.asarray(v_new),
                                        jnp.asarray(act))
            return
        s = self.spec
        for b in range(self.batch):
            if not act[b]:
                continue
            t = int(self._lengths[b])
            pi, off = divmod(t, s.page_tokens)
            pid = self._page_id(b, pi)
            self._allocated[pid] = True
            self._writes[pid] += 1
            slot = self._slot_of[pid]
            if slot < 0 and off == 0:
                slot = self._grab_slot(pid)     # first touch -> fast tier
            if slot >= 0:
                self.hbm_k = self.hbm_k.at[slot, :, off].set(
                    jnp.asarray(k_new[b], s.dtype))
                self.hbm_v = self.hbm_v.at[slot, :, off].set(
                    jnp.asarray(v_new[b], s.dtype))
            else:
                self.host_k[pid, :, off] = k_new[b]
                self.host_v[pid, :, off] = v_new[b]
            self._lengths[b] = t + 1

    def _grab_slot(self, pid: int) -> int:
        free = np.flatnonzero(self._page_of_slot < 0)
        if len(free) == 0:
            return -1
        slot = int(free[0])
        self._page_of_slot[slot] = pid
        self._slot_of[pid] = slot
        return slot

    # -- attention (reads) -------------------------------------------------
    def attend(self, q: np.ndarray, active=None) -> jnp.ndarray:
        """q: (B, H, D) one decode step (single layer's query is the common
        case; for multi-layer pools q attends the layer-0 view and the
        access statistics apply to the whole page).  Returns (B, H, D).
        Records the step's attention-mass reads (see ``record_reads``)."""
        act = self._active(active)
        if self.compiled:
            self._st, out, res, tot = self._srv.attend(
                self._st, jnp.asarray(q), jnp.asarray(act))
            self._last_pages = (res, tot)   # device arrays; see property
            return out
        tbl = self.block_table()
        out = kops.paged_attention(
            jnp.asarray(q, self.spec.dtype),
            self.hbm_k[:, 0], self.hbm_v[:, 0],
            tbl, jnp.asarray(self._lengths, jnp.int32))
        self.record_reads(active=act)
        return out

    def decode_step(self, k_new, v_new, q, active=None,
                    dt_ms: Optional[float] = None) -> jnp.ndarray:
        """The fused serving step: append + attend + record (+ one engine
        epoch when ``dt_ms`` is given).  In compiled mode this is ONE
        jitted call (plus the engine pair at epochs); in reference mode the
        same operations run through the per-page Python loops."""
        act = self._active(active)
        if self.compiled:
            self._st, out, res, tot = self._srv.decode(
                self._st, jnp.asarray(k_new), jnp.asarray(v_new),
                jnp.asarray(q), jnp.asarray(act))
            self._last_pages = (res, tot)   # device arrays; see property
        else:
            self.append(k_new, v_new, active=act)
            out = self.attend(q, active=act)
        if dt_ms is not None:
            self.step_engine(dt_ms)
        return out

    #: attention-mass -> access-count scale: one decode step reads each
    #: page's tokens across kv heads and layers, so a unit of mass is worth
    #: page_tokens x kv_heads x n_layers "accesses" in PEBS-knob units
    @property
    def READ_SCALE(self) -> float:
        s = self.spec
        return float(s.page_tokens * s.kv_heads * s.n_layers * 64)

    def record_reads(self, active=None):
        """Attention-mass access accounting (the PEBS analogue).  Resident
        pages are scored by the paged-attention kernel; non-resident pages
        by the low-precision page-summary scoring pass (the cold-tier
        analogue of PEBS sampling slow-tier accesses), so the engine sees
        the whole address space like HeMem does.

        Counts are integer (``step_read_counts``) so the reference loop and
        the fused compiled step accumulate bit-identical engine inputs.  In
        compiled mode recording is fused into ``attend``/``decode_step``."""
        if self.compiled:
            raise RuntimeError(
                "compiled TieredKVCache fuses read recording into "
                "attend()/decode_step(); there is no separate record pass")
        act = self._active(active)
        scale = int(self.READ_SCALE)
        if self._mass_fn is not None:
            mass = np.asarray(self._mass_fn(), np.float64)
            counts_flat = np.rint(mass * scale).astype(np.int64)
            act_page = counts_flat.reshape(self.batch, self.max_pages) > 0
        else:
            counts, act_page = step_read_counts(
                self._lengths, self.max_pages, self.spec.page_tokens,
                scale, xp=np)
            counts = np.where(act[:, None], counts, 0)
            act_page = act_page & act[:, None]
            counts_flat = counts.reshape(self.n_logical).astype(np.int64)
            mass = counts_flat / scale
        resident = self._slot_of >= 0
        self._reads += counts_flat
        # recall bookkeeping counts only truly-resident service
        self._recall_num += float(mass[resident].sum())
        self._recall_den += float(mass.sum())
        res2 = resident.reshape(self.batch, self.max_pages)
        self._last_pages = ((res2 & act_page).sum(1), act_page.sum(1))

    def _record_reads(self):
        warnings.warn(
            "repro.core.tiered_kv.TieredKVCache._record_reads is "
            "deprecated; use the public record_reads()",
            DeprecationWarning, stacklevel=2)
        self.record_reads()

    def true_attention_mass(self) -> np.ndarray:
        """Per-logical-page attention mass for the current step (recency +
        sink-heavy profile, quantized to the integer access counts the
        engine sees).  Synthetic serving benchmarks may install a generator
        via ``set_mass_fn`` (reference mode only)."""
        counts, _ = step_read_counts(self.lengths, self.max_pages,
                                     self.spec.page_tokens,
                                     int(self.READ_SCALE), xp=np)
        return counts.reshape(self.n_logical) / self.READ_SCALE

    def set_mass_fn(self, fn):
        if self.compiled:
            raise RuntimeError("set_mass_fn is reference-mode only; the "
                               "compiled step bakes the serving profile in")
        self._mass_fn = fn

    # -- tiering (the paper's engine — the lifted kv-hemem def) ------------
    def step_engine(self, dt_ms: float):
        """One engine epoch: observe accumulated access counts, plan, and
        apply the promote/demote masks.  The decision math runs through the
        ONE jitted executable both modes share (``CompiledServing.
        engine_decide``); only the apply differs — batched ``page_migrate``
        in compiled mode vs the per-page reference loop here."""
        if self.compiled:
            self._st, _ = self._srv.engine_step(self._st, self._kv, dt_ms)
            return
        in_fast = self._slot_of >= 0
        self._eng, pmask, dmask = self._srv.engine_decide(
            self._eng, self._kv,
            self._reads.astype(np.float32), self._writes.astype(np.float32),
            in_fast, self._allocated, np.float32(dt_ms),
            np.int32(self._epoch))
        self._reads[:] = 0
        self._writes[:] = 0
        self._epoch += 1
        pmask, dmask = np.asarray(pmask), np.asarray(dmask)
        moved = 0
        for pid in np.flatnonzero(dmask):
            if self._slot_of[pid] < 0:
                continue
            self._demote(int(pid))
            moved += 1
        # promote page-ids ascending into free slots ascending — the same
        # pairing the batched compiled apply uses
        free = np.flatnonzero(self._page_of_slot < 0)
        j = 0
        for pid in np.flatnonzero(pmask):
            if self._slot_of[pid] >= 0 or not self._allocated[pid]:
                continue
            if j >= len(free):
                break
            self._promote(int(pid), int(free[j]))
            j += 1
            moved += 1
        self._migrations += moved

    def _demote(self, pid: int):
        slot = int(self._slot_of[pid])
        if slot < 0:
            return
        self.host_k[pid] = np.asarray(self.hbm_k[slot], np.float32)
        self.host_v[pid] = np.asarray(self.hbm_v[slot], np.float32)
        self._slot_of[pid] = -1
        self._page_of_slot[slot] = -1

    def _promote(self, pid: int, slot: int):
        # device-side copy via the page-migration kernel datapath
        ids = (jnp.asarray([slot]), jnp.asarray([0]))
        self.hbm_k = kops.page_migrate(
            self.hbm_k, jnp.asarray(self.host_k[pid:pid + 1], self.spec.dtype),
            *ids)
        self.hbm_v = kops.page_migrate(
            self.hbm_v, jnp.asarray(self.host_v[pid:pid + 1], self.spec.dtype),
            *ids)
        self._slot_of[pid] = slot
        self._page_of_slot[slot] = pid

    # -- sequence lifecycle (traffic replay) -------------------------------
    def reset_seqs(self, done):
        """Retire finished sequences (boolean ``(B,)`` mask): zero their
        lengths, access counters and engine heat, free their HBM slots.
        Pool rows keep stale data; the next occupant overwrites them."""
        done = np.asarray(done, bool)
        if self.compiled:
            self._st = self._srv.reset_seqs(self._st, jnp.asarray(done))
            return
        kill = np.repeat(done, self.max_pages)
        for pid in np.flatnonzero(kill & (self._slot_of >= 0)):
            self._page_of_slot[self._slot_of[pid]] = -1
        self._slot_of[kill] = -1
        self._allocated[kill] = False
        self._reads[kill] = 0
        self._writes[kill] = 0
        self._lengths[done] = 0
        km = jnp.asarray(kill)[None, :]
        self._eng = dict(self._eng,
                         rc=jnp.where(km, 0.0, self._eng["rc"]),
                         wc=jnp.where(km, 0.0, self._eng["wc"]))

    # -- metrics -----------------------------------------------------------
    def recall(self) -> float:
        """Fraction of true attention mass served from the fast tier."""
        if self.compiled:
            return float(self._st["recall_num"]) / \
                max(float(self._st["recall_den"]), 1e-12)
        return self._recall_num / max(self._recall_den, 1e-12)

    def hbm_utilization(self) -> float:
        return float((self.page_of_slot >= 0).mean())
