"""Compiled tiered-KV serving: the fused decode + engine step.

This module is the jitted backend behind ``TieredKVCache(compiled=True)``.
One decode step — token append, paged attention over the HBM-resident
pages, and attention-mass read recording — is a single jitted function over
``(B, pages)`` arrays; engine epochs run as two more jitted calls (decide +
apply) with page moves batched through ONE :func:`~repro.kernels.ops.
page_migrate` call per direction instead of the per-page Python loops of
the reference path.

Conformance is **by construction**, not by tolerance:

* The engine's observe/plan math (:class:`~repro.core.engine_jax.
  KVHeMemDef`, the first lifted engine) is compiled ONCE per cache
  geometry, and the *same jitted executable* serves both the compiled path
  and the Python reference loop in :mod:`~repro.core.tiered_kv`.  XLA is
  free to fuse differently across different jit programs (observed ~1-ULP
  drift in the cooling EWMAs between eager and jitted traces), so sharing
  the executable is the only way residency decisions stay bit-identical.
* Access accounting is *integer*: one decode step charges each logical
  page ``step_read_counts`` accesses — pure int32 arithmetic, so numpy,
  eager jnp and any jit fusion produce the same bits, and the int->f32
  conversion fed to the engine is the same correctly-rounded value on both
  paths.

Structural state (``slot_of``, ``page_of_slot``, ``lengths``) is integer
throughout; both page pools carry one extra **dump row** (index ``H`` for
HBM, ``n`` for host) so every scatter/migrate index is always valid —
masked-out lanes write garbage to the dump row instead of relying on ``-1``
sentinel handling inside the kernels.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

from . import engine_jax
from .engine_jax import KVHeMemDef
from .traffic import step_read_counts  # noqa: F401  (re-export; shared
#                                        with the Python reference loop)

# this module is jax-only; bind engine_jax's lazy jax globals up front so
# the engine defs are usable without a prior simulator call
engine_jax.have_jax()


def read_scale(spec) -> int:
    """Attention-mass -> access-count scale (PEBS-knob units): one unit of
    mass is worth page_tokens x kv_heads x n_layers x 64 accesses."""
    return int(spec.page_tokens * spec.kv_heads * spec.n_layers * 64)


class CompiledServing:
    """Jitted serving functions for one cache geometry.

    All methods are pure: state pytree in, state pytree out.  Instances are
    cached per ``(spec, batch, max_pages, hbm_pages, kernel path)`` by
    :func:`get_serving` so every ``TieredKVCache`` of the same geometry —
    including the Python-loop reference, which borrows :attr:`engine_decide`
    — shares one set of compiled executables.
    """

    def __init__(self, spec, batch: int, max_pages: int, hbm_pages: int):
        self.spec = spec
        self.B, self.mp, self.H = batch, max_pages, hbm_pages
        self.n = batch * max_pages
        self.pt = spec.page_tokens
        self.scale = read_scale(spec)
        self.page_shape = (spec.n_layers, spec.page_tokens, spec.kv_heads,
                           spec.head_dim)
        self.page_elems = int(np.prod(self.page_shape))
        self.edef = KVHeMemDef(1, self.n, hbm_pages, "elementwise",
                               kops.select_path())
        self.edef.page_bytes = np.float32(self.page_elems * 2)

        # the state pytree is donated: XLA aliases the KV pools in place
        # instead of copying ~page_elems * (n + H) bytes per decode step.
        # Callers always replace their state with the returned one, so the
        # consumed buffers are never observed again.
        self._append_fn = jax.jit(self._append, donate_argnums=0)
        self._attend_fn = jax.jit(self._attend_record, donate_argnums=0)
        self._decode_fn = jax.jit(self._decode, donate_argnums=0)
        self._apply_fn = jax.jit(self._apply, donate_argnums=0)
        self._reset_fn = jax.jit(self._reset, donate_argnums=0)
        # the ONE engine-decision executable both paths share (see module
        # docstring); knob vectors are traced, so tuner configs never retrace
        self.engine_decide = jax.jit(self._engine_decide)

    # -- state -------------------------------------------------------------
    def fresh_state(self) -> Dict[str, Any]:
        B, n, H, dt = self.B, self.n, self.H, self.spec.dtype
        ps = self.page_shape
        st = {
            "lengths": jnp.zeros(B, jnp.int32),
            "slot_of": jnp.full(n + 1, -1, jnp.int32),
            "page_of_slot": jnp.full(H + 1, -1, jnp.int32),
            "allocated": jnp.zeros(n, bool),
            "reads": jnp.zeros(n, jnp.int32),
            "writes": jnp.zeros(n, jnp.int32),
            "hbm_k": jnp.zeros((H + 1,) + ps, dt),
            "hbm_v": jnp.zeros((H + 1,) + ps, dt),
            "host_k": jnp.zeros((n + 1,) + ps, dt),
            "host_v": jnp.zeros((n + 1,) + ps, dt),
            "eng": self.edef.init(None),
            "migrations": jnp.int32(0),
            "epoch": jnp.int32(0),
            "recall_num": jnp.float32(0.0),
            "recall_den": jnp.float32(0.0),
        }
        # jax dedupes identical constants (e.g. the two zero pools) into one
        # buffer; donated pytrees must not contain the same buffer twice, so
        # force every leaf onto its own storage.
        return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), st)

    # -- decode-step pieces (traced) ---------------------------------------
    def _append(self, st, k_new, v_new, active):
        B, mp, n, H, pt = self.B, self.mp, self.n, self.H, self.pt
        t = st["lengths"]
        pi, off = t // pt, t % pt
        pid = jnp.arange(B, dtype=jnp.int32) * mp + pi       # (B,) unique
        allocated = st["allocated"].at[pid].set(
            st["allocated"][pid] | active)
        writes = st["writes"].at[pid].add(active.astype(jnp.int32))
        slot = st["slot_of"][pid]
        # first touch of a page grabs the lowest free HBM slot; the j-th
        # needy sequence (ascending b) gets the j-th lowest free slot —
        # exactly the reference loop's repeated flatnonzero(free)[0]
        need = active & (slot < 0) & (off == 0)
        free = st["page_of_slot"][:H] < 0
        n_free = free.sum()
        free_slots = jnp.sort(
            jnp.where(free, jnp.arange(H, dtype=jnp.int32), H))
        rank = jnp.cumsum(need.astype(jnp.int32))            # inclusive
        got = need & (rank <= n_free)
        new_slot = free_slots[jnp.clip(rank - 1, 0, H - 1)]
        slot = jnp.where(got, new_slot, slot)
        slot_of = st["slot_of"].at[jnp.where(got, pid, n)].set(
            jnp.where(got, new_slot, -1))
        pos = st["page_of_slot"].at[jnp.where(got, new_slot, H)].set(
            jnp.where(got, pid, -1))
        # token writes: resident rows to their slot, everything else to the
        # dump row of the respective pool
        kt = k_new.astype(self.spec.dtype)
        vt = v_new.astype(self.spec.dtype)
        rows_hbm = jnp.where(active & (slot >= 0), slot, H)
        rows_host = jnp.where(active & (slot < 0), pid, n)
        return dict(
            st, lengths=t + active.astype(jnp.int32), slot_of=slot_of,
            page_of_slot=pos, allocated=allocated, writes=writes,
            hbm_k=st["hbm_k"].at[rows_hbm, :, off].set(kt),
            hbm_v=st["hbm_v"].at[rows_hbm, :, off].set(vt),
            host_k=st["host_k"].at[rows_host, :, off].set(kt),
            host_v=st["host_v"].at[rows_host, :, off].set(vt))

    def _attend_record(self, st, q, active):
        B, mp, n = self.B, self.mp, self.n
        tbl = st["slot_of"][:n].reshape(B, mp)
        out = kops.paged_attention(
            q.astype(self.spec.dtype), st["hbm_k"][:, 0], st["hbm_v"][:, 0],
            tbl, st["lengths"])
        counts, act_page = step_read_counts(st["lengths"], mp, self.pt,
                                            self.scale, xp=jnp)
        counts = jnp.where(active[:, None], counts, 0)
        act_page = act_page & active[:, None]
        flat = counts.reshape(n)
        resident = st["slot_of"][:n] >= 0
        mass = flat.astype(jnp.float32) / np.float32(self.scale)
        st = dict(
            st, reads=st["reads"] + flat,
            recall_num=st["recall_num"]
            + jnp.sum(jnp.where(resident, mass, 0.0)),
            recall_den=st["recall_den"] + jnp.sum(mass))
        res_pages = (resident.reshape(B, mp) & act_page).sum(1)
        tot_pages = act_page.sum(1)
        return st, out, res_pages, tot_pages

    def _decode(self, st, k_new, v_new, q, active):
        st = self._append(st, k_new, v_new, active)
        return self._attend_record(st, q, active)

    # -- engine epoch (traced) ---------------------------------------------
    def _engine_decide(self, eng, kv, reads_f, writes_f, in_fast, allocated,
                       dt_ms, e):
        keys = jnp.zeros((1,), jnp.uint32)   # kv-hemem draws no noise
        est = jnp.full((1,), dt_ms, jnp.float32)
        eng, _ = self.edef.observe(eng, kv, keys, e, reads_f, writes_f, est)
        eng, pm, dm, _ = self.edef.plan(
            eng, kv, keys, e, reads_f, writes_f, in_fast[None, :],
            allocated[None, :], est, jnp.int32(self.H))
        return eng, pm[0], dm[0]

    def _apply(self, st, pmask, dmask):
        """Apply one epoch's migration masks: batched demote (HBM->host),
        then batched promote into the freed slots — promote page-ids
        ascending paired with free slots ascending, the reference loop's
        repeated lowest-free-slot rule."""
        n, H = self.n, self.H
        arn = jnp.arange(n, dtype=jnp.int32)
        slots = st["slot_of"][:n]
        dm = dmask & (slots >= 0)
        d_ids = jnp.sort(jnp.where(dm, arn, n))[:H]
        d_valid = d_ids < n
        d_rows = jnp.where(d_valid, d_ids, n)                # host dump row
        d_slots = jnp.where(d_valid, slots[jnp.minimum(d_ids, n - 1)], H)
        host_k = kops.page_migrate(st["host_k"], st["hbm_k"], d_rows, d_slots)
        host_v = kops.page_migrate(st["host_v"], st["hbm_v"], d_rows, d_slots)
        slots = jnp.where(dm, -1, slots)
        posn = st["page_of_slot"][:H]
        owner = jnp.maximum(posn, 0)
        posn = jnp.where((posn >= 0) & dm[owner], -1, posn)

        pm = pmask & (slots < 0) & st["allocated"]
        p_ids = jnp.sort(jnp.where(pm, arn, n))[:H]
        f_slots = jnp.sort(
            jnp.where(posn < 0, jnp.arange(H, dtype=jnp.int32), H))
        valid = (p_ids < n) & (f_slots < H)
        p_rows = jnp.where(valid, p_ids, n)
        p_slots = jnp.where(valid, f_slots, H)
        hbm_k = kops.page_migrate(st["hbm_k"], host_k, p_slots, p_rows)
        hbm_v = kops.page_migrate(st["hbm_v"], host_v, p_slots, p_rows)
        slot_of = jnp.concatenate([slots, st["slot_of"][n:]])
        slot_of = slot_of.at[p_rows].set(jnp.where(valid, p_slots, -1))
        pos = jnp.concatenate([posn, st["page_of_slot"][H:]])
        pos = pos.at[p_slots].set(jnp.where(valid, p_ids, -1))
        moved = dm.sum() + valid.sum()
        return dict(st, slot_of=slot_of, page_of_slot=pos,
                    hbm_k=hbm_k, hbm_v=hbm_v, host_k=host_k, host_v=host_v,
                    reads=jnp.zeros_like(st["reads"]),
                    writes=jnp.zeros_like(st["writes"]),
                    migrations=st["migrations"] + moved.astype(jnp.int32),
                    epoch=st["epoch"] + 1), moved

    def engine_step(self, st, kv, dt_ms):
        """One engine epoch on compiled state: shared decide + batched
        apply.  Returns ``(state, moved)``."""
        in_fast = st["slot_of"][:self.n] >= 0
        eng, pmask, dmask = self.engine_decide(
            st["eng"], kv, st["reads"].astype(jnp.float32),
            st["writes"].astype(jnp.float32), in_fast, st["allocated"],
            np.float32(dt_ms), st["epoch"])
        st, moved = self._apply_fn(dict(st, eng=eng), pmask, dmask)
        # the zeroed read/write accumulators are identical values, which XLA
        # may CSE into one output buffer — split them so the next donated
        # call doesn't see the same buffer twice (cheap: 2 x n int32,
        # engine epochs only)
        st = dict(st, reads=st["reads"].copy(), writes=st["writes"].copy())
        return st, int(moved)

    # -- sequence completion ----------------------------------------------
    def _reset(self, st, done):
        """Retire finished sequences: zero their lengths and access
        counters, free their HBM slots and engine heat.  Pool rows keep
        stale data; the next occupant's appends overwrite them."""
        n, H, mp = self.n, self.H, self.mp
        owner = jnp.arange(n, dtype=jnp.int32) // mp
        kill = done[owner]
        slots = st["slot_of"][:n]
        fs = kill & (slots >= 0)
        pos = st["page_of_slot"].at[jnp.where(fs, slots, H)].set(-1)
        eng = dict(st["eng"],
                   rc=jnp.where(kill[None, :], 0.0, st["eng"]["rc"]),
                   wc=jnp.where(kill[None, :], 0.0, st["eng"]["wc"]))
        return dict(
            st, lengths=jnp.where(done, 0, st["lengths"]),
            slot_of=jnp.concatenate([jnp.where(kill, -1, slots),
                                     st["slot_of"][n:]]),
            page_of_slot=pos, allocated=st["allocated"] & ~kill,
            reads=jnp.where(kill, 0, st["reads"]),
            writes=jnp.where(kill, 0, st["writes"]), eng=eng)

    # -- public jitted entry points ---------------------------------------
    def append(self, st, k_new, v_new, active):
        return self._append_fn(st, k_new, v_new, active)

    def attend(self, st, q, active):
        return self._attend_fn(st, q, active)

    def decode(self, st, k_new, v_new, q, active):
        """The fused serving step: append + paged attention + read/recall
        recording in ONE jitted call.  Returns
        ``(state, out, res_pages, tot_pages)``."""
        return self._decode_fn(st, k_new, v_new, q, active)

    def reset_seqs(self, st, done):
        return self._reset_fn(st, done)


_CACHE: Dict[Tuple, CompiledServing] = {}


def get_serving(spec, batch: int, max_pages: int,
                hbm_pages: int) -> CompiledServing:
    """Cached :class:`CompiledServing` per geometry + kernel path (the
    dispatch choice is folded in at trace time, so flipping
    ``kops.FORCE`` builds fresh executables instead of silently reusing
    ones compiled for the other path)."""
    key = (spec, batch, max_pages, hbm_pages, kops.select_path())
    srv = _CACHE.get(key)
    if srv is None:
        srv = _CACHE[key] = CompiledServing(spec, batch, max_pages,
                                            hbm_pages)
    return srv
