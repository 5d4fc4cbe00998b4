"""jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled Pallas kernels run natively; on CPU (this container,
including the multi-pod dry-run) the same math executes through the pure-jnp
reference implementations, which share the online-softmax block structure —
so tests exercise the kernels in interpret mode against the refs, while
models remain portable.

Set ``FORCE = "pallas" | "ref"`` to pin a path (tests use "pallas" with
interpret mode; the dry-run uses "ref" so the lowered HLO stays analyzable
by cost_analysis).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as R

FORCE: Optional[str] = None


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  A backend that fails to
    initialize raises here rather than reading as "no TPU": that would
    silently run the kernels in interpret mode."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode (every backend but
    the TPU)."""
    return not on_tpu()


def _use_pallas() -> bool:
    if FORCE == "pallas":
        return True
    if FORCE == "ref":
        return False
    return on_tpu()


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0):
    if _use_pallas():
        from .flash_attention import flash_attention as fa
        return fa(q, k, v, causal=causal, window=window,
                  logit_softcap=logit_softcap, interpret=interpret())
    return R.flash_attention_ref(q, k, v, causal=causal, window=window,
                                 logit_softcap=logit_softcap)


def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    logit_softcap: float = 0.0):
    if _use_pallas() and logit_softcap == 0.0:
        from .paged_attention import paged_attention as pa
        return pa(q, k_pages, v_pages, block_table, lengths,
                  interpret=interpret())
    return R.paged_attention_ref(q, k_pages, v_pages, block_table, lengths,
                                 logit_softcap=logit_softcap)


def select_path() -> str:
    """The dispatch target :func:`select_topk` resolves to right now
    (``"pallas"`` or ``"ref"``).  The compiled epoch loop folds this into
    its jit-cache key so flipping :data:`FORCE` retraces instead of
    silently reusing a function compiled for the other path."""
    return "pallas" if _use_pallas() else "ref"


def select_topk(p_mask, p_heat, d_mask, d_heat, n_promote, n_demote,
                mode: Optional[str] = None):
    """Exact top-k promote/demote selection masks (stable index tie-break,
    bit-exact vs numpy's stable sorts); see ``kernels/select_topk.py``.

    ``mode=None`` resolves via :func:`select_path` (the ``FORCE``/TPU
    dispatch); ``"pallas"``/``"ref"`` pin one implementation — the single
    place the interpret-mode rule lives, so callers (the compiled epoch
    loop in particular) never re-derive it."""
    if mode is None:
        mode = select_path()
    if mode == "pallas":
        from .select_topk import select_topk as sk
        return sk(p_mask, p_heat, d_mask, d_heat, n_promote, n_demote,
                  interpret=interpret())
    if mode == "ref":
        return R.select_topk_ref(p_mask, p_heat, d_mask, d_heat,
                                 n_promote, n_demote)
    raise ValueError(f"unknown selection mode {mode!r}; "
                     "expected 'pallas', 'ref' or None")


def topk_mask(scores, k, valid=None, mode: Optional[str] = None):
    """Exact top-``k`` boolean mask over a 1-D float32 score vector
    (descending, page/candidate-index tie-break) — the promote side of
    :func:`select_topk` with an empty demote side.

    Used by the BO acquisition's top-q-EI step
    (:func:`repro.core.bo.forest_fast.suggest_topq`) instead of a dense
    ``np.argsort(-ei)``; ``k`` may be a traced scalar so a jitted caller
    does not retrace when the batch's model-slot count changes.
    """
    s = jnp.asarray(scores, jnp.float32)[None, :]
    v = jnp.ones(s.shape, bool) if valid is None \
        else jnp.asarray(valid, bool)[None, :]
    pm, _ = select_topk(v, s, jnp.zeros(s.shape, bool), jnp.zeros_like(s),
                        jnp.asarray([k]), jnp.asarray([0]), mode=mode)
    return pm[0]


def page_migrate(dst_pool, src_pool, dst_ids, src_ids):
    if _use_pallas():
        from .page_migrate import page_migrate as pm
        return pm(dst_pool, src_pool, dst_ids, src_ids,
                  interpret=interpret())
    return R.page_migrate_ref(dst_pool, src_pool, dst_ids, src_ids)


def hotness_update(counts, page_ids, *, cool: bool, hot_threshold: float):
    return R.hotness_update_ref(counts, page_ids, cool=cool,
                                hot_threshold=hot_threshold)
