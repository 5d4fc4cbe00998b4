"""Traffic generation: everything a run draws from ``--seed``.

* tuning: the simulation seed of the GUPS trace and each study's optimizer
  seed ``(seed, i)``;
* serving: each slot's sequence of target lengths, a permutation of one
  fixed set of lognormal quantiles (every seed decodes the same lengths, in
  another order), and the pool of step inputs (K, V, q) made on the device
  in one jitted call;
* the sample of results that the correctness check compares.
"""

from __future__ import annotations

import statistics
from typing import Mapping

import numpy as np


def seed_words(seed: int, *tags: int) -> np.random.SeedSequence:
    """A seed sequence for ``(seed, *tags)``; any whole number works."""
    return np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *tags])


def u32(seed: int, tag: int) -> int:
    """A 32-bit word drawn from ``(seed, tag)``."""
    return int(seed_words(seed, tag).generate_state(1)[0])


def rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, tag))


# -- tuning ------------------------------------------------------------------
def sim_seed(seed: int) -> int:
    return u32(seed, 1)


def study_seed(seed: int, i: int):
    """The optimizer seed of study ``i``."""
    return [int(seed) & (2 ** 64 - 1), int(i)]


# -- serving -----------------------------------------------------------------
def length_set(spec: Mapping) -> np.ndarray:
    """``n`` evenly spaced quantiles of the lognormal target length, clipped
    to ``[lo, hi]``."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / spec["n"]) for i in range(spec["n"])])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)


class TargetLengths:
    """Per-slot stream of target lengths: slot ``b`` cycles through its own
    permutation of :func:`length_set`."""

    def __init__(self, spec: Mapping, batch: int, seed: int):
        base = length_set(spec)
        r = rng(seed, 2)
        self.orders = [r.permutation(base) for _ in range(batch)]
        self.next_i = np.zeros(batch, np.int64)

    def draw(self, b: int) -> int:
        order = self.orders[b]
        v = order[self.next_i[b] % len(order)]
        self.next_i[b] += 1
        return int(v)


def input_pool(seed: int, n: int, batch: int, n_layers: int, kv_heads: int,
               heads: int, head_dim: int, dtype):
    """``n`` step inputs made on the device in one jitted call: K and V
    ``(n, batch, n_layers, kv_heads, head_dim)`` and q
    ``(n, batch, heads, head_dim)``, standard normal in ``dtype``."""
    import jax

    def make(key):
        kk, kv, kq = jax.random.split(key, 3)
        shp = (n, batch, n_layers, kv_heads, head_dim)
        return (jax.random.normal(kk, shp, dtype),
                jax.random.normal(kv, shp, dtype),
                jax.random.normal(kq, (n, batch, heads, head_dim), dtype))

    return jax.jit(make)(jax.random.key(u32(seed, 3)))


# -- the checked sample --------------------------------------------------------
def sample(seed: int, tag: int, n_items: int, k: int) -> np.ndarray:
    """``min(k, n_items)`` distinct indices below ``n_items``, sorted."""
    if n_items <= 0:
        return np.zeros(0, np.int64)
    return np.sort(rng(seed, tag).choice(n_items, size=min(k, n_items),
                                         replace=False))
