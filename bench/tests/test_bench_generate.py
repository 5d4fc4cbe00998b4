"""The traffic generators are deterministic in the seed."""

import numpy as np

import bench_tiny  # noqa: F401
from bench import generate as G

BIG = 2 ** 31 + 12345          # seeds past 32 signed bits


def test_study_and_sim_seeds():
    assert G.sim_seed(BIG) == G.sim_seed(BIG)
    assert G.sim_seed(BIG) != G.sim_seed(BIG + 1)
    assert 0 <= G.sim_seed(BIG) < 2 ** 32
    assert G.study_seed(BIG, 3) == G.study_seed(BIG, 3)
    a = np.random.default_rng(G.study_seed(BIG, 0)).random(4)
    b = np.random.default_rng(G.study_seed(BIG, 1)).random(4)
    assert not np.array_equal(a, b)


def test_target_lengths_same_set_other_order():
    spec = {"median": 1024, "sigma": 0.5, "lo": 256, "hi": 2048, "n": 64}
    base = G.length_set(spec)
    assert base.min() >= 256 and base.max() <= 2048
    assert abs(np.median(base) - 1024) <= 16

    def stream(seed):
        t = G.TargetLengths(spec, 8, seed)
        return np.array([[t.draw(b) for _ in range(64)] for b in range(8)])

    s1, s2, s3 = stream(BIG), stream(BIG), stream(BIG + 1)
    assert np.array_equal(s1, s2)
    assert not np.array_equal(s1, s3)
    for b in range(8):                   # each slot: the whole set, permuted
        assert np.array_equal(np.sort(s1[b]), np.sort(base))
        assert np.array_equal(np.sort(s3[b]), np.sort(base))


def test_input_pool_is_made_from_the_seed():
    import jax.numpy as jnp
    a = G.input_pool(BIG, 3, 2, 2, 2, 4, 8, jnp.bfloat16)
    b = G.input_pool(BIG, 3, 2, 2, 2, 4, 8, jnp.bfloat16)
    c = G.input_pool(BIG + 1, 3, 2, 2, 2, 4, 8, jnp.bfloat16)
    assert a[0].shape == (3, 2, 2, 2, 8) and a[2].shape == (3, 2, 4, 8)
    assert a[0].dtype == jnp.bfloat16
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_sample():
    assert np.array_equal(G.sample(BIG, 5, 100, 10), G.sample(BIG, 5, 100, 10))
    assert not np.array_equal(G.sample(BIG, 5, 100, 10),
                              G.sample(BIG + 1, 5, 100, 10))
    assert len(G.sample(BIG, 5, 4, 10)) == 4
    assert len(G.sample(BIG, 5, 0, 10)) == 0
