"""The inputs of a run come from the seed alone."""

import numpy as np
import pytest

import benchtest_util as U  # noqa: F401  (puts bench/ on the path)
from tsbench import gen, spec

SIZES = {"season": (300, 960, {"season_len": 10, "strength": 0.5,
                                 "spread": 0.09}),
         "random_walk": (300, 256, {})}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_same_seed_same_series(name):
    n, T, args = SIZES[name]
    mod = spec.plugin(U.REPO, "data", name)
    big = 2 ** 31 + 12345           # wider than 32 signed bits
    a = gen.series(mod, args, big, gen.CORPUS, n, T)
    b = gen.series(mod, args, big, gen.CORPUS, n, T)
    assert a.shape == (n, T) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    other = gen.series(mod, args, big + 1, gen.CORPUS, n, T)
    assert not np.array_equal(a, other)
    pool = gen.series(mod, args, big, gen.POOL, n, T)
    assert not np.any(np.all(pool[:, None, :] == a[None, :, :], axis=-1))


@pytest.mark.parametrize("name", sorted(SIZES))
def test_series_are_z_normalised(name):
    n, T, args = SIZES[name]
    x = gen.series(spec.plugin(U.REPO, "data", name), args, 3,
                   gen.CORPUS, n, T)
    np.testing.assert_allclose(x.mean(axis=1), 0.0, atol=1e-4)
    np.testing.assert_allclose(x.std(axis=1), 1.0, atol=1e-3)


def test_season_strength_is_in_range():
    """The mixed-in season holds 0.5 +- 0.09 of the variance."""
    n, T, args = SIZES["season"]
    x = gen.series(spec.plugin(U.REPO, "data", "season"), args, 5,
                   gen.CORPUS, n, T).astype(np.float64)
    seas = x.reshape(n, T // 10, 10).mean(axis=1)
    r2 = 1.0 - (x - np.tile(seas, (1, T // 10))).var(axis=1) / x.var(axis=1)
    assert 0.35 < r2.min() and r2.max() < 0.65


def test_blocks_do_not_repeat():
    """A corpus longer than one block is not one block repeated."""
    mod = spec.plugin(U.REPO, "data", "random_walk")
    x = gen.series(mod, {}, 1, gen.CORPUS, gen.BLOCK + 5, 8)
    assert not np.array_equal(x[:5], x[gen.BLOCK:])


def test_seed_reorders_the_same_queries_within_blocks():
    """Every seed is asked the same queries: the seed permutes each block
    of ``clients`` consecutive ones."""
    mod = spec.plugin(U.REPO, "data", "random_walk")
    traffic = {"clients": 8, "pool": 64, "query_seed": 9}
    a = gen.queries(mod, {}, traffic, 1, 16)
    b = gen.queries(mod, {}, traffic, 2 ** 33 + 1, 16)
    np.testing.assert_array_equal(a, gen.queries(mod, {}, traffic, 1, 16))
    assert not np.array_equal(a, b)
    for lo in range(0, 64, 8):
        key = lambda x: np.lexsort(x[lo:lo + 8].T[::-1])
        np.testing.assert_array_equal(a[lo:lo + 8][key(a)],
                                      b[lo:lo + 8][key(b)])
