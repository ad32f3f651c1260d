"""The engine's one sort signature: lexsort_permutation against
np.lexsort over every key dtype, and the float64 keys on both kinds of
backend (true doubles; the TPU's float32 pairs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.ops import sort as S


def _np_f64_bits_order(x):
    bits = x.view(np.int64).copy()
    bits[np.isnan(x)] = 0x7FF8000000000000
    return np.where(bits < 0, bits ^ np.int64(2**63 - 1), bits)


def test_lexsort_permutation_matches_numpy_over_key_dtypes():
    rng = np.random.default_rng(3)
    n = 4096
    keys = [
        rng.integers(0, 2, n).astype(bool),
        rng.integers(-5, 5, n).astype(np.int8),
        rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32),
        rng.integers(0, 1 << 32, n, dtype=np.uint32),
        rng.integers(-(1 << 62), 1 << 62, n),
        np.array([-1, 0, 1, np.iinfo(np.int64).min,
                  np.iinfo(np.int64).max] * (n // 5) + [0] * (n % 5)),
    ]
    for k in range(1, len(keys) + 1):
        got = np.asarray(jax.jit(S.lexsort_permutation)(
            [jnp.asarray(a) for a in keys[:k]]))
        assert got.dtype == np.int32
        assert np.array_equal(got, np.lexsort(keys[:k])), k
    one = keys[2]
    assert np.array_equal(np.asarray(S.stable_argsort(jnp.asarray(one))),
                          np.argsort(one, kind="stable"))


_SPECIALS = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan,
                      1.0, -1.0, 1e-30, -1e-30, 3e38, -3e38])


@pytest.mark.parametrize("descending", [False, True])
def test_float64_keys_order_true_doubles(descending):
    rng = np.random.default_rng(5)
    x = np.concatenate([_SPECIALS, rng.uniform(-1e300, 1e300, 500),
                        np.nextafter(1.0, 2.0) * np.ones(3)])
    keys = S.float64_order_keys(jnp.asarray(x), descending)
    got = np.asarray(S.lexsort_permutation(keys))
    order = _np_f64_bits_order(x)
    want = np.argsort(~order if descending else order, kind="stable")
    assert np.array_equal(got, want)


@pytest.mark.parametrize("descending", [False, True])
def test_float64_keys_order_float32_pairs(monkeypatch, descending):
    """On a TPU a float64 is a pair of float32 words: for every value
    such a pair can hold, the pair's keys order exactly like the
    double's own bits."""
    rng = np.random.default_rng(7)
    hi = rng.uniform(-1e6, 1e6, 600).astype(np.float32)
    lo = (hi * rng.uniform(-2e-8, 2e-8, 600)).astype(np.float32)
    x = np.concatenate([_SPECIALS,
                        hi.astype(np.float64) + lo.astype(np.float64),
                        hi[:50].astype(np.float64)])  # ties on hi
    # only what survives the pair: (float32(x), float32(x - hi))
    xh = x.astype(np.float32)
    with np.errstate(invalid="ignore"):
        xl = (x - xh.astype(np.float64)).astype(np.float32)
    keep = ~np.isfinite(x) | (xh.astype(np.float64)
                              + xl.astype(np.float64) == x)
    x = x[keep]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    keys = S.float64_order_keys(jnp.asarray(x), descending)
    assert all(k.dtype == jnp.int32 for k in keys) and len(keys) == 2
    got = np.asarray(S.lexsort_permutation(keys))
    order = _np_f64_bits_order(x)
    want = np.argsort(~order if descending else order, kind="stable")
    assert np.array_equal(got, want)
