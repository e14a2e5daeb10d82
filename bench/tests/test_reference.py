"""The plain reference (`bench/reference.py`) gives the answers of the
program's own brute-force oracle (`repro.core.search`) at a small size,
for ED and banded DTW, and its control (bfloat16) does not.

    PYTHONPATH=src python -m pytest -q bench/tests/test_reference.py
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference

K = 5


@pytest.fixture(scope="module")
def data():
    return gen.make_collection(2**33 + 5, 48, 96)


@pytest.fixture(scope="module")
def queries(data):
    return gen.make_queries(data, 11, [32, 48, 64], 0.02)


@pytest.mark.parametrize("measure,r", [("ed", 0), ("dtw", 3), ("dtw", 6)])
def test_reference_equals_program_brute_force(data, queries, measure, r):
    from repro.core.search import brute_force_d2, knn_from_d2
    for q in queries:
        d, s, o = reference.knn(data, q.values, K, measure, r)
        want = knn_from_d2(
            [brute_force_d2(data, q.values, True, measure, r)], K)
        np.testing.assert_array_equal(s, want.series)
        np.testing.assert_array_equal(o, want.offsets)
        # the program's banded DP (a cumsum/cummin closed form) and the
        # wavefront here round differently in float32: up to ~1e-4 on
        # the chip, and the same order here
        np.testing.assert_allclose(d, want.dists, rtol=0,
                                   atol=1e-5 if measure == "ed" else 1e-4)
        # the query's own window is its nearest neighbour
        assert (s[0], o[0]) == (q.series, q.offset)


@pytest.mark.parametrize("measure,r", [("ed", 0), ("dtw", 3)])
def test_window_dists_match_table(data, queries, measure, r):
    q = queries[1]
    d, s, o = reference.knn(data, q.values, K, measure, r)
    np.testing.assert_allclose(
        reference.window_dists(data, q.values, s, o, measure, r), d,
        rtol=0, atol=1e-6)


def test_flat_window_normalizes_to_zero():
    flat = jnp.ones((4, 40), jnp.float32)
    q = np.sin(np.linspace(0, 3, 16)).astype(np.float32)
    qn = (q - q.mean()) / q.std()
    d, _, _ = reference.knn(flat, q, 1, "ed")
    assert d[0] == pytest.approx(np.sqrt(np.sum(qn ** 2)), rel=1e-5)


@pytest.mark.parametrize("measure,r", [("ed", 0), ("dtw", 3)])
def test_bfloat16_control_departs(data, queries, measure, r):
    gaps = []
    for q in queries:
        d, _, _ = reference.knn(data, q.values, K, measure, r)
        dc, _, _ = reference.knn(data, q.values, K, measure, r,
                                 dtype=jnp.bfloat16)
        gaps.append(np.max(np.abs(dc - d)))
    assert max(gaps) > 1e-3
