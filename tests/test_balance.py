"""Tests for the balancing core (the paper's scheduler).

Two layers: (1) randomized equivalence of the vectorized implementations
against the retained ``_*_reference`` originals — these run everywhere
(stdlib ``random`` only); (2) hypothesis property tests, defined only
when the dev extra is installed.
"""
import random

import numpy as np
import pytest

from repro.core.balance import (
    METHODS, REFERENCE_METHODS, balance_items, bin_loads, greedy_binpack,
    imbalance, karmarkar_karp, multi_greedy_binpack,
)


# ----------------------------------------------------- vectorized == reference
def _random_costs(rng, n):
    if rng.random() < 0.3:
        # heavy ties: stresses stable-sort / heap tie-breaking
        return [rng.choice([1.0, 2.0, 4.0, 4.0]) for _ in range(n)]
    return [rng.random() * 10.0 for _ in range(n)]


@pytest.mark.parametrize("method", ["greedy_binpack", "karmarkar_karp"])
def test_vectorized_matches_reference_assignments(method):
    rng = random.Random(1234)
    for _ in range(200):
        n = rng.randrange(0, 64)
        k = rng.randrange(1, 9)
        costs = _random_costs(rng, n)
        got = METHODS[method](costs, k)
        want = REFERENCE_METHODS[method](costs, k)
        # item-for-item identical, hence identical imbalance too
        assert got == want, (method, n, k, costs)


def test_multi_greedy_matches_reference_assignments():
    rng = random.Random(99)
    for _ in range(200):
        n = rng.randrange(0, 48)
        k = rng.randrange(1, 9)
        d = rng.randrange(1, 4)
        vecs = [[rng.random() * 5.0 for _ in range(d)] for _ in range(n)]
        got = multi_greedy_binpack(vecs, k)
        want = REFERENCE_METHODS["multi_greedy_binpack"](vecs, k)
        assert got == want, (n, k, d, vecs)


def test_vectorized_imbalance_never_worse():
    """Belt-and-braces for the acceptance bar: even if assignments ever
    diverge, the vectorized straggler must be same-or-better."""
    rng = random.Random(7)
    for _ in range(100):
        n = rng.randrange(1, 64)
        k = rng.randrange(1, 9)
        costs = _random_costs(rng, n)
        for method in ("greedy_binpack", "karmarkar_karp"):
            got = imbalance(bin_loads(
                costs, METHODS[method](costs, k), k))
            want = imbalance(bin_loads(
                costs, REFERENCE_METHODS[method](costs, k), k))
            assert got <= want + 1e-9


def test_empty_and_single_item_edges():
    assert greedy_binpack([], 3) == []
    assert karmarkar_karp([], 3) == []
    assert multi_greedy_binpack([], 3) == []
    assert greedy_binpack([5.0], 3) == REFERENCE_METHODS[
        "greedy_binpack"]([5.0], 3)
    assert karmarkar_karp([5.0], 3) == REFERENCE_METHODS[
        "karmarkar_karp"]([5.0], 3)
    with pytest.raises(ValueError):
        greedy_binpack([1.0], 0)
    with pytest.raises(ValueError):
        karmarkar_karp([1.0], 0)
    with pytest.raises(ValueError):
        multi_greedy_binpack([[1.0]], 0)


def test_balance_reduces_imbalance_on_skewed_data():
    rng = np.random.default_rng(0)
    costs = np.square(rng.lognormal(3.0, 1.2, 512)).tolist()
    n = 16
    rr = [i % n for i in range(len(costs))]
    base = imbalance(bin_loads(costs, rr, n))
    bal_loads = bin_loads(costs, greedy_binpack(costs, n), n)
    assert imbalance(bal_loads) < base
    # LPT lands within 4/3 of the theoretical lower bound even under the
    # heavy-tailed quadratic-cost skew (a single giant doc can dominate)
    lower = max(max(costs), sum(costs) / n)
    assert max(bal_loads) <= 4.0 / 3.0 * lower


def test_unknown_method_raises():
    with pytest.raises(ValueError):
        balance_items([1.0], 2, "nope")


# ------------------------------------------------------- property tests
try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # pragma: no cover - dev extra absent
    given = None

if given is not None:
    costs_strategy = st.lists(
        st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
                  allow_infinity=False),
        min_size=1, max_size=200)

    @given(costs_strategy, st.integers(1, 16))
    @settings(max_examples=100, deadline=None)
    def test_greedy_assignment_valid(costs, n_bins):
        assign = greedy_binpack(costs, n_bins)
        assert len(assign) == len(costs)
        assert all(0 <= a < n_bins for a in assign)
        # conservation: every item assigned exactly once
        assert sum(bin_loads(costs, assign, n_bins)) == pytest.approx(
            sum(costs), rel=1e-6, abs=1e-6)

    @given(costs_strategy, st.integers(1, 8))
    @settings(max_examples=100, deadline=None)
    def test_greedy_within_4_3_of_round_robin(costs, n_bins):
        """LPT is 4/3-of-OPT, hence within 4/3 of ANY assignment's max
        load (instance-wise dominance over round-robin does not hold in
        general)."""
        assign = greedy_binpack(costs, n_bins)
        rr = [i % n_bins for i in range(len(costs))]
        g = max(bin_loads(costs, assign, n_bins))
        r = max(bin_loads(costs, rr, n_bins))
        assert g <= 4.0 / 3.0 * r + 1e-6

    @given(costs_strategy, st.integers(1, 8))
    @settings(max_examples=50, deadline=None)
    def test_greedy_within_graham_list_bound(costs, n_bins):
        """Graham's list-scheduling bound: the last item placed on the
        heaviest bin started on the lightest one, so the max load is at
        most mean + (1 - 1/m) * max item, for any order and hence LPT.

        4/3 x max(mean, max item) is not a bound: 4/3 bounds LPT against
        OPT, and max(mean, max item) can sit below OPT.  Five unit items
        in four bins give LPT = OPT = 2 > 4/3 x 1.25."""
        assign = greedy_binpack(costs, n_bins)
        got = max(bin_loads(costs, assign, n_bins))
        bound = sum(costs) / n_bins + (1 - 1 / n_bins) * max(costs)
        assert got <= bound + 1e-6

    @given(st.lists(st.floats(min_value=0.1, max_value=1e4), min_size=2,
                    max_size=40), st.integers(2, 6))
    @settings(max_examples=50, deadline=None)
    def test_karmarkar_karp_valid_and_competitive(costs, n_bins):
        kk = karmarkar_karp(costs, n_bins)
        assert len(kk) == len(costs)
        assert all(0 <= a < n_bins for a in kk)
        assert sum(bin_loads(costs, kk, n_bins)) == pytest.approx(
            sum(costs))
        # KK should not be wildly worse than greedy
        kk_max = max(bin_loads(costs, kk, n_bins))
        g_max = max(bin_loads(costs, greedy_binpack(costs, n_bins),
                              n_bins))
        assert kk_max <= 2.0 * g_max + 1e-6

    @given(st.lists(st.tuples(st.floats(0, 1e4), st.floats(0, 1e4)),
                    min_size=1, max_size=60), st.integers(1, 8))
    @settings(max_examples=50, deadline=None)
    def test_multi_greedy_valid(vectors, n_bins):
        assign = multi_greedy_binpack(vectors, n_bins)
        assert len(assign) == len(vectors)
        assert all(0 <= a < n_bins for a in assign)
else:
    @pytest.mark.skip(reason="property tests need the dev extra "
                             "(pip install -e .[dev])")
    def test_property_suite_needs_hypothesis():
        pass
