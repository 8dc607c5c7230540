"""The replay's choice of blocks where the ranking is a near tie."""
import numpy as np

from bench.reference.tokendance import MAX_CHOICES, TIE, choices

INF = np.inf


def test_a_clear_ranking_allows_one_set():
    score = np.array([INF, 10.0, 5.0, 1.0, INF])
    assert [s.tolist() for s in choices(score, 3, TIE)] == [[0, 1, 4]]


def test_a_near_tie_at_the_cut_allows_both_sets_highest_first():
    score = np.array([INF, 10.0, 9.99, 1.0, INF])
    sets = [s.tolist() for s in choices(score, 3, TIE)]
    assert sets == [[0, 1, 4], [0, 2, 4]]
    # without a band only the ranking's own set
    assert [s.tolist() for s in choices(score, 3, 0.0)] == [[0, 1, 4]]


def test_many_near_ties_narrow_to_the_blocks_nearest_the_cut():
    score = np.array([5.0 + 0.01 * i for i in range(12)] + [1.0])
    sets = choices(score, 6, TIE)
    assert 1 < len(sets) <= MAX_CHOICES
    top = np.sort(np.argsort(-score)[:6])
    assert np.array_equal(sets[0], top)
    assert all(len(s) == 6 and len(set(s.tolist())) == 6 for s in sets)
