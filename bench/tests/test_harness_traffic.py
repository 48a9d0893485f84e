"""The traffic generators: the same seed gives the same work, and every
seed the same amount of it."""
import numpy as np

from bench.traffic import offline_batches, open_poisson


def test_offline_prompts_repeat_for_a_seed():
    a = offline_batches.prompts(2**31 + 7, 3, 4, 16, 100)
    b = offline_batches.prompts(2**31 + 7, 3, 4, 16, 100)
    c = offline_batches.prompts(2**31 + 8, 3, 4, 16, 100)
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 100


def test_offline_plan_cycles_the_lengths():
    mix = {"prompt_lens": [128, 256, 512]}
    assert offline_batches.plan(mix, 5) == [(0, 128), (1, 256), (2, 512),
                                            (3, 128), (4, 256)]


def test_poisson_arrivals_same_set_in_seeded_order():
    a = open_poisson.arrivals(500.0, 2.0, 11)
    b = open_poisson.arrivals(500.0, 2.0, 11)
    c = open_poisson.arrivals(500.0, 2.0, 2**33 + 1)
    assert np.array_equal(a, b)
    assert len(a) == len(c) == 1000
    assert not np.array_equal(a, c)
    gaps_a = np.sort(np.diff(a, prepend=0.0))
    gaps_c = np.sort(np.diff(c, prepend=0.0))
    assert np.allclose(gaps_a, gaps_c)      # the same gaps, in another order
    assert np.all(np.diff(a) > 0)
    assert abs(a[-1] - c[-1]) < 1e-9 and 1.99 < a[-1] <= 2.0
