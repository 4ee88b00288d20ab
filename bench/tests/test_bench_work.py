"""``bench/work.py`` against hand counts on a toy index."""

import pytest

from bench import work

# T=2 clusterings of K=3 buckets, flat ids t*K + k, live members each.
COUNTS = [4, 0, 2, 5, 1, 3]
D = 8


def test_hand_counts():
    # dispatch 1: two requests, probes {0, 2} and {2, 3}: union {0, 2, 3}
    # dispatch 2: one request, probes {5}
    dispatches = [
        [([0, 2], 6 + 6), ([2, 3], 7 + 6)],
        [([5], 3 + 6)],
    ]
    ops, nbytes = work.kernel_work(dispatches, COUNTS, D, 4, n_leaders=6)
    assert ops == 2 * D * (6 + 7 + 3)
    assert nbytes == (4 + 2 + 5) * D * 4 + 2 * D * 4 + 3 * D * 4 + 1 * D * 4


def test_repeated_bucket_is_read_once_per_dispatch():
    once = work.kernel_work([[([1, 3], 5 + 6)]], COUNTS, D, 2, 6)[1]
    twice = work.kernel_work([[([3], 5 + 6), ([3], 5 + 6)]], COUNTS, D, 2, 6)
    assert twice[1] == 5 * D * 2 + 2 * D * 4
    assert once == 5 * D * 2 + D * 4


def test_roofline_bound_and_share():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    share, bound = work.roofline(50.0, 20.0, 4.0, peaks)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = work.roofline(800.0, 20.0, 4.0, peaks, n_devices=2)
    assert bound == "operations" and share == pytest.approx(100.0)
