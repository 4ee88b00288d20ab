"""The traffic generator is deterministic for a seed."""

import numpy as np
import pytest

from bench import spec, traffic


@pytest.fixture(scope="module")
def closed():
    return spec.traffic("mlt-closed")


def _same(a, b):
    return (a.like, a.k, a.probes) == (b.like, b.k, b.probes) and \
        np.array_equal(a.weights, b.weights)


def test_requests_repeat_for_a_seed(closed):
    big = 2**31 + 99
    a = traffic.RequestStream(closed, big, 53722, 3)
    b = traffic.RequestStream(closed, big, 53722, 3)
    assert all(_same(a[i], b[i]) for i in (5000, 0, 4095, 4096, 9000))
    other = traffic.RequestStream(closed, big + 1, 53722, 3)
    assert not all(_same(a[i], other[i]) for i in range(8))
    warm = traffic.RequestStream(closed, big, 53722, 3, traffic.WARM_STREAM)
    assert not all(_same(a[i], warm[i]) for i in range(8))


def test_request_values(closed):
    s = traffic.RequestStream(closed, 7, 100, 3)
    reqs = [s[i] for i in range(500)]
    assert all(0 <= r.like < 100 and r.k == 10 and r.probes == 12
               for r in reqs)
    w = np.stack([r.weights for r in reqs])
    assert w.dtype == np.float32 and np.allclose(w.sum(-1), 1, atol=1e-5)
    assert len({tuple(x) for x in w}) == len(w)


def test_validate_refuses_what_it_cannot_run(closed):
    traffic.validate(closed)
    with pytest.raises(ValueError):
        traffic.validate(dict(closed, loop="open"))
    with pytest.raises(ValueError):
        traffic.validate(dict(closed, clients=0))
    with pytest.raises(ValueError):
        traffic.validate(dict(closed, request=dict(closed["request"],
                                                   like="zipf")))
