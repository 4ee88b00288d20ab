"""``correct`` on the CPU at a tiny size: true for the program as it is,
false for each fault a cell can have, planted where the answer is made or
in the build's clustering, and false for the control."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell():
    return tiny.cell()


def _planted(monkeypatch, alter):
    """``alter(scores, ids, n_scored)`` applied where the engine answers."""
    from repro.core import engine

    real = engine.ReferenceEngine.search

    def search(self, qw, **kw):
        scores, ids, n_scored = real(self, qw, **kw)
        return alter(scores, ids, n_scored)

    monkeypatch.setattr(engine.ReferenceEngine, "search", search)


def test_sound_program_is_correct(cell):
    res = tiny.run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _one_answer_altered(monkeypatch):
    def alter(scores, ids, n):
        return scores, ids.at[0, 0].set((ids[0, 0] + 1) % 2000), n
    _planted(monkeypatch, alter)


def _half_batch_left_out(monkeypatch):
    def alter(scores, ids, n):
        half = max(1, ids.shape[0] // 2)
        keep = jnp.arange(ids.shape[0]) % half
        return scores[keep], ids[keep], n[keep]
    _planted(monkeypatch, alter)


def _leaders_at_random(monkeypatch):
    """FPF skipped: the sample's first K documents in a random order."""
    from repro.core import cluster

    def centers(self, xs, k, key):
        return jax.random.permutation(key, xs.shape[0])[:k]

    for cls in (cluster.FPFClusterer, cluster.FusedFPFClusterer):
        monkeypatch.setattr(cls, "_centers", centers)


def _assigned_at_fp8(monkeypatch):
    """Documents assigned to leaders by products of float8 operands."""
    from repro.core import cluster

    real = cluster.assign_to_centers

    def fp8(x):
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)

    def assign(x, reps, **kw):
        return real(fp8(x), fp8(reps), **kw)

    monkeypatch.setattr(cluster, "assign_to_centers", assign)


@pytest.mark.parametrize("plant,check", [
    (_one_answer_altered, "answer_gap"),
    (_half_batch_left_out, "answer_gap"),
    (_leaders_at_random, "leader_gap"),
    (_assigned_at_fp8, "assign_gap"),
], ids=["answer_altered", "half_batch_left_out", "leaders_at_random",
        "assigned_at_fp8"])
def test_fault_is_not_correct(cell, monkeypatch, plant, check):
    plant(monkeypatch)
    res = tiny.run(cell)
    assert not res["correct"]
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


def test_control_is_not_correct(cell):
    """The reference at the control's precision, in the program's place,
    comes out not correct through the same comparison, its answers and its
    assignment each over their limits, while the program's own readings of
    the same run are under them."""
    res = tiny.run(cell, control=True)
    assert not res["correct"]
    for name in ("answer_gap", "assign_gap"):
        c = res["checks"][name]
        assert c["value"] > c["limit"], name
        assert res["program"][name] <= c["limit"], name
    assert list(res)[-1] == "checks"


def test_gap_flags_duplicates_self_and_short_answers():
    ids = np.array([[3, 4, -1], [3, 3, 5], [9, 4, 5]])
    sc = np.zeros((3, 3), np.float32)
    fl = np.zeros((3, 3, 2), np.float32)
    ps = np.array([[0.0, 0.0, 0.0]] * 3)
    rp = np.zeros((3, 3))
    rf = np.zeros((3, 3, 2))
    gap = reference.answer_gaps(ids, sc, fl, np.array([1, 1, 9]), ps, rp, rf)
    assert np.all(np.isinf(gap))


def test_navigation_ties_may_go_either_way():
    """A probed bucket whose leader ties with the best left out is not one
    every sound navigation probes; a clear winner is."""
    qw = jnp.asarray([[1.0, 0.0]])
    leaders = jnp.asarray([[[1.0, 0.0], [0.5, 0.5], [0.5, 0.4], [0.5, 0.3],
                            [0.0, 1.0]]])
    flat, sure = reference._navigate(qw, leaders, (3,), reference.HIGHEST)
    assert np.asarray(flat)[0, 0] == 0
    assert np.asarray(sure).tolist() == [[True, False, False]]
    flat, sure = reference._navigate(qw, leaders, (1,), reference.HIGHEST)
    assert np.asarray(sure).tolist() == [[True]]
