"""The one generator every traffic file is read by.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next request when
    its last one is answered. (The only loop so far.)
``ramp_s``
    Seconds of the same traffic before the window opens, not counted.
``request``
    ``like``: how the more-like-this document is drawn (``"uniform"``, with
    replacement). ``weights_dirichlet_alpha``: the per-request field
    weights, continuous, so no two requests repeat. ``shapes``: ``k`` and
    ``probes`` with the ``share`` of requests that carry them.

Everything is drawn from the run's seed, each stream from its own child
generator, so the same seed gives the same requests.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Child streams of the run's seed.
_REQUESTS, WARM_STREAM = 0, 2

LOOPS = ("closed",)


@dataclasses.dataclass(frozen=True)
class Request:
    """One generated request, before it becomes the program's own type."""

    like: int
    weights: np.ndarray      # (fields,) float32
    k: int
    probes: int


def validate(traffic: dict) -> None:
    """Raise ValueError for a traffic file the generator cannot run."""
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}: {traffic.get('loop')}")
    if int(traffic.get("clients", 0)) < 1:
        raise ValueError("a closed loop needs clients >= 1")
    req = traffic["request"]
    if req.get("like") != "uniform":
        raise ValueError(f"request like must be 'uniform': {req.get('like')}")
    if not req.get("shapes") or any(
        s["k"] < 1 or s["probes"] < 1 or s["share"] <= 0 for s in req["shapes"]
    ):
        raise ValueError(f"request shapes need k, probes, share > 0: {req}")


_CHUNK = 4096


class RequestStream:
    """The run's requests by position, drawn ``_CHUNK`` at a time from a
    child generator of the seed per chunk, so request ``i`` is the same
    however many are drawn. ``stream`` keeps set-up's warm-up requests
    apart from the window's."""

    def __init__(self, traffic: dict, seed: int, n_docs: int, n_fields: int,
                 stream: int = _REQUESTS):
        self.traffic, self.seed, self.stream = traffic, int(seed), stream
        self.n_docs, self.n_fields = n_docs, n_fields
        self._chunks: dict[int, list[Request]] = {}

    def __getitem__(self, i: int) -> Request:
        c, j = divmod(int(i), _CHUNK)
        if c not in self._chunks:
            rng = np.random.default_rng([self.seed, self.stream, c])
            self._chunks[c] = _draw(rng, self.traffic, self.n_docs,
                                    self.n_fields, _CHUNK)
        return self._chunks[c][j]


def _draw(rng: np.random.Generator, traffic: dict, n_docs: int,
          n_fields: int, n: int) -> list[Request]:
    req = traffic["request"]
    shapes = req["shapes"]
    share = np.asarray([s["share"] for s in shapes], np.float64)
    pick = rng.choice(len(shapes), size=n, p=share / share.sum())
    likes = rng.integers(0, n_docs, size=n)
    alpha = float(req["weights_dirichlet_alpha"])
    w = rng.dirichlet([alpha] * n_fields, size=n).astype(np.float32)
    return [
        Request(int(likes[i]), w[i], int(shapes[pick[i]]["k"]),
                int(shapes[pick[i]]["probes"]))
        for i in range(n)
    ]
