"""The closed load loop over ``SearchServer.submit``.

Adapted from the program's load test (``benchmarks/loadtest.py``), with two
changes of measure: each request is timed on the client's side, and the
loop runs for a window of wall time around which the caller opens and
closes its measurement, instead of over a fixed list of requests.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable


@dataclasses.dataclass
class Record:
    """One request as the client saw it."""

    index: int               # position in the run's request sequence
    sent: float              # perf_counter time submit was called
    done: float = float("nan")
    response: object = None  # the program's SearchResponse, or None
    error: str | None = None


def _span(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


async def _one(server, request, rec: Record, errors: tuple) -> None:
    try:
        rec.response = await server.submit(request)
    except errors as e:              # a refused or failed request: counted
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.perf_counter()


async def closed_loop(server, make: Callable[[int], object], *, clients: int,
                      ramp_s: float, seconds: float, errors: tuple,
                      on_window: Callable[[str], None]) -> tuple[list, float, float]:
    """``clients`` callers, each sending its next request (the next index
    of the run's sequence) once its last is answered. ``on_window("open")``
    runs after ``ramp_s`` and ``on_window("close")`` after ``seconds`` more;
    then no caller sends again and the loop waits for those in flight.
    Returns ``(records, window_start, window_end)``."""
    records: list[Record] = []
    counter = iter(range(1 << 62))
    stop = False

    async def caller():
        while not stop:
            i = next(counter)
            with _span("bench.generate"):
                request = make(i)
            now = time.perf_counter()
            rec = Record(i, now)
            records.append(rec)
            await _one(server, request, rec, errors)

    tasks = [asyncio.create_task(caller()) for _ in range(clients)]
    await asyncio.sleep(ramp_s)
    on_window("open")
    start = time.perf_counter()
    await asyncio.sleep(seconds)
    end = time.perf_counter()
    on_window("close")
    stop = True
    await asyncio.gather(*tasks)
    return records, start, end
