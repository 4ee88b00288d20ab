"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing of the program. On a
TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line has an event per HLO operation run (its name is the HLO
text, ``%<op> = ...``) and whose ``XLA Modules`` line has an event per
program run (``jit_<function>(<fingerprint>)``); the host plane
(``/host:CPU``) has a line per thread with the spans the host recorded,
the harness's own ``TraceAnnotation`` spans among them. Device and host
events share one clock, in nanoseconds from the start of the trace.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# A host span longer than this is a bracket around the work, not the work
# (the window itself, a caller waiting on its answer); it labels no gap.
_LABEL_MAX_NS = 1e9


def op_name(event_name: str) -> str:
    """``'%fusion.3 = f32[...] fusion(...)'`` -> ``'fusion.3'``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``'jit_f(123)'`` -> ``'jit_f'``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_ns(starts, ends, t0: float, t1: float) -> np.ndarray:
    """Merged ``(m, 2)`` intervals of ``[starts, ends)`` clipped to
    ``[t0, t1)``."""
    s = np.clip(np.asarray(starts, np.float64), t0, t1)
    e = np.clip(np.asarray(ends, np.float64), t0, t1)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.r_[True, s[1:] > e[:-1]]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.r_[e[idx[1:] - 1], e[-1]]], axis=1)


class Trace:
    """Events of one trace: per device its ops and modules, and the host's
    spans, each an ``(names, starts, ends)`` triple in nanoseconds."""

    def __init__(self, ops: dict, modules: dict, host: tuple):
        self.ops = ops
        self.modules = modules
        self.host = host

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        ops, modules = {}, {}
        names, starts, ends = [], [], []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
                        target = ops if line.name == OPS_LINE else modules
                        target[plane.name] = _triple(ev)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    thread = line.name.split("/")[0]
                    for e in line.events:
                        names.append(f"{thread}: {e.name}")
                        starts.append(e.start_ns)
                        ends.append(e.start_ns + e.duration_ns)
        return cls(ops, modules, (names, np.asarray(starts, np.float64),
                                  np.asarray(ends, np.float64)))

    @classmethod
    def from_dir(cls, log_dir) -> "Trace":
        found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
        return cls.from_file(found[-1])

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def span(self, name: str) -> tuple[float, float]:
        """``(start, end)`` of the first host span called ``name``."""
        names, starts, ends = self.host
        for i, n in enumerate(names):
            if n.split(": ", 1)[-1] == name:
                return float(starts[i]), float(ends[i])
        raise KeyError(f"no host span {name!r} in the trace")

    def busy_ns(self, device: str, t0: float, t1: float) -> float:
        _, s, e = self.ops.get(device, ([], [], []))
        iv = union_ns(s, e, t0, t1)
        return float(np.sum(iv[:, 1] - iv[:, 0]))

    def busy_s(self, t0: float, t1: float) -> float:
        """Seconds in ``[t0, t1)`` in which some operation ran, averaged
        over the devices in the trace."""
        devs = self.devices
        if not devs:
            return 0.0
        return float(np.mean([self.busy_ns(d, t0, t1) for d in devs])) / 1e9

    def op_seconds(self, match, t0: float, t1: float) -> float:
        """Seconds of the ops whose HLO name satisfies ``match``, counted
        for those that start in ``[t0, t1)``, averaged over devices."""
        per = []
        for d in self.devices:
            names, s, e = self.ops[d]
            sel = np.asarray([bool(match(op_name(n))) for n in names], bool)
            sel &= (s >= t0) & (s < t1)
            per.append(float(np.sum(e[sel] - s[sel])))
        return float(np.mean(per)) / 1e9 if per else 0.0

    def top_modules(self, t0: float, t1: float, n: int = 10) -> list:
        """The ``n`` programs with the most device seconds in the window,
        summed over devices and over runs."""
        total: dict[str, float] = {}
        for names, s, e in self.modules.values():
            for name, a, b in zip(names, s, e):
                if t0 <= a < t1:
                    key = module_name(name)
                    total[key] = total.get(key, 0.0) + (b - a) / 1e9
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, t0: float, t1: float, n: int = 10) -> list:
        """Idle time of the first device in ``[t0, t1)`` by what the host
        was doing: each gap between its operations is labelled by the
        shortest host span (thread and name) open at the gap's midpoint,
        brackets longer than a second excluded. Returns the ``n`` labels
        with the most idle seconds."""
        devs = self.devices
        if not devs:
            return []
        _, s, e = self.ops[devs[0]]
        iv = union_ns(s, e, t0, t1)
        edges = np.concatenate([[t0], iv.ravel(), [t1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        mids = (gaps[:, 0] + gaps[:, 1]) / 2
        order = np.argsort(mids)
        mids, gaps = mids[order], gaps[order]
        names, hs, he = self.host
        dur = he - hs
        label = np.full(len(mids), -1)
        for i in np.argsort(dur, kind="stable"):
            if dur[i] > _LABEL_MAX_NS:
                break
            lo, hi = np.searchsorted(mids, [hs[i], he[i]])
            if hi > lo:
                free = label[lo:hi] < 0
                label[lo:hi][free] = i
        total: dict[str, float] = {}
        for g, i in zip(gaps, label):
            key = names[i][:100] if i >= 0 else "no host span"
            total[key] = total.get(key, 0.0) + (g[1] - g[0]) / 1e9
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]


def _triple(events):
    names = [n for n, _, _ in events]
    s = np.asarray([a for _, a, _ in events], np.float64)
    e = np.asarray([b for _, _, b in events], np.float64)
    return names, s, e
