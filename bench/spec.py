"""Everything the harness finds by name: ``BENCHMARK.json``, and beside it
one file per configuration, traffic mix, per-layer metric reader and the
table of peaks. Adding a cell takes new files and entries, never an edit."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> Path:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return json.loads(config_file(name).read_text())


def traffic_file(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def traffic(name: str) -> dict:
    return json.loads(traffic_file(name).read_text())


def metric_file(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def metric_reader(name: str):
    """The ``read(reading) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = metric_file(name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise ValueError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


def metrics_for(cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those that list no cells."""
    return [
        m for m in benchmark()[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]
