"""``BENCHMARK.json`` keeps to its format, and every name in it resolves to
its file."""

import json
import re

import pytest

from bench import spec, traffic

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_entries(bench):
    assert set(bench) == KEYS
    assert set(bench["paths"]) == {"bench"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    assert len(json.dumps(bench)) <= 64 * 1024


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.fullmatch(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for c in bench["configs"]:
        assert all(NAME.fullmatch(k) for k in c["reduced"])


def test_every_name_resolves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(bench["workloads"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cfg = spec.config(w["config"])
        assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert cfg["reduced"] == entry["reduced"]
        traffic.validate(spec.traffic(w["traffic"]))
        assert {m["name"] for m in spec.metrics_for(w["name"], "end_to_end")
                } >= {"setup_s", "build_s", "recall"}
        assert spec.metrics_for(w["name"], "per_layer")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells


def test_unknown_device_is_an_error():
    assert spec.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks"):
        spec.peaks("TPU v99 imaginary")
